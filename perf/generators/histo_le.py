"""``histo_le``: ``histo.py``'s fleet in the classic Prometheus layout, as
every client library exports a histogram and remote-write sends it — each
bucket its own ``prom-counter`` series ``http_req_latency_bucket`` with an
``le`` label, and beside them ``http_req_latency_sum`` and
``http_req_latency_count``. ``histo.py`` is loaded by path and draws the
latencies, so both deployments share one latency model: the same seed and
sizes give the same counts in either layout."""

from __future__ import annotations

import importlib.util
import os

import numpy as np


def _histo():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "histo.py")
    spec = importlib.util.spec_from_file_location("perf_generators_histo",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def le_text(les) -> np.ndarray:
    """Each bound as its ``le`` label: ``repr`` of the f64, ``+Inf``."""
    return np.array(["+Inf" if np.isposinf(le) else repr(float(le))
                     for le in les])


def make(params: dict, seed: int) -> dict:
    """{"http_req_latency_bucket", "http_req_latency_sum",
    "http_req_latency_count": {"name", "schema": "prom-counter", "labels":
    {label: str [N]}, "ts": int64 ms [N, S], "vals": f64 [N, S]}}, from the
    seed. The bucket series are an instance's ``buckets`` rows one after
    another (row ``i·B + b``), each with the instance's scrape times."""
    h = _histo().make(params, seed)["http_req_latency"]
    vals, labels, ts = h["vals"], h["labels"], h["ts"]
    les, counts = vals["h"]["les"], vals["h"]["counts"]
    n, samples, nb = counts.shape
    bucket_labels = {k: np.repeat(v, nb) for k, v in labels.items()}
    bucket_labels["le"] = np.tile(le_text(les), n)
    rows = np.empty((n * nb, samples), np.float64)
    rows.reshape(n, nb, samples)[:] = counts.transpose(0, 2, 1)
    del counts, vals["h"]

    def counter(name, labs, times, v):
        return {"name": name, "schema": "prom-counter", "labels": labs,
                "ts": times, "vals": v}

    return {"http_req_latency_bucket": counter(
                "http_req_latency_bucket", bucket_labels,
                np.repeat(ts, nb, axis=0), rows),
            "http_req_latency_sum": counter(
                "http_req_latency_sum", labels, ts, vals["sum"]),
            "http_req_latency_count": counter(
                "http_req_latency_count", labels, ts, vals["count"])}
