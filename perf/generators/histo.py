"""``histo``: a fleet of services that each export one first-class latency
histogram — ``apps`` x ``instances`` series of ``http_req_latency``, schema
``prom-histogram`` (``sum``, ``count``, ``h``), ``buckets`` geometric ``le``s
from ``le_first_s`` to ``le_last_s`` and ``+Inf``, cumulative int64 bucket
counts that only rise, a share of the targets restarting once (every column
falls back to zero), every target at its own scrape phase: ``fleet.py``'s
habits, upstream's histogram dev source in shape. Made in bulk: one Poisson
draw a (series, scrape, bucket), two running sums."""

from __future__ import annotations

import math

import numpy as np


def _bucket_shares(les, median_s, sigma) -> np.ndarray:
    """Share of a log-normal latency in each bucket: f64 [N, B]."""
    z = (np.log(les[None, :-1]) - np.log(median_s)[:, None]) \
        / (sigma * math.sqrt(2.0))
    cdf = np.concatenate([0.5 * (1.0 + np.vectorize(math.erf)(z)),
                          np.ones((len(median_s), 1))], axis=1)
    return np.diff(cdf, axis=1, prepend=0.0)


def make(params: dict, seed: int) -> dict:
    """{"http_req_latency": {"name", "schema", "labels": {label: str [N]},
    "ts": int64 ms [N, S], "vals": {"sum": f64 [N, S], "count": f64 [N, S],
    "h": {"les": f64 [B], "counts": int64 [N, S, B]}}}}, from the seed."""
    rng = np.random.default_rng(seed)
    apps, n = params["apps"], params["apps"] * params["instances"]
    samples, interval = params["samples"], params["interval_ms"]
    nb = params["buckets"]
    app = np.arange(n) % apps
    labels = {"_ws_": np.full(n, "demo"),
              "_ns_": np.char.add("App-", app.astype(str)),
              "app": np.char.add("app-", app.astype(str)),
              "instance": np.char.add("inst-", np.arange(n).astype(str))}
    phase = rng.integers(0, interval, n)
    ts = (params["t0_sec"] * 1000 + phase[:, None]
          + np.arange(samples, dtype=np.int64)[None, :] * interval)
    les = np.concatenate([np.geomspace(params["le_first_s"],
                                       params["le_last_s"], nb - 1),
                          [np.inf]])
    # busier apps answer more requests a scrape; every instance has its own
    # median latency, around 20 ms, a decade wide
    per_scrape = (10.0 + app) * rng.uniform(0.8, 1.25, n)
    median_s = 0.02 * np.exp(rng.normal(0.0, 0.5, n))
    lam = per_scrape[:, None] * _bucket_shares(les, median_s,
                                               params["latency_sigma"])
    new = rng.poisson(lam[:, None, :], (n, samples, nb))  # int64
    # what a request cost, for ``sum``: its bucket's bound (twice the last
    # finite one in +Inf)
    cost = np.concatenate([les[:-1], [2.0 * les[-2]]])
    sums = np.cumsum(new @ cost, axis=1)
    counts = np.cumsum(np.cumsum(new, axis=1, out=new), axis=2, out=new)
    for i in np.nonzero(rng.random(n) < params["restart_share"])[0]:
        at = int(rng.integers(1, samples))
        counts[i, at:] -= counts[i, at - 1]
        sums[i, at:] -= sums[i, at - 1]
    return {"http_req_latency": {
        "name": "http_req_latency", "schema": "prom-histogram",
        "labels": labels, "ts": ts,
        "vals": {"sum": sums,
                 "count": counts[:, :, -1].astype(np.float64),
                 "h": {"les": les, "counts": counts}}}}
