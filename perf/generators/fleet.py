"""``fleet``: an operator's fleet of scraped targets — per-instance
``cpu_seconds_total`` counters and ``heap_usage`` gauges, ``apps`` apps,
every target at its own scrape phase, a share of the counters restarting
once. A copy of ``chip_smoke.make_metric``/``make_data`` (PR 21) that returns
label columns instead of the program's ``PartKey`` objects, so that the
reference never sees the program's types."""

from __future__ import annotations

import numpy as np


def _metric(name, schema, n_series, p, rng) -> dict:
    app = np.arange(n_series) % p["apps"]
    inst = np.arange(n_series)
    labels = {"_ws_": np.full(n_series, "demo"),
              "_ns_": np.char.add("App-", app.astype(str)),
              "app": np.char.add("app-", app.astype(str)),
              "instance": np.char.add("inst-", inst.astype(str))}
    samples, interval = p["samples"], p["interval_ms"]
    # every target is scraped at its own phase of the interval
    phase = rng.integers(0, interval, n_series)
    ts = (p["t0_sec"] * 1000 + phase[:, None]
          + np.arange(samples, dtype=np.int64)[None, :] * interval)
    if schema == "prom-counter":
        # busier apps count faster
        incr = rng.integers(0, (10 + app)[:, None],
                            (n_series, samples)).astype(np.float64)
        vals = np.cumsum(incr, axis=1)
        # a share of the counters restarts once: it falls back to zero
        for i in np.nonzero(rng.random(n_series) < p["restart_share"])[0]:
            at = int(rng.integers(1, samples))
            vals[i, at:] -= vals[i, at - 1]
    else:
        vals = 50.0 + np.cumsum(rng.normal(0.0, 1.0, (n_series, samples)),
                                axis=1)
    return {"name": name, "schema": schema, "labels": labels, "ts": ts,
            "vals": vals}


def make(params: dict, seed: int) -> dict:
    """{metric name: {"name", "schema", "labels": {label: str [N]},
    "ts": int64 ms [N, S], "vals": f64 [N, S]}}, from the seed."""
    rng = np.random.default_rng(seed)
    out = {}
    for name, schema, count in (
            ("cpu_seconds_total", "prom-counter", params["counter_series"]),
            ("heap_usage", "gauge", params["gauge_series"])):
        out[name] = _metric(name, schema, count, params, rng)
    return out
