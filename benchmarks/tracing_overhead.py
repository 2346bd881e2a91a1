"""Tracing overhead: sampled vs unsampled query latency.

Dashboard-style workload (8192 gauge series over 4 shards, the panel mix
from ``serving.py --dashboard``) run twice through the same QueryService:
once with ``sample_rate=0.0`` (head sampler declines every query; span()
calls are thread-local no-ops) and once with ``sample_rate=1.0`` (every
query builds a full span tree and feeds the stage histograms). The delta
is what tracing costs; the unsampled path is the one production serves at
low sample rates, so its overhead must stay in the noise (<2% p50 target).

A micro-bench of the no-op ``span()`` path is included so the per-call
cost of dormant instrumentation is visible independently of query noise.

    python benchmarks/tracing_overhead.py [--series 8192]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

START = 1_600_000_000


def bench_tracing_overhead(series: int = 8192, refreshes: int = 3):
    from filodb_tpu.coordinator.ingestion import ingest_routed
    from filodb_tpu.coordinator.query_service import QueryService
    from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.core.store.config import StoreConfig
    from filodb_tpu.query.model import PlannerParams, QueryContext
    from filodb_tpu.testing.data import gauge_stream, machine_metrics_series
    from filodb_tpu.utils import tracing

    num_shards = 4
    interval_ms = 30_000
    step = 60
    base_samples = 240                   # 2h of history
    window_s = 3_600                     # 1h dashboard window
    ms = TimeSeriesMemStore()
    for s in range(num_shards):
        ms.setup("timeseries", s,
                 StoreConfig(max_chunk_size=400, groups_per_shard=4,
                             retention_ms=10**15))
    half = series // 2
    for kk in (machine_metrics_series(half, ns="App-2"),
               machine_metrics_series(series - half, ns="App-3")):
        ingest_routed(ms, "timeseries",
                      gauge_stream(kk, base_samples, start_ms=START * 1000,
                                   interval_ms=interval_ms, seed=9),
                      num_shards, spread=1)

    svc = QueryService(ms, "timeseries", num_shards, spread=1)
    panels = [
        "sum(rate(heap_usage[5m]))",
        "sum by (host) (rate(heap_usage[5m]))",
        "avg_over_time(heap_usage[5m])",
        "max_over_time(heap_usage[10m])",
        "max by (host) (avg_over_time(heap_usage[5m]))",
    ]
    qe0 = START + (base_samples - 1) * interval_ms // 1000

    def run_panel(promql, qe):
        ctx = QueryContext(
            planner_params=PlannerParams(sample_limit=50_000_000))
        t0 = time.perf_counter()
        svc.query_range(promql, qe - window_s, step, qe, ctx)
        return time.perf_counter() - t0

    prev = {f: getattr(tracing.config(), f)
            for f in ("sample_rate", "slow_query_threshold_ms",
                      "slowlog_capacity")}
    lat = {"unsampled": [], "sampled": []}
    try:
        # warm compile caches so neither mode pays tracing-unrelated
        # first-run costs
        for promql in panels:
            run_panel(promql, qe0)
        for refresh in range(refreshes):
            qe = qe0 + refresh * step
            # alternate mode order per refresh so drift (cache warmth,
            # allocator state) doesn't bias one side
            modes = [("unsampled", 0.0), ("sampled", 1.0)]
            if refresh % 2:
                modes.reverse()
            for name, rate in modes:
                tracing.configure(sample_rate=rate,
                                  slow_query_threshold_ms=10**9,
                                  slowlog_capacity=8)
                for promql in panels:
                    lat[name].append(run_panel(promql, qe))
    finally:
        tracing.configure(**prev)
        tracing.flight_recorder().clear()

    # dormant-instrumentation micro: span() with no active trace
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        with tracing.span("noop"):
            pass
    noop_ns = (time.perf_counter() - t0) / n * 1e9

    def pct(xs, p):
        return round(float(np.percentile(np.array(xs), p)) * 1000, 2)

    un_p50, sa_p50 = pct(lat["unsampled"], 50), pct(lat["sampled"], 50)
    return {
        "metric": "tracing_overhead",
        "series": series,
        "panels": len(panels),
        "refreshes": refreshes,
        "unsampled_p50_ms": un_p50,
        "unsampled_p99_ms": pct(lat["unsampled"], 99),
        "sampled_p50_ms": sa_p50,
        "sampled_p99_ms": pct(lat["sampled"], 99),
        "sampled_overhead_pct": round(
            (sa_p50 - un_p50) / max(un_p50, 1e-9) * 100, 2),
        "noop_span_ns": round(noop_ns, 1),
        "unit": "ms",
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--series", type=int, default=8192)
    ap.add_argument("--refreshes", type=int, default=3)
    args = ap.parse_args(argv)
    print(json.dumps(bench_tracing_overhead(args.series, args.refreshes)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
