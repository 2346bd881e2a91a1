"""BASELINE.json config 4: topk over high cardinality.

    topk(5, sum by (app)(rate(cpu_seconds_total[1m])))
    over 100K series / 128 shards

The reference's comparable workload is ``QueryHiCardInMemoryBenchmark``
(``jmh/src/main/scala/filodb.jmh/QueryHiCardInMemoryBenchmark.scala``).
Runs the full path — index lookup across 128 shards → chunk decode → rate
kernels → grouped sum → topk — through the exec engine and (all-shards-local)
the device-mesh engine, reporting throughput and latency percentiles.

    python benchmarks/topk_hicard.py [--series 100000] [--shards 128]
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
QUERY = 'topk(5, sum by (app)(rate(cpu_seconds_total[1m])))'


def build(num_series: int, num_shards: int, n_samples: int, n_apps: int):
    from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.core.partkey import (
        METRIC_LABEL,
        PartKey,
        ingestion_shard,
        shard_key_hash,
    )
    from filodb_tpu.core.record import (
        BytesContainer,
        IngestRecord,
        RecordContainer,
        SomeData,
    )
    from filodb_tpu.core.store.config import StoreConfig

    ms = TimeSeriesMemStore()
    for s in range(num_shards):
        ms.setup("hicard", s, StoreConfig(max_chunk_size=120,
                                          groups_per_shard=4))
    rng = np.random.default_rng(9)
    # pre-route records per shard (the gateway's job), then ingest bytes
    per_shard: dict[int, RecordContainer] = {s: RecordContainer()
                                             for s in range(num_shards)}
    keys = []
    for i in range(num_series):
        key = PartKey.create("prom-counter", {
            METRIC_LABEL: "cpu_seconds_total", "_ws_": "demo",
            "_ns_": f"App-{i % n_apps}", "app": f"app-{i % n_apps}",
            "instance": str(i)})
        keys.append(key)
    spread = 7  # 2^7 = 128: hicard metrics spread over every shard
    shards = [ingestion_shard(
        shard_key_hash({lbl: k.label_map.get(lbl, "")
                        for lbl in ("_ws_", "_ns_", METRIC_LABEL)}),
        k.part_hash, num_shards, spread) for k in keys]
    rows = 0
    offset = 0
    t0 = time.perf_counter()
    incr = rng.integers(1, 50, num_series)
    for t in range(n_samples):
        ts = (START + t * 10) * 1000
        for i, key in enumerate(keys):
            per_shard[shards[i]].add(
                IngestRecord(key, ts, (float((t + 1) * incr[i]),)))
        for s, cont in per_shard.items():
            if len(cont):
                ms.get_shard("hicard", s).ingest(
                    SomeData(BytesContainer(cont.serialize()), offset))
                offset += 1
                rows += len(cont)
        per_shard = {s: RecordContainer() for s in range(num_shards)}
    build_dt = time.perf_counter() - t0
    return ms, rows, build_dt


def run_queries(svc, n: int, start_sec: int, end_sec: int, step: int = 60):
    lat = []
    for _ in range(n):
        t0 = time.perf_counter()
        r = svc.query_range(QUERY, start_sec, step, end_sec)
        lat.append(time.perf_counter() - t0)
        assert r.result.num_series == 5, r.result.num_series
    lat = np.asarray(lat)
    return {
        "qps": round(n / lat.sum(), 2),
        "p50_ms": round(float(np.percentile(lat, 50)) * 1000, 1),
        "p99_ms": round(float(np.percentile(lat, 99)) * 1000, 1),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--series", type=int, default=100_000)
    ap.add_argument("--shards", type=int, default=128)
    ap.add_argument("--samples", type=int, default=60)  # 10min @ 10s
    ap.add_argument("--apps", type=int, default=100)
    ap.add_argument("--queries", type=int, default=30)
    args = ap.parse_args(argv)
    from filodb_tpu.coordinator.query_service import QueryService

    ms, rows, build_dt = build(args.series, args.shards, args.samples,
                               args.apps)
    start_sec = START + 120
    end_sec = START + args.samples * 10 - 60

    out = {"metric": "topk_hicard", "series": args.series,
           "shards": args.shards, "samples_ingested": rows,
           "ingest_samples_per_sec": round(rows / build_dt),
           "query": QUERY}
    svc = QueryService(ms, "hicard", args.shards, spread=7)
    svc.query_range(QUERY, start_sec, 60, end_sec)  # warm/compile
    out["exec_engine"] = run_queries(svc, args.queries, start_sec, end_sec)

    mesh_svc = QueryService(ms, "hicard", args.shards, spread=7,
                            engine="mesh")
    if mesh_svc.mesh_engine is not None and mesh_svc._mesh_eligible():
        mesh_svc.query_range(QUERY, start_sec, 60, end_sec)
        out["mesh_engine"] = run_queries(mesh_svc, args.queries, start_sec,
                                         end_sec)
    print(json.dumps(out))


if __name__ == "__main__":
    sys.exit(main())
