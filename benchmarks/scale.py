"""Series-scale benchmark: how many actively-ingesting series one node holds.

The reference claims ~1M+ actively ingesting series per node, memory-bound
(``README.md:409-413``). This benchmark ingests N series with a few samples
each, reports per-series memory and sustained ingest rate at that
cardinality, then runs an indexed query over a 1%-of-N shard-key slice.

    python benchmarks/scale.py [--series 1000000]
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import resource
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

START = 1_600_000_000


def rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--series", type=int, default=1_000_000)
    ap.add_argument("--samples", type=int, default=5)
    args = ap.parse_args(argv)
    from filodb_tpu.coordinator.query_service import QueryService
    from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.core.partkey import METRIC_LABEL, PartKey
    from filodb_tpu.core.record import (
        BytesContainer,
        IngestRecord,
        RecordContainer,
        SomeData,
    )
    from filodb_tpu.core.store.config import StoreConfig

    from filodb_tpu.core.store.api import (
        InMemoryColumnStore,
        InMemoryMetaStore,
    )
    ms = TimeSeriesMemStore(InMemoryColumnStore(), InMemoryMetaStore())
    # small chunk size bounds the per-series write-buffer footprint, the way
    # the reference sizes WriteBufferPool appenders for high cardinality
    shard = ms.setup("scale", 0, StoreConfig(max_chunk_size=64,
                                             groups_per_shard=64))
    rss0 = rss_mb()
    n = args.series
    batch = 20_000

    # Containers arrive as serialized bytes (gateway → log → shard), so the
    # timed region is shard ingest of container BYTES — record building is
    # the producer's cost (reference IngestionBenchmark likewise ingests
    # pre-built containers). Bytes are built per batch outside the timer.
    def batch_bytes(s: int, lo: int, hi: int) -> bytes:
        c = RecordContainer()
        for i in range(lo, hi):
            key = PartKey.create("gauge", {
                METRIC_LABEL: "scale_metric", "_ws_": "w",
                "_ns_": f"ns-{i % 100}", "instance": str(i)})
            c.add(IngestRecord(key, (START + s * 10) * 1000, (float(i),)))
        return c.serialize()

    create_dt = 0.0
    for lo in range(0, n, batch):
        raw = batch_bytes(0, lo, min(lo + batch, n))
        t0 = time.perf_counter()
        shard.ingest(SomeData(BytesContainer(raw), lo // batch))
        create_dt += time.perf_counter() - t0

    # steady-state: more samples for every series
    steady_dt = 0.0
    rows = 0
    for s in range(1, args.samples):
        for lo in range(0, n, batch):
            raw = batch_bytes(s, lo, min(lo + batch, n))
            t0 = time.perf_counter()
            rows += shard.ingest(SomeData(BytesContainer(raw),
                                          s * 1000 + lo // batch))
            steady_dt += time.perf_counter() - t0
    gc.collect()
    rss1 = rss_mb()

    svc = QueryService(ms, "scale", 1, spread=0)
    t0 = time.perf_counter()
    r = svc.query_range('count(scale_metric{_ns_="ns-7"})',
                        START + args.samples * 10, 60,
                        START + args.samples * 10)
    q_dt = time.perf_counter() - t0

    # restart: index snapshot write + snapshot-restored recover
    # (reference target: Lucene index ready without a full part-key scan)
    t0 = time.perf_counter()
    snap_bytes = shard.snapshot_index()
    snap_dt = time.perf_counter() - t0
    ms3 = TimeSeriesMemStore(ms.column_store, ms.meta_store)
    t0 = time.perf_counter()
    s3 = ms3.setup("scale", 0, StoreConfig(max_chunk_size=64,
                                           groups_per_shard=64))
    restored = s3.recover_index()
    restart_dt = time.perf_counter() - t0

    out = {
        "series": n,
        "create_series_per_sec": round(n / create_dt),
        "steady_ingest_samples_per_sec": round(rows / steady_dt)
        if rows else None,
        "per_series_bytes": round((rss1 - rss0) * 1024 * 1024 / n),
        "rss_mb": round(rss1, 1),
        "slice_query_series": int(r.result.values[0, 0]),
        "slice_query_sec": round(q_dt, 3),
        "index_snapshot_mb": round(snap_bytes / 1e6, 1),
        "index_snapshot_write_sec": round(snap_dt, 2),
        "restart_index_ready_sec": round(restart_dt, 2),
        "restart_series_restored": restored,
    }
    print(json.dumps(out))


if __name__ == "__main__":
    sys.exit(main())
