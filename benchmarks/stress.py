"""Soak / stress harness.

Counterpart of the reference ``stress/`` module (``IngestionStress``,
``MemStoreStress`` — Spark-driven soak jobs, disabled in the reference
build): sustained high-cardinality ingest with series churn, concurrent
queries, periodic flush + memory-pressure eviction + TTL purge, asserting
invariants throughout. Run manually:

    python benchmarks/stress.py [--seconds 30] [--series 5000]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

START = 1_600_000_000


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--series", type=int, default=2000)
    ap.add_argument("--device-pages", action="store_true")
    args = ap.parse_args(argv)
    from filodb_tpu.coordinator.query_service import QueryService
    from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.core.partkey import PartKey
    from filodb_tpu.core.record import IngestRecord, RecordContainer, SomeData
    from filodb_tpu.core.store.api import (
        InMemoryColumnStore,
        InMemoryMetaStore,
    )
    from filodb_tpu.core.store.config import StoreConfig

    ms = TimeSeriesMemStore(InMemoryColumnStore(), InMemoryMetaStore())
    shard = ms.setup("stress", 0, StoreConfig(
        max_chunk_size=200, groups_per_shard=8, flush_task_parallelism=4,
        device_pages=args.device_pages))
    svc = QueryService(ms, "stress", 1, spread=0)
    stop = threading.Event()
    errors: list[str] = []
    stats = {"rows": 0, "queries": 0, "flushes": 0, "evictions": 0,
             "churned": 0}

    def ingester():
        rng = np.random.default_rng(0)
        t = START * 1000
        gen = 0
        while not stop.is_set():
            c = RecordContainer()
            for i in range(args.series):
                # churn: 10% of series rotate identity every pass
                sid = i if i % 10 else f"{i}g{gen}"
                key = PartKey.create("gauge", {
                    "_metric_": "stress_metric", "_ws_": "w", "_ns_": "n",
                    "instance": str(sid)})
                c.add(IngestRecord(key, t, (float(rng.normal(50, 10)),)))
            try:
                shard.ingest(SomeData(c, gen))
                stats["rows"] += len(c)
                stats["churned"] += args.series // 10
            except Exception as e:  # pragma: no cover
                errors.append(f"ingest: {e!r}")
                return
            t += 10_000
            gen += 1

    def maintainer():
        while not stop.is_set():
            time.sleep(0.5)
            try:
                shard.flush_group(shard.next_flush_group())
                stats["flushes"] += 1
                stats["evictions"] += shard.enforce_memory(
                    budget_bytes=64 * 1024 * 1024)
                # purge with a "now" aligned to the synthetic data clock
                data_now = (START + stats["rows"] // max(args.series, 1)
                            * 10) * 1000
                shard.purge_expired(data_now)
            except Exception as e:  # pragma: no cover
                errors.append(f"maintain: {e!r}")
                return

    def querier():
        while not stop.is_set():
            try:
                horizon = START + stats["rows"] // max(args.series, 1) * 10
                r = svc.query_range(
                    "sum(sum_over_time(stress_metric[5m]))",
                    horizon, 60, horizon + 60)
                if r.result.num_series > 1:
                    errors.append("aggregation produced >1 series")
                stats["queries"] += 1
            except Exception as e:
                errors.append(f"query: {e!r}")
                return

    threads = [threading.Thread(target=f, daemon=True)
               for f in (ingester, maintainer, querier)]
    for th in threads:
        th.start()
    time.sleep(args.seconds)
    stop.set()
    for th in threads:
        th.join(timeout=10)

    ok = not errors
    print(json.dumps({"ok": ok, "errors": errors[:5], **stats,
                      "partitions": shard.num_partitions}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
