"""Benchmark suite: reproductions of the reference's JMH workloads.

Counterpart of ``jmh/src/main/scala/filodb.jmh/`` (see SURVEY.md §6 /
``run_benchmarks.sh``). Each benchmark prints one JSON line; run all with

    python benchmarks/run_benchmarks.py [--only NAME]

Workload definitions mirror the JMH classes:
- ingestion        — ``IngestionBenchmark``: 100k samples through the shard
  ingest path, samples/sec.
- hist_ingest      — ``HistogramIngestBenchmark``: 30k first-class histograms.
- query_inmemory   — ``QueryInMemoryBenchmark``: handled by ../bench.py.
- query_hicard     — ``QueryHiCardInMemoryBenchmark``: 1 shard, 5k series.
- query_and_ingest — ``QueryAndIngestBenchmark``: queries under concurrent
  ingest.
- hist_query       — ``HistogramQueryBenchmark``: histogram_quantile of rate.
- partkey_index    — ``PartKeyIndexBenchmark``: index add + filter queries.
- gateway          — ``GatewayBenchmark``: Influx line parse ops/sec.
- encoding         — ``EncodingBenchmark``: vector encode/decode ops.
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


def bench_ingestion():
    from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.core.store.config import StoreConfig
    from filodb_tpu.testing.data import gauge_stream, machine_metrics_series

    ms = TimeSeriesMemStore()
    shard = ms.setup("bench", 0, StoreConfig(max_chunk_size=400))
    keys = machine_metrics_series(100)
    # shard-ingest of pre-built binary containers (the gateway→log→shard
    # contract; reference IngestionBenchmark likewise pre-builds records)
    from filodb_tpu.core.record import BytesContainer, SomeData
    stream = [SomeData(BytesContainer(sd.container.serialize()), sd.offset)
              for sd in gauge_stream(keys, 1000, start_ms=START * 1000,
                                     batch=500)]
    t0 = time.perf_counter()
    for sd in stream:
        shard.ingest(sd)
    dt = time.perf_counter() - t0
    native = shard._native_core is not None
    return {"metric": "ingestion_throughput", "value": round(100_000 / dt),
            "unit": "samples/sec", "native_lane": native}


def bench_hist_ingest():
    from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.core.store.config import StoreConfig
    from filodb_tpu.testing.data import histogram_series, histogram_stream

    ms = TimeSeriesMemStore()
    shard = ms.setup("bench", 0, StoreConfig(max_chunk_size=400))
    keys = histogram_series(30)
    # binary containers (gateway->log->shard contract) take the C++ hist
    # ingest lane
    from filodb_tpu.core.record import BytesContainer, SomeData
    stream = [SomeData(BytesContainer(sd.container.serialize()), sd.offset)
              for sd in histogram_stream(keys, 1000, start_ms=START * 1000,
                                         batch=500)]
    t0 = time.perf_counter()
    for sd in stream:
        shard.ingest(sd)
    dt = time.perf_counter() - t0
    native = shard._native_core is not None
    return {"metric": "histogram_ingestion_throughput",
            "value": round(30_000 / dt), "unit": "histograms/sec",
            "native_lane": native}


def bench_query_hicard():
    from filodb_tpu.coordinator.query_service import QueryService
    from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.core.store.config import StoreConfig
    from filodb_tpu.testing.data import counter_series, counter_stream

    ms = TimeSeriesMemStore()
    shard = ms.setup("bench", 0, StoreConfig(max_chunk_size=400))
    keys = counter_series(5000, metric="hicard_total")
    for sd in counter_stream(keys, 60, start_ms=START * 1000, batch=5000):
        shard.ingest(sd)
    svc = QueryService(ms, "bench", 1, spread=0, engine="mesh")
    q = 'sum(rate(hicard_total[5m]))'
    svc.query_range(q, START + 300, 60, START + 540)  # warm
    n = 20
    t0 = time.perf_counter()
    for _ in range(n):
        r = svc.query_range(q, START + 300, 60, START + 540)
    dt = time.perf_counter() - t0
    return {"metric": "hicard_query_throughput", "value": round(n / dt, 2),
            "unit": "queries/sec", "series": 5000}


def bench_query_and_ingest():
    import threading

    from filodb_tpu.coordinator.query_service import QueryService
    from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.core.store.config import StoreConfig
    from filodb_tpu.testing.data import counter_series, counter_stream

    ms = TimeSeriesMemStore()
    shard = ms.setup("bench", 0, StoreConfig(max_chunk_size=400))
    keys = counter_series(100, metric="qi_total")
    for sd in counter_stream(keys, 720, start_ms=START * 1000):
        shard.ingest(sd)
    svc = QueryService(ms, "bench", 1, spread=0, engine="mesh")
    q = 'sum(rate(qi_total[5m]))'
    svc.query_range(q, START + 3600, 60, START + 5400)
    stop = threading.Event()

    def ingester():
        t = START + 7200
        while not stop.is_set():
            for sd in counter_stream(keys, 10, start_ms=t * 1000, batch=1000):
                shard.ingest(sd)
            t += 100

    th = threading.Thread(target=ingester, daemon=True)
    th.start()
    n = 50
    t0 = time.perf_counter()
    for _ in range(n):
        svc.query_range(q, START + 3600, 60, START + 5400)
    dt = time.perf_counter() - t0
    stop.set()
    th.join(1)
    return {"metric": "query_under_ingest_throughput",
            "value": round(n / dt, 2), "unit": "queries/sec"}


def bench_hist_flat_vs_first_class():
    """First-class histogram columns vs prom-flat bucket-per-series — the
    reference's headline histogram claim (README.md:437: "up to two orders
    of magnitude")."""
    from filodb_tpu.coordinator.query_service import QueryService
    from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.core.partkey import PartKey
    from filodb_tpu.core.record import IngestRecord, RecordContainer, SomeData
    from filodb_tpu.core.store.config import StoreConfig
    from filodb_tpu.testing.data import histogram_series, histogram_stream

    # the reference's claim regime is high-bucket-count histograms
    # (README.md:437); 64 buckets matches its quoted hist shapes
    n_series, n_samples, nb = 96, 240, 64

    # first-class
    ms1 = TimeSeriesMemStore()
    ms1.setup("bench", 0, StoreConfig(max_chunk_size=400))
    for sd in histogram_stream(histogram_series(n_series), n_samples,
                               start_ms=START * 1000, batch=2000):
        ms1.get_shard("bench", 0).ingest(sd)
    svc1 = QueryService(ms1, "bench", 1, spread=0, engine="mesh")
    q1 = 'histogram_quantile(0.99, sum(rate(http_req_latency[5m])))'

    # prom-flat: same data as bucket-per-series counters
    ms2 = TimeSeriesMemStore()
    ms2.setup("bench", 0, StoreConfig(max_chunk_size=400))
    rng = np.random.default_rng(0)
    c = RecordContainer()
    flat_keys = [[PartKey.create("prom-counter", {
        "_metric_": "lat_bucket", "_ws_": "demo", "_ns_": "App-0",
        "instance": f"i{s}", "le": str(float(b + 1))})
        for b in range(nb)] for s in range(n_series)]
    for s in range(n_series):
        cum = np.zeros(nb)
        for i in range(n_samples):
            cum += np.cumsum(rng.integers(0, 5, nb))
            for b in range(nb):
                c.add(IngestRecord(flat_keys[s][b], (START + i * 10) * 1000,
                                   (float(cum[b]),)))
            if len(c) >= 5000:
                ms2.get_shard("bench", 0).ingest(SomeData(c, i))
                c = RecordContainer()
    if len(c):
        ms2.get_shard("bench", 0).ingest(SomeData(c, 0))
    svc2 = QueryService(ms2, "bench", 1, spread=0, engine="mesh")
    q2 = ('histogram_quantile(0.99, sum(rate(lat_bucket[5m])) '
          'by (le, instance))')

    args1 = (START + 900, 60, START + 2100)
    svc1.query_range(q1, *args1)
    svc2.query_range(q2, *args1)
    n = 15
    t0 = time.perf_counter()
    for _ in range(n):
        svc1.query_range(q1, *args1)
    first_class = n / (time.perf_counter() - t0)
    t0 = time.perf_counter()
    for _ in range(n):
        svc2.query_range(q2, *args1)
    flat = n / (time.perf_counter() - t0)
    return {"metric": "hist_first_class_vs_flat",
            "first_class_qps": round(first_class, 2),
            "prom_flat_qps": round(flat, 2),
            "speedup": round(first_class / flat, 2), "unit": "queries/sec"}


def bench_hist_query():
    from filodb_tpu.coordinator.query_service import QueryService
    from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.core.store.config import StoreConfig
    from filodb_tpu.testing.data import histogram_series, histogram_stream

    ms = TimeSeriesMemStore()
    shard = ms.setup("bench", 0, StoreConfig(max_chunk_size=400))
    keys = histogram_series(20)
    for sd in histogram_stream(keys, 720, start_ms=START * 1000, batch=2000):
        shard.ingest(sd)
    svc = QueryService(ms, "bench", 1, spread=0, engine="mesh")
    q = 'histogram_quantile(0.99, sum(rate(http_req_latency[5m])))'
    svc.query_range(q, START + 3600, 60, START + 5400)
    n = 30
    t0 = time.perf_counter()
    for _ in range(n):
        svc.query_range(q, START + 3600, 60, START + 5400)
    dt = time.perf_counter() - t0
    return {"metric": "histogram_query_throughput",
            "value": round(n / dt, 2), "unit": "queries/sec"}


def bench_partkey_index():
    from filodb_tpu.core.filters import ColumnFilter, Equals, EqualsRegex
    from filodb_tpu.core.memstore.index import PartKeyIndex
    from filodb_tpu.core.partkey import PartKey

    from filodb_tpu.core.filters import EqualsRegex
    from filodb_tpu.core.memstore.native_shard import part_key_blob

    # keys/filters built in setup, like the reference JMH benchmark
    # (partKeys prepared in @Setup; the measured op is the index call)
    idx = PartKeyIndex()
    n = 50_000
    keys = [PartKey.create("gauge", {
        "_metric_": f"metric_{i % 100}", "_ws_": "demo",
        "_ns_": f"App-{i % 16}", "instance": f"i{i}",
        "host": f"h{i % 1000}"}) for i in range(n)]
    blobs = [part_key_blob(k) for k in keys]
    t0 = time.perf_counter()
    for i, (k, b) in enumerate(zip(keys, blobs)):
        idx.add_part_key_blob(i, k, b, i)
    add_rate = n / (time.perf_counter() - t0)
    m = 2000
    filter_sets = [
        [ColumnFilter("_metric_", Equals(f"metric_{i % 100}")),
         ColumnFilter("_ns_", Equals(f"App-{i % 16}"))]
        for i in range(100)]
    idx.part_ids_from_filters(filter_sets[0], 0, 2**62)  # warm caches
    t0 = time.perf_counter()
    for i in range(m):
        idx.part_ids_from_filters(filter_sets[i % 100], 0, 2**62)
    q_rate = m / (time.perf_counter() - t0)
    regex_sets = [
        [ColumnFilter("_ns_", Equals(f"App-{i % 16}")),
         ColumnFilter("instance", EqualsRegex(f"i{i % 10}.*"))]
        for i in range(20)]
    for fs in regex_sets:
        idx.part_ids_from_filters(fs, 0, 2**62)  # cold scans
    t0 = time.perf_counter()
    for i in range(m):
        idx.part_ids_from_filters(regex_sets[i % 20], 0, 2**62)
    rx_rate = m / (time.perf_counter() - t0)
    return {"metric": "partkey_index", "add_per_sec": round(add_rate),
            "equals_query_per_sec": round(q_rate),
            "regex_query_per_sec": round(rx_rate), "unit": "ops/sec"}


def bench_gateway():
    from filodb_tpu.gateway.influx import parse_influx_line

    lines = [f"cpu,host=h{i % 50},app=api,_ws_=demo,_ns_=App-0 "
             f"value={i}.5 {(START + i) * 1_000_000_000}"
             for i in range(5000)]
    t0 = time.perf_counter()
    for line in lines:
        parse_influx_line(line)
    dt = time.perf_counter() - t0
    return {"metric": "gateway_influx_parse", "value": round(len(lines) / dt),
            "unit": "lines/sec"}


def bench_encoding():
    from filodb_tpu.memory import codecs

    rng = np.random.default_rng(0)
    ts = (np.arange(10_000) * 10_000 + START * 1000
          + rng.integers(-50, 50, 10_000)).astype(np.int64)
    vals = rng.normal(100, 10, 10_000)
    n = 50
    t0 = time.perf_counter()
    for _ in range(n):
        e1 = codecs.encode_delta_delta(ts)
        e2 = codecs.encode_xor_double(vals)
    enc_rate = n * 20_000 / (time.perf_counter() - t0)
    t0 = time.perf_counter()
    for _ in range(n):
        codecs.decode_delta_delta(e1)
        codecs.decode_xor_double(e2)
    dec_rate = n * 20_000 / (time.perf_counter() - t0)
    ratio = (len(e1) + len(e2)) / (ts.nbytes + vals.nbytes)
    return {"metric": "encoding", "encode_samples_per_sec": round(enc_rate),
            "decode_samples_per_sec": round(dec_rate),
            "compression_ratio": round(ratio, 3), "unit": "samples/sec"}


def bench_query_odp():
    """On-demand-paging query throughput (reference
    ``jmh/.../QueryOnDemandBenchmark.scala``): data lives only in the
    column store; queries page chunks back in. ``cold`` clears the paged
    cache every query (pure ODP path incl. store reads + decode); ``warm``
    reuses the demand-paged cache."""
    import tempfile

    from filodb_tpu.coordinator.ingestion import ingest_routed
    from filodb_tpu.coordinator.query_service import QueryService
    from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.core.store.config import StoreConfig
    from filodb_tpu.core.store.localstore import (
        LocalDiskColumnStore,
        LocalDiskMetaStore,
    )
    from filodb_tpu.testing.data import counter_series, counter_stream

    tmp = tempfile.mkdtemp(prefix="filodb-odp-")
    cs = LocalDiskColumnStore(tmp + "/store")
    ms = TimeSeriesMemStore(cs, LocalDiskMetaStore(tmp + "/meta"))
    n_shards = 2
    for s in range(n_shards):
        ms.setup("timeseries", s, StoreConfig(max_chunk_size=400,
                                              groups_per_shard=4,
                                              flush_interval_ms=0))
    keys = counter_series(100, metric="heap_usage", ns="App-2")
    stream = counter_stream(keys, 720, start_ms=START * 1000, seed=11)
    ingest_routed(ms, "timeseries", stream, n_shards, spread=1)
    for shard in ms.shards_for("timeseries"):
        shard.flush_all()
        shard.evict_cold_partitions(max_evict=10**9)  # all data now cold
    svc = QueryService(ms, "timeseries", n_shards, spread=1)
    q = 'sum(rate(heap_usage{_ws_="demo",_ns_="App-2"}[5m]))'
    a, b = START + 1800, START + 3600

    def run(m, clear):
        for shard in ms.shards_for("timeseries"):
            shard.batch_cache.clear()
            shard.odp_cache.clear()
        svc.query_range(q, a, 60, b)  # warm compile
        t0 = time.perf_counter()
        for _ in range(m):
            if clear:
                for shard in ms.shards_for("timeseries"):
                    shard.batch_cache.clear()
                    shard.odp_cache.clear()
            r = svc.query_range(q, a, 60, b)
            assert r.result.num_series == 1
        return m / (time.perf_counter() - t0)

    return {"metric": "query_odp", "cold_qps": round(run(50, True), 1),
            "warm_qps": round(run(200, False), 1), "unit": "queries/sec"}


def bench_dict_string():
    """Dict-string column codec micro (reference
    ``jmh/.../DictStringBenchmark.scala``)."""
    from filodb_tpu.memory import codecs

    rng = np.random.default_rng(1)
    vocab = [f"value-{i}" for i in range(64)]
    vals = [vocab[i] for i in rng.integers(0, 64, 10_000)]
    n = 50
    t0 = time.perf_counter()
    for _ in range(n):
        enc = codecs.encode_dict_string(vals)
    enc_rate = n * len(vals) / (time.perf_counter() - t0)
    t0 = time.perf_counter()
    for _ in range(n):
        codecs.decode_dict_string(enc)
    dec_rate = n * len(vals) / (time.perf_counter() - t0)
    return {"metric": "dict_string",
            "encode_strings_per_sec": round(enc_rate),
            "decode_strings_per_sec": round(dec_rate),
            "encoded_bytes": len(enc), "unit": "ops/sec"}


def bench_mesh_churn():
    """Mesh engine under ingest churn and shard imbalance:
    q/s with a static store vs with every query preceded by an ingest tick
    (data_version bump -> batch rebuild + re-upload), on a 10:1 skewed
    shard distribution."""
    import sys as _sys
    _sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tests"))
    from test_mesh_stress import NUM_SHARDS, skewed_store
    from filodb_tpu.coordinator.query_service import QueryService
    from filodb_tpu.core.partkey import PartKey
    from filodb_tpu.core.record import (
        IngestRecord,
        RecordContainer,
        SomeData,
    )

    ms = skewed_store(per_shard=(80, 8, 8, 8), n_samples=120)
    svc = QueryService(ms, "timeseries", NUM_SHARDS, spread=1,
                       engine="mesh")
    q = 'sum(rate(skew_total[5m])) by (shardtag)'
    args = (START + 400, 10, START + 1100)
    svc.query_range(q, *args)  # warm/compile
    n = 30
    t0 = time.perf_counter()
    for _ in range(n):
        svc.query_range(q, *args)
    static_qps = n / (time.perf_counter() - t0)

    key = PartKey.create("prom-counter", {
        "_metric_": "skew_total", "_ws_": "demo", "_ns_": "App-0",
        "shardtag": "s0", "instance": "i0-0"})
    shard = ms.get_shard("timeseries", 0)
    t0 = time.perf_counter()
    for i in range(n):
        c = RecordContainer()
        c.add(IngestRecord(key, (START + (121 + i) * 10) * 1000,
                           (1e6 + i,)))
        shard.ingest(SomeData(c, 10_000 + i))
        svc.query_range(q, *args)
    churn_qps = n / (time.perf_counter() - t0)
    eng = svc.mesh_engine
    return {"metric": "mesh_churn", "static_qps": round(static_qps, 1),
            "churn_qps": round(churn_qps, 1),
            "rebuild_overhead_x": round(static_qps / churn_qps, 2),
            "mesh_hit_rate": round(eng.hit_rate, 3),
            "skew": "10:1 over 4 shards", "unit": "queries/sec"}


def _bench_dist_agg():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from dist_agg import bench_dist_agg
    return bench_dist_agg()


def _bench_objectstore():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from objectstore import bench_objectstore
    return bench_objectstore()


def _bench_overload():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from overload import bench_overload
    return bench_overload()


def _bench_migration():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from migration import bench_migration
    return bench_migration()


def _bench_replication():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from replication import bench_replication
    return bench_replication()


def _bench_rules():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from rules import bench_rules
    return bench_rules()


def _bench_sidecars():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from sidecars import bench_sidecars
    return bench_sidecars()


def _bench_tracing_overhead():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from tracing_overhead import bench_tracing_overhead
    return bench_tracing_overhead()


def _bench_selfmon_overhead():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from selfmon_overhead import bench_selfmon_overhead
    return bench_selfmon_overhead()


def _bench_federation():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from federation import bench_federation
    return bench_federation()


def _bench_federation_yearscan():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from federation import bench_federation_yearscan
    return bench_federation_yearscan()


def _bench_pyramid_topk_1m():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from federation import bench_pyramid_topk_1m
    return bench_pyramid_topk_1m()


def _bench_adaptive():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import adaptive
    return adaptive.bench_adaptive()


def _bench_multiproc_mesh():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from multiproc_mesh import run_sweep
    return run_sweep()


ALL = {
    "ingestion": bench_ingestion,
    "hist_ingest": bench_hist_ingest,
    "query_hicard": bench_query_hicard,
    "query_and_ingest": bench_query_and_ingest,
    "hist_query": bench_hist_query,
    "hist_flat_vs_fc": bench_hist_flat_vs_first_class,
    "partkey_index": bench_partkey_index,
    "gateway": bench_gateway,
    "encoding": bench_encoding,
    "query_odp": bench_query_odp,
    "dict_string": bench_dict_string,
    "mesh_churn": bench_mesh_churn,
    "dist_agg": _bench_dist_agg,
    "overload": _bench_overload,
    "objectstore": _bench_objectstore,
    "migration": _bench_migration,
    "replication": _bench_replication,
    "rules": _bench_rules,
    "sidecars": _bench_sidecars,
    "tracing_overhead": _bench_tracing_overhead,
    "selfmon_overhead": _bench_selfmon_overhead,
    "federation": _bench_federation,
    "federation_yearscan": _bench_federation_yearscan,
    "pyramid_topk_1m": _bench_pyramid_topk_1m,
    "adaptive": _bench_adaptive,
    "multiproc_mesh": _bench_multiproc_mesh,
}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args(argv)
    for name, fn in ALL.items():
        if args.only and name != args.only:
            continue
        out = fn()
        out["benchmark"] = name
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    sys.exit(main())
