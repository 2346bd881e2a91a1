"""Benchmark: the reference's QueryInMemoryBenchmark workload on TPU.

Reproduces the workload of
``jmh/src/main/scala/filodb.jmh/QueryInMemoryBenchmark.scala:31-35,126-130``:
100 series × 720 samples (2h @ 10s) ingested into a sharded in-memory store;
measures end-to-end PromQL range-query throughput for
``sum(rate(heap_usage{_ws_="demo",_ns_="App-2"}[5m]))`` (the north-star shape)
— full path: index lookup → chunk decode → batch build → jitted TPU kernels →
aggregated result.

Also reports a device-kernel microbench — bit-packed device-page decode →
counter-corrected rate → label-grouped segment sum, the fused hot loop — with
samples/s and an effective-HBM-bandwidth estimate, so there is a pure device
number even when the end-to-end path is host-bound.

vs_baseline: ratio against an in-process naive per-sample sliding-window
evaluation of the same queries (the reference engine's iteration strategy,
``PeriodicSamplesMapper``/``RangeFunction`` — measured here in numpy/python on
CPU since the JVM reference can't run in this image).

Runs on the backend JAX gives it and names it in the output (``platform``,
``device_kind``, ``device_count``). That backend must be a TPU: there is no
probe and no fallback. The one exception is a caller who sets
``JAX_PLATFORMS=cpu`` themselves, for a functional run on the host — the
output then says ``cpu`` and carries none of the fields named after the
device (``est_hbm_*``, ``*_vs_peak_pct``). Prints ONE JSON line:
{"metric", "value", "unit", "vs_baseline", "platform", "device_kind",
 "device_count", "kernel_microbench"}.
"""

import json
import os
import sys
import time

import numpy as np

# Peak HBM bandwidth by ``device_kind`` (GB/s). A device that is not in the
# table is an error, not a default. Source: Google Cloud documentation,
# "TPU v5e": 16 GB of HBM at 819 GB/s per chip.
PEAK_HBM_GB_S = {"TPU v5 lite": 819.0}


def require_device() -> dict:
    """The device this run measures, or SystemExit when it is not one this
    benchmark may report on."""
    from filodb_tpu import startup

    dev = startup.device_info()
    if dev["platform"] == "cpu":
        if os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
            raise SystemExit(
                "bench.py: JAX found no accelerator. Set JAX_PLATFORMS=cpu "
                "for a functional run on the host (no device metrics).")
    elif dev["platform"] != "tpu" or dev["kind"] not in PEAK_HBM_GB_S:
        raise SystemExit(f"bench.py: no peak table entry for {dev}")
    return dev

NUM_SHARDS = 8
NUM_SERIES = 100
NUM_SAMPLES = 720
INTERVAL_MS = 10_000
START_SEC = 1_600_000_000
QUERY = 'sum(rate(heap_usage{_ws_="demo",_ns_="App-2"}[5m]))'
QUERY_STEP_SEC = 60
N_QUERIES = 100
N_WARMUP = 3
# large-scan section: enough samples that scan cost ≫ the per-query sync floor
BIG_SERIES = 8192
BIG_SAMPLES = 1440  # 4h @ 10s per series
BIG_QUERY = 'sum(rate(big_counter[10m]))'
BIG_RANGE_SEC = 3 * 3600  # ~9.3M samples scanned per query


def config_default_engine() -> str:
    """The engine a default-config server actually ships with — the bench
    must measure the shape users get, not a hand-picked one."""
    from filodb_tpu.config import DEFAULTS
    return DEFAULTS["datasets"]["timeseries"].get("engine", "mesh")


def build_service(engine: str | None = None):
    engine = engine or config_default_engine()
    from filodb_tpu.coordinator.ingestion import ingest_routed
    from filodb_tpu.coordinator.query_service import QueryService
    from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.core.store.config import StoreConfig
    from filodb_tpu.testing.data import counter_stream, counter_series

    keys = counter_series(NUM_SERIES, metric="heap_usage", ns="App-2")
    stream = counter_stream(keys, NUM_SAMPLES, start_ms=START_SEC * 1000,
                            interval_ms=INTERVAL_MS, seed=42)
    ms = TimeSeriesMemStore()
    for s in range(NUM_SHARDS):
        ms.setup("timeseries", s, StoreConfig(max_chunk_size=400,
                                              groups_per_shard=8))
    n = ingest_routed(ms, "timeseries", stream, NUM_SHARDS, spread=1)
    assert n == NUM_SERIES * NUM_SAMPLES, n
    return QueryService(ms, "timeseries", NUM_SHARDS, spread=1,
                        engine=engine), keys


def build_big_store():
    """Big-scan store, loaded via the bulk chunk path: per-sample Python
    ingest of ~12M records would dominate the bench's wall clock, and this
    section measures QUERY cost (the headline section exercises the real
    ingest path).

    Everything here is seeded/deterministic, so N mesh worker processes
    started with ``--seed bench:build_big_store`` rebuild bit-identical
    per-shard data — benchmarks/multiproc_mesh.py depends on that."""
    from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.core.partkey import PartKey
    from filodb_tpu.core.store.config import StoreConfig
    from filodb_tpu.memory.chunk import encode_chunk

    ms = TimeSeriesMemStore()
    for s in range(NUM_SHARDS):
        ms.setup("timeseries", s, StoreConfig(max_chunk_size=400,
                                              groups_per_shard=8,
                                              native_ingest=False))
    rng = np.random.default_rng(11)
    ts = START_SEC * 1000 + np.arange(BIG_SAMPLES, dtype=np.int64) \
        * INTERVAL_MS
    chunk = 400
    for i in range(BIG_SERIES):
        key = PartKey.create("prom-counter", {
            "_metric_": "big_counter", "_ws_": "demo", "_ns_": "Big",
            "instance": f"inst-{i}"})
        shard = ms.get_shard("timeseries", i % NUM_SHARDS)
        part = shard.get_or_create_partition(key, int(ts[0]))
        vals = np.cumsum(rng.integers(0, 20, BIG_SAMPLES)).astype(
            np.float64)
        for c0 in range(0, BIG_SAMPLES, chunk):
            c1 = min(c0 + chunk, BIG_SAMPLES)
            part.chunks.append(encode_chunk(
                part.schema, ts[c0:c1], [vals[c0:c1]], len(part.chunks)))
        shard.stats.rows_ingested.inc(BIG_SAMPLES)  # data_version stamp
    return ms


def build_big_service(engine: str):
    from filodb_tpu.coordinator.query_service import QueryService

    ms = build_big_store()
    return QueryService(ms, "timeseries", NUM_SHARDS, spread=1,
                        engine=engine)


def run_queries(svc, n, start_sec, end_sec):
    t0 = time.perf_counter()
    lats = []
    for i in range(n):
        q0 = time.perf_counter()
        r = svc.query_range(QUERY, start_sec, QUERY_STEP_SEC, end_sec)
        lats.append(time.perf_counter() - q0)
        assert r.result.num_series == 1
    qps = n / (time.perf_counter() - t0)
    lats.sort()
    p50 = lats[len(lats) // 2] * 1e3
    p99 = lats[min(int(len(lats) * 0.99), len(lats) - 1)] * 1e3
    return qps, p50, p99


def run_queries_concurrent(svc, n, start_sec, end_sec, workers=16):
    """Throughput with n queries in flight (the JMH workload shape: 100
    concurrent queries per measured op) — overlaps result fetches."""
    qs = [(QUERY, start_sec, QUERY_STEP_SEC, end_sec)] * n
    t0 = time.perf_counter()
    rs = svc.query_range_many(qs, workers=workers)
    dt = time.perf_counter() - t0
    assert all(r.result.num_series == 1 for r in rs)
    return n / dt


def run_queries_sustained(svc, start_sec, end_sec, threads=4, batch=25,
                          rounds=4):
    """Sustained serving throughput: ``threads`` submitters each pipeline
    ``rounds`` batches of ``batch`` queries (the JMH posture — multiple
    benchmark threads with many in-flight queries per op). Completion
    syncs of different passes overlap, so this measures steady-state
    throughput rather than one pass's latency."""
    import threading

    done = []

    def worker():
        c = 0
        for _ in range(rounds):
            qs = [(QUERY, start_sec, QUERY_STEP_SEC, end_sec)] * batch
            rs = svc.query_range_many(qs)
            assert all(r.result.num_series == 1 for r in rs)
            c += batch
        done.append(c)

    ts = [threading.Thread(target=worker) for _ in range(threads)]
    t0 = time.perf_counter()
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    return sum(done) / (time.perf_counter() - t0)


def measure_big_scan():
    """The mesh engine at scan-heavy scale (~9M samples per query): the
    complement of the small-scan workload, where the per-query sync floor
    dominates."""
    from filodb_tpu.promql.parser import TimeStepParams

    svc = build_big_service("mesh")
    start_sec = START_SEC + 3600
    end_sec = start_sec + BIG_RANGE_SEC
    engine = svc.mesh_engine
    out = {"engine": "mesh",
           "series": BIG_SERIES,
           "samples_per_query_approx":
               BIG_SERIES * (BIG_RANGE_SEC + 600) // 10}
    lows = [engine._lower(svc._parse_cached(BIG_QUERY, TimeStepParams(
        start_sec, QUERY_STEP_SEC, end_sec)))]
    for _ in range(2):  # warm: compile + batch build + upload
        engine.execute_lowered_many(lows, svc.memstore,
                                    "timeseries")[0].materialize()
    iters = 5
    t0 = time.perf_counter()
    for _ in range(iters):
        engine.execute_lowered_many(lows, svc.memstore,
                                    "timeseries")[0].materialize()
    out["ms_per_query"] = round(
        (time.perf_counter() - t0) / iters * 1e3, 1)
    return out


def naive_baseline_qps(svc, start_sec, end_sec, n_iters=5):
    """Per-sample sliding-window evaluation (the reference's strategy) over
    the same decoded data, including the same index lookup + decode path."""
    from filodb_tpu.core.filters import ColumnFilter, Equals

    filters = [ColumnFilter("_metric_", Equals("heap_usage")),
               ColumnFilter("_ws_", Equals("demo")),
               ColumnFilter("_ns_", Equals("App-2"))]
    window = 300_000
    t0 = time.perf_counter()
    for _ in range(n_iters):
        steps = np.arange(start_sec * 1000, end_sec * 1000 + 1,
                          QUERY_STEP_SEC * 1000)
        total = np.zeros(len(steps))
        count = np.zeros(len(steps), dtype=int)
        for shard in svc.memstore.shards_for("timeseries"):
            for pid in shard.lookup_partitions(
                    filters, start_sec * 1000 - window, end_sec * 1000):
                part = shard.partition(pid)
                t, v = part.read_samples(start_sec * 1000 - window,
                                         end_sec * 1000)
                for k, te in enumerate(steps):
                    m = (t > te - window) & (t <= te)
                    wt, wv = t[m], v[m]
                    if len(wt) < 2:
                        continue
                    corr = np.concatenate(
                        [[0.0], np.cumsum(np.where(np.diff(wv) < 0,
                                                   wv[:-1], 0.0))])
                    cv = wv + corr
                    inc = cv[-1] - cv[0]
                    sampled = (wt[-1] - wt[0]) / 1000.0
                    avg_dur = sampled / (len(wt) - 1)
                    ds = (wt[0] - (te - window)) / 1000.0
                    de = (te - wt[-1]) / 1000.0
                    if inc > 0:
                        ds = min(ds, sampled * wv[0] / inc)
                    th = avg_dur * 1.1
                    ext = sampled + (ds if ds < th else avg_dur / 2) \
                        + (de if de < th else avg_dur / 2)
                    total[k] += inc * (ext / sampled) / (window / 1000.0)
                    count[k] += 1
    return n_iters / (time.perf_counter() - t0)


def kernel_microbench(dev: dict, iters: int = 50):
    """Pure device pipeline: bit-packed page decode → rate → segment_sum.

    Shapes follow ``__graft_entry__.entry()`` scaled up one notch (P=512
    series × ~4096 samples × K=128 steps) so the device sees real work.
    Reports fused-pipeline samples/s and, on a TPU, an effective-HBM-
    bandwidth lower bound (packed input read + decoded [P,S] write+read once
    each) against the peak of the ``device_kind`` that ran it.
    """
    import jax
    import jax.numpy as jnp
    from filodb_tpu.memory.device_pages import encode_f32_page, encode_ts_page
    from filodb_tpu.query.engine.aggregations import aggregate
    from filodb_tpu.query.engine.device_batch import (
        _assemble,
        pack_series_pages,
    )
    from filodb_tpu.query.engine.kernels import range_eval_masked

    P, S, K, G = 512, 4096, 128, 8
    rng = np.random.default_rng(7)
    per_series = []
    total_samples = 0
    for p in range(P):
        n = S - int(rng.integers(0, 128))
        ts = np.cumsum(rng.integers(8_000, 12_000, n)).astype(np.int64)
        vals = np.cumsum(rng.integers(0, 20, n)).astype(np.float64)
        per_series.append([(encode_ts_page(ts), encode_f32_page(vals),
                            n)])
        total_samples += n
    packed, counts = pack_series_pages(per_series, start=0)
    span = np.int32(int(12_000) * S + 1)
    gids = (np.arange(len(counts)) % G).astype(np.int32)
    last = int(min(c for c in counts if c)) * 8_000
    steps = np.linspace(last // 2, last, K).astype(np.int32)
    window = np.int32(300_000)

    packed_dev = [jnp.asarray(a) for a in packed]
    gids_d, steps_d = jnp.asarray(gids), jnp.asarray(steps)

    def fused(arrs, span_, gids_, steps_, window_):
        ts_d, vals_d, valid_d = _assemble(*arrs, span_)
        rate = range_eval_masked("rate", ts_d, vals_d, valid_d, steps_,
                                 window_, counter=True)
        return aggregate("sum", rate, gids_, G)

    jfused = jax.jit(fused)
    out = jfused(packed_dev, jnp.asarray(span), gids_d, steps_d,
                 jnp.asarray(window))
    out.block_until_ready()  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jfused(packed_dev, jnp.asarray(span), gids_d, steps_d,
                     jnp.asarray(window))
    out.block_until_ready()
    dt = (time.perf_counter() - t0) / iters

    from filodb_tpu.memory.device_pages import BLOCK
    packed_bytes = sum(a.nbytes for a in packed)
    Pp, NB = int(packed[0].shape[0]), int(packed[0].shape[1])
    # decoded [P, NB*BLOCK]: int32 ts + f32 vals + bool valid, written then
    # read by the rate kernel → 2 passes
    decoded_bytes = Pp * NB * BLOCK * (4 + 4 + 1)
    traffic = packed_bytes + 2 * decoded_bytes
    out = {
        "shape": {"P": P, "S": S, "K": K, "G": G,
                  "total_samples": int(total_samples)},
        "fused_decode_rate_sum_ms": round(dt * 1000, 3),
        "samples_per_sec": int(total_samples / dt),
        "window_evals_per_sec": int(P * K / dt),
        "packed_mb": round(packed_bytes / 1e6, 1),
        "platform": dev["platform"],
    }
    if dev["platform"] == "tpu":
        gb_s = traffic / dt / 1e9
        out["est_hbm_gb_s"] = round(gb_s, 1)
        out["est_hbm_util_vs_peak_pct"] = round(
            100 * gb_s / PEAK_HBM_GB_S[dev["kind"]], 1)
    return out


def main():
    from filodb_tpu import startup

    startup.configure_jax()
    dev = require_device()
    sys.stderr.write(f"bench device: {json.dumps(dev)}\n")

    micro = kernel_microbench(dev)
    sys.stderr.write(f"kernel microbench: {json.dumps(micro)}\n")

    engine = config_default_engine()
    sys.stderr.write(f"bench engine (config default): {engine}\n")
    svc, _ = build_service(engine)
    start_sec = START_SEC + 1800
    end_sec = START_SEC + 1800 + 30 * 60  # 30-min range, 31 steps

    run_queries(svc, N_WARMUP, start_sec, end_sec)  # compile + warm caches
    run_queries_concurrent(svc, N_QUERIES, start_sec, end_sec)  # batch compile
    seq_qps, p50_ms, p99_ms = run_queries(svc, N_QUERIES, start_sec, end_sec)
    conc_qps = run_queries_concurrent(svc, N_QUERIES, start_sec, end_sec)
    sustained_qps = run_queries_sustained(svc, start_sec, end_sec)
    qps = max(seq_qps, conc_qps, sustained_qps)
    baseline = naive_baseline_qps(svc, start_sec, end_sec)

    # pure device kernel time (microbench) beside the end-to-end numbers
    breakdown = {"device_kernel_ms": micro.get("fused_decode_rate_sum_ms")}

    big = measure_big_scan()
    sys.stderr.write(f"big scan: {json.dumps(big)}\n")

    print(json.dumps({
        "metric": "promql_sum_rate_range_query_throughput",
        "value": round(qps, 2),
        "unit": "queries/sec",
        "engine": engine,
        "sequential_qps": round(seq_qps, 2),
        "latency_p50_ms": round(p50_ms, 2),
        "latency_p99_ms": round(p99_ms, 2),
        "concurrent_qps": round(conc_qps, 2),
        "sustained_qps": round(sustained_qps, 2),
        "latency_breakdown": breakdown,
        "big_scan": big,
        "platform": dev["platform"],
        "device_kind": dev["kind"],
        "device_count": dev["count"],
        # ratio against naive per-sample numpy/python iteration of the
        # same queries (NOT the JVM engine)
        "vs_baseline": round(qps / baseline, 2),
        "kernel_microbench": micro,
    }))


if __name__ == "__main__":
    sys.exit(main())
