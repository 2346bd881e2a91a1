"""Serving benchmark: concurrent HTTP clients against a live server.

End-to-end throughput including HTTP, JSON rendering, planner, kernels —
the number a dashboard fleet actually experiences (the reference's JMH
benches stop at the query engine; this covers the full serving stack).

    python benchmarks/serving.py [--clients 8] [--seconds 15]

``--workers N`` (N > 1) is a CPU harness: every extra worker is a full
server process, an accelerator belongs to one process, so the workers are
pinned to the CPU and the run refuses to start unless the parent's backend
is the CPU too (``JAX_PLATFORMS=cpu``).

Dashboard mode (--dashboard) measures the extent result cache on the
workload it exists for: N panels re-rendered every refresh with the window
slid one step, against a store that keeps ingesting. Cache-on and cache-off
services share one memstore and every refresh cross-checks their answers,
so the speedup number is only reported if zero stale reads occurred.

    python benchmarks/serving.py --dashboard [--series 8192]
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

START = 1_600_000_000


def dashboard(args):
    """Sliding-dashboard bench: extent result cache on vs off, live ingest.

    In-process (no HTTP) so the number isolates the query path the cache
    fronts; the HTTP rendered-response cache can't help here because every
    refresh has different start/end params.
    """
    from filodb_tpu.coordinator.ingestion import ingest_routed
    from filodb_tpu.coordinator.query_service import QueryService
    from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.core.store.config import StoreConfig
    from filodb_tpu.query import result_cache as rc
    from filodb_tpu.query.model import PlannerParams, QueryContext
    from filodb_tpu.testing.data import gauge_stream, machine_metrics_series

    num_shards = 4
    interval_ms = 30_000
    step = 60
    window_s = 21_600                    # 6h big-scan dashboard window
    base_samples = 800                   # ~6.7h of history before t0
    ms = TimeSeriesMemStore()
    for s in range(num_shards):
        ms.setup("timeseries", s,
                 StoreConfig(max_chunk_size=400, groups_per_shard=4,
                             retention_ms=10**15))
    # two namespaces so the router populates every shard at spread=1
    half = args.series // 2
    keysets = [machine_metrics_series(half, ns="App-2"),
               machine_metrics_series(args.series - half, ns="App-3")]
    t_ing0 = time.perf_counter()
    for kk in keysets:
        ingest_routed(ms, "timeseries",
                      gauge_stream(kk, base_samples, start_ms=START * 1000,
                                   interval_ms=interval_ms, seed=9),
                      num_shards, spread=1)
    ingest_s = time.perf_counter() - t_ing0

    plain = QueryService(ms, "timeseries", num_shards, spread=1)
    # short extents: under live ingest only the head extent re-evaluates
    # each refresh, and its cost scales with extent+lookback length
    cached = QueryService(ms, "timeseries", num_shards, spread=1,
                          result_cache={"extent_steps": 8})

    panels = [
        "sum(rate(heap_usage[5m]))",
        "sum by (host) (rate(heap_usage[5m]))",
        "avg_over_time(heap_usage[5m])",
        "max_over_time(heap_usage[10m])",
        "max by (host) (avg_over_time(heap_usage[5m]))",
    ]

    def check_equiv(a, b, promql):
        m0, m1 = a.result, b.result
        i0 = {k: i for i, k in enumerate(m0.keys)}
        i1 = {k: i for i, k in enumerate(m1.keys)}
        if set(i0) != set(i1):
            return f"{promql}: key sets differ"
        for k, i in i0.items():
            va = np.asarray(m0.values[i])
            vb = np.asarray(m1.values[i1[k]])
            if not np.array_equal(np.isnan(va), np.isnan(vb)):
                return f"{promql}: NaN masks differ for {k}"
            # float32 prefix sums over a 6h, 800-sample scan carry up to
            # ~1e-3 absolute noise vs per-extent scans (eps x prefix
            # magnitude); a stale head step would differ by a random-walk
            # increment, O(0.1-10), so detection power is intact
            if not np.allclose(va, vb, rtol=1e-3, atol=5e-3,
                               equal_nan=True):
                m = ~np.isnan(va)
                d = np.abs(va[m] - vb[m])
                j = int(np.argmax(d))
                at = int(np.nonzero(m)[0][j])
                return (f"{promql}: values differ for {k}: "
                        f"max |d|={float(d[j]):.2e} at step {at}/"
                        f"{len(va)} (a={float(va[m][j]):.6g} "
                        f"b={float(vb[m][j]):.6g})")
        return None

    qe0 = START + (base_samples - 1) * interval_ms // 1000  # last sample
    plain_lat, cached_lat, cold_lat = [], [], []
    stale = []
    samples_done = base_samples
    for refresh in range(args.refreshes):
        # live ingest: data keeps arriving between refreshes (appended
        # synchronously so cache-on and cache-off compare the same store;
        # delta-only — value continuity across batches doesn't matter here)
        if refresh:
            t_new = START * 1000 + samples_done * interval_ms
            new_samples = step * 1000 // interval_ms
            for kk in keysets:
                ingest_routed(
                    ms, "timeseries",
                    gauge_stream(kk, new_samples, start_ms=t_new,
                                 interval_ms=interval_ms,
                                 seed=100 + refresh),
                    num_shards, spread=1)
            samples_done += new_samples
        qe = qe0 + refresh * step
        qs = qe - window_s
        for promql in panels:
            # big-scan panels return series x steps well past the default
            # sample limit; raise it (fresh context per query)
            t0 = time.perf_counter()
            r_cached = cached.query_range(promql, qs, step, qe, QueryContext(
                planner_params=PlannerParams(sample_limit=50_000_000)))
            t1 = time.perf_counter()
            r_plain = plain.query_range(promql, qs, step, qe, QueryContext(
                planner_params=PlannerParams(sample_limit=50_000_000)))
            t2 = time.perf_counter()
            (cold_lat if refresh == 0 else cached_lat).append(t1 - t0)
            plain_lat.append(t2 - t1)
            err = check_equiv(r_plain, r_cached, promql)
            if err:
                stale.append(f"refresh {refresh}: {err}")

    def pct(xs, p):
        return round(float(np.percentile(np.array(xs), p)) * 1000, 2)

    out = {
        "metric": "dashboard_refresh_latency",
        "series": args.series,
        "panels": len(panels),
        "refreshes": args.refreshes,
        "window_s": window_s,
        "step_s": step,
        "ingest_seconds": round(ingest_s, 1),
        "cache_off_p50_ms": pct(plain_lat, 50),
        "cache_off_p99_ms": pct(plain_lat, 99),
        "cache_cold_p50_ms": pct(cold_lat, 50),
        "cache_warm_p50_ms": pct(cached_lat, 50),
        "cache_warm_p99_ms": pct(cached_lat, 99),
        "warm_speedup_p50": round(
            pct(plain_lat, 50) / max(pct(cached_lat, 50), 1e-9), 1),
        "cache_hits": int(rc.cache_hits.value),
        "cache_misses": int(rc.cache_misses.value),
        "cache_bytes": int(cached.result_cache.nbytes),
        "stale_reads": stale[:5] if stale else 0,
    }
    print(json.dumps(out))
    return 1 if stale else 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--workers", type=int, default=1,
                    help="server processes sharing the port (SO_REUSEPORT "
                         "log-replica serving plane)")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--dashboard", action="store_true",
                    help="sliding-dashboard bench of the extent result "
                         "cache (in-process, cache on vs off)")
    ap.add_argument("--series", type=int, default=8192)
    ap.add_argument("--refreshes", type=int, default=20)
    args = ap.parse_args(argv)
    if args.dashboard:
        return dashboard(args)
    if args.workers > 1:
        import jax
        if jax.default_backend() != "cpu":
            raise SystemExit(
                f"--workers {args.workers} is a CPU harness (see the module "
                f"docstring) and this process runs on "
                f"{jax.default_backend()}; set JAX_PLATFORMS=cpu")

    from filodb_tpu.client import FiloClient
    from filodb_tpu.config import ServerConfig
    from filodb_tpu.coordinator.ingestion import route_container
    from filodb_tpu.standalone import FiloServer
    from filodb_tpu.testing.data import counter_series, counter_stream

    tmp = tempfile.mkdtemp(prefix="filodb-serving-")
    cfg = os.path.join(tmp, "s.json")
    with open(cfg, "w") as f:
        json.dump({
            "node_name": "bench", "data_dir": os.path.join(tmp, "d"),
            "wal_dir": os.path.join(tmp, "wal"),
            "http_port": 0, "gateway_port": 0,
            # headline measures real serving: the rendered-response cache is
            # off (it would trivially absorb this bench's fixed query mix);
            # a second short phase measures it separately (cached_qps)
            "http_response_cache": False,
            "datasets": {"timeseries": {
                "num_shards": 4, "spread": 1,
                "store": {"max_chunk_size": 400, "groups_per_shard": 4,
                          "retention_ms": 10**15}}},
        }, f)
    server = FiloServer(ServerConfig.load(cfg)).start()
    extra_procs = []
    try:
        keys = counter_series(100, metric="heap_usage", ns="App-2")
        for sd in counter_stream(keys, 720, start_ms=START * 1000, seed=1):
            for shard, cont in route_container(sd.container, 4, 1).items():
                server.logs[("timeseries", shard)].append(cont)
        # wait for ingest workers
        c0 = FiloClient(port=server.http.port)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            r = c0.query("count(heap_usage)", START + 7100)
            if r and float(r[0]["value"][1]) == 100:
                break
            time.sleep(0.2)

        if args.workers > 1:
            # extra worker processes: each runs a full server on the SAME
            # port via SO_REUSEPORT, reading the same data dir/WAL (the
            # log-replica serving plane). The primary re-binds with
            # reuse_port so the kernel can balance across all of them.
            import subprocess
            port = server.http.port
            with open(cfg) as f:
                base = json.load(f)
            for w in range(args.workers - 1):
                wcfg = dict(base)
                wcfg["node_name"] = f"worker-{w}"
                wcfg["data_dir"] = os.path.join(tmp, f"wd{w}")
                wcfg["http_port"] = port
                wcfg["http_reuse_port"] = True
                wpath = os.path.join(tmp, f"w{w}.json")
                with open(wpath, "w") as f:
                    json.dump(wcfg, f)
                code = (
                    "from filodb_tpu.config import ServerConfig;"
                    "from filodb_tpu.standalone import FiloServer;"
                    f"s = FiloServer(ServerConfig.load({wpath!r})).start();"
                    "import time;"
                    "print('WORKER_READY', flush=True);"
                    "time.sleep(10**9)")
                env = dict(os.environ, JAX_PLATFORMS="cpu")
                pr = subprocess.Popen(
                    [sys.executable, "-c", code], env=env,
                    cwd=os.path.dirname(os.path.dirname(
                        os.path.abspath(__file__))),
                    stdout=subprocess.PIPE, text=True)
                extra_procs.append(pr)
            # rebind the primary with reuse_port on the same port
            server.http.stop()
            from filodb_tpu.http.server import FiloHttpServer
            server.http = FiloHttpServer(
                server.http.services, port=port,
                cluster=server.http.cluster,
                shard_maps=server.http.shard_maps,
                reuse_port=True).start()
            for pr in extra_procs:
                line = pr.stdout.readline()
                assert "WORKER_READY" in line, line
            # wait for every worker to finish ingesting (query via the
            # shared port until all answers stabilize at full count)
            deadline = time.monotonic() + 120
            stable = 0
            while time.monotonic() < deadline and stable < args.workers * 3:
                r = FiloClient(port=port).query("count(heap_usage)",
                                                START + 7100)
                if r and float(r[0]["value"][1]) == 100:
                    stable += 1
                else:
                    stable = 0
                    time.sleep(0.5)

        queries = [
            ("range", 'sum(rate(heap_usage{_ws_="demo",_ns_="App-2"}[5m]))',
             START + 3600, START + 5400, 60),
            ("range", 'rate(heap_usage[5m])', START + 3600, START + 5400,
             300),
            ("range", 'topk(5, rate(heap_usage[5m]))', START + 3600,
             START + 4500, 300),
            ("instant", 'sum by (job) (rate(heap_usage[5m]))',
             START + 5000, 0, 0),
        ]
        # warm all query shapes
        for kind, q, a, b, step in queries:
            if kind == "range":
                c0.query_range(q, a, b, step)
            else:
                c0.query(q, a)

        # client load runs in separate PROCESSES: in-process client threads
        # would share the server's GIL and measure the bench, not the server
        import multiprocessing as mp

        def client_proc(i, port, seconds, warm_seconds, out_q):
            import time as _t

            client = FiloClient(port=port)
            rng = np.random.default_rng(i)
            deadline_warm = _t.monotonic() + warm_seconds
            while _t.monotonic() < deadline_warm:  # unmeasured warm phase
                kind, q, a, b, step = queries[rng.integers(len(queries))]
                if kind == "range":
                    client.query_range(q, a, b, step)
                else:
                    client.query(q, a)
            lat = []
            deadline = _t.monotonic() + seconds
            while _t.monotonic() < deadline:
                kind, q, a, b, step = queries[rng.integers(len(queries))]
                t0 = _t.perf_counter()
                if kind == "range":
                    client.query_range(q, a, b, step)
                else:
                    client.query(q, a)
                lat.append(_t.perf_counter() - t0)
            out_q.put(lat)

        ctx = mp.get_context("fork")
        out_q = ctx.Queue()
        warm_s = 4.0 if args.workers <= 1 else 4.0 + 4.0 * args.workers
        procs = [ctx.Process(target=client_proc,
                             args=(i, server.http.port, args.seconds,
                                   warm_s, out_q), daemon=True)
                 for i in range(args.clients)]
        for pr in procs:
            pr.start()
        t_start = time.perf_counter() + warm_s
        per_client = [out_q.get(timeout=args.seconds + warm_s + 60)
                      for _ in procs]
        for pr in procs:
            pr.join(timeout=10)
        wall = args.seconds
        counts = [len(lt) for lt in per_client]
        all_lats = np.array([x for lt in per_client for x in lt])

        # second phase: rendered-response cache on (the query-frontend
        # pattern) — the dashboard-refresh workload where the same panel
        # queries repeat against unchanged data
        cached_qps = None
        if args.workers <= 1:
            from filodb_tpu.http.server import ResponseCache
            server.http.response_cache = ResponseCache()
            out_q2 = ctx.Queue()
            procs2 = [ctx.Process(target=client_proc,
                                  args=(i, server.http.port, 5.0, 2.0,
                                        out_q2), daemon=True)
                      for i in range(args.clients)]
            for pr in procs2:
                pr.start()
            per_client2 = [out_q2.get(timeout=60) for _ in procs2]
            for pr in procs2:
                pr.join(timeout=10)
            cached_qps = round(sum(len(lt) for lt in per_client2) / 5.0, 2)

        print(json.dumps({
            "metric": "http_serving_throughput",
            "value": round(sum(counts) / wall, 2),
            "unit": "queries/sec",
            "clients": args.clients,
            "p50_ms": round(float(np.percentile(all_lats, 50)) * 1000, 2),
            "p99_ms": round(float(np.percentile(all_lats, 99)) * 1000, 2),
            "response_cache_qps": cached_qps,
        }))
    finally:
        for pr in extra_procs:
            pr.terminate()
        server.shutdown()


if __name__ == "__main__":
    sys.exit(main())
