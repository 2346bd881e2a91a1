"""Multi-process mesh scaling sweep: N worker processes × 1 CPU device.

Runs the headline big scan (``bench.BIG_QUERY`` over ``bench.BIG_SERIES``
series) through the multi-process mesh runtime at several worker counts.
Each width spawns real worker processes via ``MeshWorkerSupervisor``
(seeded with ``bench:build_big_store`` — deterministic, so every process
derives identical per-shard data) and the root reduces their partial
matrices with the cross-process collective path. Before any number is
reported, every width's result is asserted BYTE-IDENTICAL to the
single-process mesh engine over the same store.

On a single-core container the worker axis cannot show wall-clock
speedup (all processes share one core, plus per-query IPC cost); the
sweep verifies the distributed path stays correct and bounds its
overhead vs the in-process engine. On real multi-host hardware the same
harness is the scaling measurement (doc/mesh_engine.md §multi-process).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

DEFAULT_WORKERS = (1, 2, 4)
WARMUPS = 1
ITERS = 5


def run_sweep(widths=DEFAULT_WORKERS) -> dict:
    import jax
    import numpy as np

    import bench

    # the supervisor pins every worker to one CPU device, and the sweep
    # asserts their results byte-identical to the root's engine: a CPU
    # harness end to end
    if jax.default_backend() != "cpu":
        raise SystemExit("multiproc_mesh is a CPU harness (workers are "
                         "pinned to the CPU); set JAX_PLATFORMS=cpu")

    from filodb_tpu.coordinator.mesh_cluster import MeshClusterRuntime
    from filodb_tpu.parallel.mesh_engine import (
        MeshQueryEngine,
        make_query_mesh,
    )
    from filodb_tpu.parallel.multiproc import MeshWorkerSupervisor
    from filodb_tpu.promql.parser import TimeStepParams, parse_query

    store = bench.build_big_store()
    start_sec = bench.START_SEC + 3600
    plan = parse_query(bench.BIG_QUERY, TimeStepParams(
        start_sec, bench.QUERY_STEP_SEC, start_sec + bench.BIG_RANGE_SEC))

    # single-process reference: same 1-device mesh the workers use
    engine = MeshQueryEngine(mesh=make_query_mesh(n_devices=1))
    want = engine.execute(store, "timeseries", plan)
    t0 = time.perf_counter()
    for _ in range(ITERS):
        engine.execute(store, "timeseries", plan)
    single_ms = (time.perf_counter() - t0) / ITERS * 1e3
    blob = np.asarray(want.values).tobytes()

    curve = []
    for w in widths:
        sup = MeshWorkerSupervisor(
            dataset="timeseries", num_shards=bench.NUM_SHARDS, workers=w,
            seed="bench:build_big_store",
            env={"PYTHONPATH": REPO_ROOT, "FILODB_BENCH_CPU": "1"})
        t_ready = time.perf_counter()
        sup.spawn()
        try:
            sup.wait_ready(timeout_s=600.0)
            ready_s = time.perf_counter() - t_ready
            rt = MeshClusterRuntime(store, "timeseries", bench.NUM_SHARDS,
                                    sup.slices, timeout=120.0)
            got = None
            for _ in range(WARMUPS + 1):
                got = rt.execute_plan(plan)
            assert got is not None, f"multiproc fell back at {w} workers"
            assert np.asarray(got.values).tobytes() == blob, (
                f"multiproc result differs from single-process at "
                f"{w} workers")
            t0 = time.perf_counter()
            for _ in range(ITERS):
                rt.execute_plan(plan)
            ms = (time.perf_counter() - t0) / ITERS * 1e3
            curve.append({"workers": w,
                          "ms_per_query": round(ms, 1),
                          "ready_s": round(ready_s, 1),
                          "identical_results": True})
        except Exception as e:  # noqa: BLE001 - record and keep sweeping
            curve.append({"workers": w, "error": repr(e)[:200]})
        finally:
            sup.stop()
    return {"metric": "multiproc_mesh", "unit": "ms/query",
            "series": bench.BIG_SERIES,
            "single_process_ms_per_query": round(single_ms, 1),
            "curve": curve}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", default=",".join(map(str, DEFAULT_WORKERS)),
                    help="comma-separated worker counts for the sweep")
    args = ap.parse_args(argv)
    widths = tuple(int(x) for x in args.workers.split(",") if x.strip())
    print(json.dumps(run_sweep(widths)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
