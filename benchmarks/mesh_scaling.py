"""Big-scan scaling curve across mesh sizes (1 → N devices).

Measures the headline big-scan query (``bench.BIG_QUERY`` over
``bench.BIG_SERIES`` series) at several mesh widths, comparing the
mesh-sharded split pipeline (prepare/bounds cached, tiny per-query step)
against the single-program fused baseline (``FILODB_MESH_SPLIT=0``), and
asserts the two forms return byte-identical PromQL results before any
number is reported.

Every width runs in THIS process, on a mesh over ``jax.devices()[:n]``: an
accelerator belongs to one process, so a sweep must not start children that
need it. On a four-chip host the widths are 1, 2 and 4; for a host run set
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8``
yourself. A width the backend cannot supply is an error, not a skip. This
is what ``benchmarks/run_benchmarks.py --devices`` prints.

On a single-core container the device-count axis cannot show wall-clock
parallel speedup (all virtual devices share one core); the curve instead
verifies the sharded program stays correct and does not REGRESS as the
mesh widens, while the split-vs-fused column shows the algorithmic win.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEFAULT_DEVICES = (1, 2, 4, 8)
WARMUPS = 2
ITERS = 5


def _measure_form(engine, lows, memstore, split: bool) -> tuple[float, bytes]:
    """Warm ms/query for one form plus the result bytes for equality."""
    os.environ["FILODB_MESH_SPLIT"] = "1" if split else "0"
    out = None
    for _ in range(WARMUPS):
        out = engine.execute_lowered_many(lows, memstore,
                                          "timeseries")[0].materialize()
    import numpy as np
    blob = (np.asarray(out.values).tobytes()
            + np.asarray(out.steps_ms).tobytes())
    t0 = time.perf_counter()
    for _ in range(ITERS):
        engine.execute_lowered_many(lows, memstore,
                                    "timeseries")[0].materialize()
    return (time.perf_counter() - t0) / ITERS * 1e3, blob


def measure_width(svc, n_devices: int) -> dict:
    import jax

    import bench
    from filodb_tpu.parallel.mesh_engine import (
        MeshQueryEngine,
        make_query_mesh,
    )
    from filodb_tpu.promql.parser import TimeStepParams

    if len(jax.devices()) < n_devices:
        raise SystemExit(
            f"backend has {len(jax.devices())} devices, need {n_devices}")
    start_sec = bench.START_SEC + 3600
    end_sec = start_sec + bench.BIG_RANGE_SEC
    plan = svc._parse_cached(bench.BIG_QUERY, TimeStepParams(
        start_sec, bench.QUERY_STEP_SEC, end_sec))
    engine = MeshQueryEngine(mesh=make_query_mesh(n_devices=n_devices))
    lows = [engine._lower(plan)]
    assert lows[0] is not None, "big-scan query must lower"
    split_ms, split_blob = _measure_form(engine, lows, svc.memstore, True)
    fused_ms, fused_blob = _measure_form(engine, lows, svc.memstore, False)
    assert split_blob == fused_blob, (
        f"split/fused results differ at {n_devices} devices")
    return {"devices": n_devices,
            "split_ms_per_query": round(split_ms, 1),
            "fused_ms_per_query": round(fused_ms, 1),
            "identical_results": True}


def run_sweep(devices=DEFAULT_DEVICES) -> dict:
    """One store, one process, every mesh width in turn."""
    import bench

    svc = bench.build_big_service("mesh")
    try:
        curve = [measure_width(svc, n) for n in devices]
    finally:
        # _measure_form leaves the valve on "fused"; later benchmarks of a
        # run_benchmarks pass must see the default again
        os.environ.pop("FILODB_MESH_SPLIT", None)
    out = {"metric": "mesh_scaling", "unit": "ms/query", "curve": curve}
    base = next((r["fused_ms_per_query"] for r in curve
                 if r["devices"] == 1), None)
    best = min((r["split_ms_per_query"] for r in curve), default=None)
    if base and best:
        out["split_speedup_vs_single_lane_fused"] = round(base / best, 2)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", default=",".join(map(str, DEFAULT_DEVICES)),
                    help="comma-separated mesh widths for the sweep")
    args = ap.parse_args(argv)
    widths = tuple(int(x) for x in args.devices.split(",") if x.strip())
    print(json.dumps(run_sweep(widths)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
