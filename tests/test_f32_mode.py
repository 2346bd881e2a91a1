"""TPU-numerics simulation: the whole query path with x64 DISABLED (f32/i32
everywhere, as on the real chip). Catches dtype leaks that CPU tests (which
force x64 for exact Prometheus parity) would mask.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest
from batch_oracle import RANGES
from mesh_oracle import CASES, MESHES

SCRIPT = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("JAX_ENABLE_X64", None)
import jax
jax.config.update("jax_platforms", "cpu")
assert not jax.config.jax_enable_x64

import json
import numpy as np
from filodb_tpu.coordinator.ingestion import ingest_routed
from filodb_tpu.coordinator.query_service import QueryService
from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.core.store.config import StoreConfig
from filodb_tpu.testing.data import (
    counter_series, counter_stream, gauge_stream, histogram_series,
    histogram_stream, machine_metrics_series,
)

START = 1_600_000_000
out = {}

for device_pages in (False, True):
    ms = TimeSeriesMemStore()
    for s in range(2):
        ms.setup("timeseries", s, StoreConfig(max_chunk_size=100,
                                              device_pages=device_pages))
    ingest_routed(ms, "timeseries",
                  gauge_stream(machine_metrics_series(6), 400,
                               start_ms=START * 1000, seed=2), 2, 1)
    ingest_routed(ms, "timeseries",
                  counter_stream(counter_series(4), 400,
                                 start_ms=START * 1000, seed=3,
                                 reset_every=150), 2, 1)
    ingest_routed(ms, "timeseries",
                  histogram_stream(histogram_series(2), 300,
                                   start_ms=START * 1000), 2, 1)
    svc = QueryService(ms, "timeseries", 2, spread=1)
    tag = "dev" if device_pages else "host"

    r = svc.query_range("sum(rate(http_requests_total[5m]))",
                        START + 1800, 60, START + 3600).result
    vals = r.values[np.isfinite(r.values)]
    out[f"{tag}_rate_median"] = float(np.median(vals))

    r = svc.query_range("avg_over_time(heap_usage[5m])",
                        START + 1800, 300, START + 3600).result
    out[f"{tag}_gauge_series"] = r.num_series
    out[f"{tag}_gauge_finite"] = bool(np.isfinite(r.values).all())

    r = svc.query_range(
        "histogram_quantile(0.9, rate(http_req_latency[5m]))",
        START + 1500, 300, START + 2700).result
    hv = r.values[np.isfinite(r.values)]
    out[f"{tag}_hist_ok"] = bool(len(hv) and (hv > 0).all()
                                 and (hv <= 10.0).all())

    r = svc.query_range("topk(2, max_over_time(heap_usage[5m]))",
                        START + 1800, 300, START + 2400).result
    out[f"{tag}_topk_present"] = int((~np.isnan(r.values)).sum(0).max())

print(json.dumps(out))
"""


def test_f32_engine_mode():
    env = dict(os.environ)
    env.pop("JAX_ENABLE_X64", None)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    for tag in ("host", "dev"):
        assert out[f"{tag}_gauge_series"] == 6
        assert out[f"{tag}_gauge_finite"]
        assert out[f"{tag}_hist_ok"]
        assert out[f"{tag}_topk_present"] == 2
        assert out[f"{tag}_rate_median"] > 0
    # host vs device paths agree in f32 too
    assert abs(out["host_rate_median"] - out["dev_rate_median"]) \
        / out["host_rate_median"] < 1e-3


BIG_COUNTER_SCRIPT = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("JAX_ENABLE_X64", None)
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", MODE == "f64")
assert jax.config.jax_enable_x64 == (MODE == "f64")

import json
import numpy as np
from filodb_tpu.coordinator.ingestion import ingest_routed
from filodb_tpu.coordinator.query_service import QueryService
from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.core.store.config import StoreConfig
from filodb_tpu.testing.data import counter_series, counter_stream

START = 1_600_000_000

ms = TimeSeriesMemStore()
for s in range(2):
    ms.setup("timeseries", s, StoreConfig(max_chunk_size=100))
# long-lived counters: values start at 2e9 (>> 2^24 = 16.7M), per-sample
# deltas ~10 — an f32 cast of the raw values collapses every window delta
ingest_routed(ms, "timeseries",
              counter_stream(counter_series(4), 400, start_ms=START * 1000,
                             seed=7, start_value=2.0e9), 2, 1)
# and one set WITH resets at the big magnitude
ingest_routed(ms, "timeseries",
              counter_stream(counter_series(3, metric="reset_total"), 400,
                             start_ms=START * 1000, seed=8, reset_every=120,
                             start_value=3.0e9), 2, 1)

out = {}
for engine in ("exec", "mesh"):
    svc = QueryService(ms, "timeseries", 2, spread=1, engine=engine)
    r = svc.query_range("sum(rate(http_requests_total[5m]))",
                        START + 1800, 60, START + 3600).result
    out[f"{engine}_rate"] = np.asarray(r.values)[0].tolist()
    r = svc.query_range("sum(increase(reset_total[10m]))",
                        START + 1800, 120, START + 3600).result
    out[f"{engine}_increase"] = np.asarray(r.values)[0].tolist()
    r = svc.query_range("delta(http_requests_total[5m])",
                        START + 1800, 300, START + 3600).result
    out[f"{engine}_delta"] = np.asarray(r.values).tolist()
print(json.dumps(out))
"""


def _run_big_counter(mode):
    env = dict(os.environ)
    env.pop("JAX_ENABLE_X64", None)
    env["JAX_PLATFORMS"] = "cpu"
    script = f"MODE = {mode!r}\n" + BIG_COUNTER_SCRIPT
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_f32_counter_precision_rebased():
    """Counters >= 1e9 with per-window deltas ~10.
    The f32 device path (exec kernels AND the mesh engine) must match the
    f64 host path to rtol 1e-5 — without per-series f64 rebasing the f32
    cast returns garbage (window deltas collapse to 0 or +/-256)."""
    f32 = _run_big_counter("f32")
    f64 = _run_big_counter("f64")
    for key in ("exec_rate", "mesh_rate", "exec_increase", "mesh_increase",
                "exec_delta", "mesh_delta"):
        a = np.asarray(f32[key], float)
        b = np.asarray(f64[key], float)
        assert a.shape == b.shape
        finite = np.isfinite(b)
        assert finite.any(), key
        np.testing.assert_allclose(a[finite], b[finite], rtol=1e-5,
                                   err_msg=key)
        # sanity: the rates are real (deltas ~10 per 10s => ~1/s per series)
        if key.endswith("_rate"):
            assert (np.abs(b[finite]) > 0.1).all()


PLACED_SCRIPT = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("JAX_ENABLE_X64", None)
import jax
jax.config.update("jax_platforms", "cpu")
assert not jax.config.jax_enable_x64

import json
import numpy as np
sys.path.insert(0, os.path.join(os.getcwd(), "tests"))
from mesh_oracle import (CASES, MESHES, pad_leased, pad_reused,
                         placed_mismatches, run_case)

stores, out = {}, {}
POISONED = ("raw-avg", "raw-max-fused", "split-small", "split-big",
            "histogram-split", "split-big-rate", "split-delta-counter",
            "histogram-split-one-group")
for case in CASES:
    for mesh_name in MESHES:
        cap = run_case(case, mesh_name, stores)
        ts, vals, raw = cap.got[0], cap.got[1], cap.got[4]
        P_, S_ = cap.f64.ts.shape
        out[f"{case}/{mesh_name}"] = {
            "bad": placed_mismatches(cap),
            "dtype": str(vals.dtype),
            "batch_dtype": str(cap.batch.vals.dtype),
            "own": ts is cap.batch.ts and vals is cap.batch.vals,
            # numpy's rounding of the f64 samples, exactly
            "exact": cap.f64.vals.ndim == 2 and bool(np.array_equal(
                vals[:P_, :S_], np.asarray(
                    np.nan_to_num(cap.f64.vals, nan=0.0), np.float32))),
            "raw": raw is not None,
            "copied_bytes": cap.tags["mesh-pad"]["copied_bytes"],
            "vals_bytes": vals.nbytes,
        }

# the same builds into POISONED staging buffers (an earlier run's, given
# back and overwritten with garbage): f32 ``vals`` refilled with 0 on the
# raw lane, f64 with NaN where a host pass follows, and mesh-pad's own
# arrays (the mask, the f32 copies, a histogram's bucket rows)
for case in POISONED:
    cap = run_case(case, "2x2", stores, poisoned=True)
    out[f"poisoned/{case}"] = {
        "bad": placed_mismatches(cap),
        "dtype": str(cap.got[1].dtype),
        "batch_dtype": str(cap.batch.vals.dtype),
        "reused": cap.tags["batch-stack"]["reused_bytes"],
        "built": cap.batch.ts.nbytes + cap.batch.vals.nbytes,
        "pad_reused": pad_reused(cap),
        "pad_leased": pad_leased(cap),
    }

# the batch build itself: rows filled by the native shard cores against the
# per-series builder, in the dtype a server places (f32 here)
from batch_oracle import RANGES, World, mismatches, per_series_batch
from filodb_tpu.core.memstore.native_shard import native_available
from filodb_tpu.query.engine.batch import build_batch

world = World() if native_available() else None
for rng, (lo, hi) in RANGES.items() if world else ():
    with np.errstate(over="ignore"):
        got = build_batch(world.parts, lo, hi, host_f64=False,
                          mesh_multiples=(4, 2))
        want = per_series_batch(world.parts, lo, hi, host_f64=False,
                                mesh_multiples=(4, 2))
        f64 = per_series_batch(world.parts, lo, hi, mesh_multiples=(4, 2))
        out[f"native-fill/{rng}"] = {
            "bad": mismatches(got, want)
            + mismatches(build_batch(world.parts, lo, hi,
                                     mesh_multiples=(4, 2)), f64),
            "dtype": str(got.vals.dtype),
            # numpy's rounding of the f64 samples, exactly
            "exact": got.vals.tobytes() == np.asarray(
                np.nan_to_num(f64.vals, nan=0.0), np.float32).tobytes(),
            "samples": int(got.counts.sum()),
        }
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def placed_f32():
    """The equivalence matrix of ``test_mesh_batch_once.py`` with x64 off,
    as a server runs: every lane on every mesh, in ONE subprocess."""
    env = dict(os.environ)
    env.pop("JAX_ENABLE_X64", None)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", PLACED_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", CASES)
def test_f32_placed_arrays_are_the_parents_bits(case, placed_f32):
    """With x64 off the parent's put rounded f64 to f32; the placed arrays
    now ARE f32, and hold the same bits, on every mesh."""
    for mesh_name in MESHES:
        cell = placed_f32[f"{case}/{mesh_name}"]
        assert cell["bad"] == [], mesh_name
        assert cell["dtype"] == "float32", mesh_name


@pytest.mark.parametrize("case", ["raw-avg", "raw-max-fused",
                                  "raw-last-sample"])
def test_f32_raw_lane_is_built_in_f32_and_placed_as_built(case, placed_f32):
    for mesh_name in MESHES:
        cell = placed_f32[f"{case}/{mesh_name}"]
        assert cell["batch_dtype"] == "float32" and cell["own"], mesh_name
        assert cell["exact"], mesh_name
        assert cell["copied_bytes"] == 0 and not cell["raw"], mesh_name


def test_f32_delta_lanes_keep_f64_and_copy_once(placed_f32):
    for case in ("split-small", "split-big", "split-delta", "split-big-rate",
                 "split-delta-sum", "split-delta-counter"):
        cell = placed_f32[f"{case}/2x2"]
        assert cell["batch_dtype"] == "float64" and not cell["own"], case
        # the split lane at >= F32_SAFE_MAX falls back to the host f64
        # pre-pass, which for increase places the raw values too
        two = case in ("split-big", "split-big-rate")
        assert cell["raw"] == two, case
        assert cell["copied_bytes"] == (2 if two else 1) * cell["vals_bytes"]


@pytest.mark.parametrize("rng", RANGES)
def test_f32_native_fill_is_the_per_series_builders_bits(rng, placed_f32):
    """x64 off: ``build_batch`` allocates f32 and the native fill narrows in
    C — the same bits as numpy's f64 -> f32 row assignment, and the f64
    batch (``host_f64``) stays the per-series builder's too."""
    if f"native-fill/{rng}" not in placed_f32:
        pytest.skip("native library unavailable")
    cell = placed_f32[f"native-fill/{rng}"]
    assert cell["bad"] == []
    assert cell["dtype"] == "float32" and cell["exact"]
    assert (cell["samples"] > 0) == (rng not in ("before", "after"))


@pytest.mark.parametrize("case,batch_dtype", [
    ("raw-avg", "float32"), ("raw-max-fused", "float32"),
    ("split-small", "float64"), ("split-big", "float64"),
    ("histogram-split", "float64"), ("split-big-rate", "float64"),
    ("split-delta-counter", "float64"),
    ("histogram-split-one-group", "float64")])
def test_f32_poisoned_staging_buffers_place_the_parents_bits(
        case, batch_dtype, placed_f32):
    """x64 off: the raw lane's f32 ``vals`` (0 padding) and the split
    lane's f64 (NaN padding, with and without the host pre-pass) written
    into staging buffers full of garbage, and after them the f32 copies
    ``mesh-pad`` converts, a histogram's flattened into bucket rows — the
    device receives the parent's bits."""
    cell = placed_f32[f"poisoned/{case}"]
    assert cell["bad"] == []
    assert (cell["dtype"], cell["batch_dtype"]) == ("float32", batch_dtype)
    assert cell["reused"] == cell["built"] > 0
    mask, made = cell["pad_leased"]
    assert mask > 0 and (made > 0) == (batch_dtype == "float64")
    # a histogram takes its bucket rows inside hist-flatten
    assert cell["pad_reused"] == ([mask, made] if "histogram" in case
                                  else [mask + made, 0])
