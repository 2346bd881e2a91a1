"""TPU-numerics simulation: the whole query path with x64 DISABLED (f32/i32
everywhere, as on the real chip). Catches dtype leaks that CPU tests (which
force x64 for exact Prometheus parity) would mask.
"""

import json
import os
import subprocess
import sys

import numpy as np

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
