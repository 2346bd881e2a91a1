"""Split-pipeline mesh execution: prepare/bounds/eval caches + per-query
group reduce (``parallel/dist_query.py`` / ``parallel/mesh_engine.py``).

The reference is the scatter-gather exec tree (``engine="exec"``), which
shares no kernel helper with the mesh programs: every range function that
has a split form, every aggregate, per-series output and the edge shapes
must agree with it on the 8-virtual-device mesh the conftest forces.
"""

import numpy as np
import pytest

from filodb_tpu.coordinator.ingestion import ingest_routed
from filodb_tpu.coordinator.query_service import QueryService
from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.core.store.config import StoreConfig
from filodb_tpu.parallel.dist_query import SPLIT_FNS
from filodb_tpu.parallel.mesh_engine import (
    _M_DISPATCH,
    _M_EVAL,
    F32_SAFE_MAX,
    MESH_FNS,
    _device_correction_ok,
)
from filodb_tpu.promql.parser import TimeStepParams, parse_query
from filodb_tpu.testing.data import (
    counter_series,
    counter_stream,
    gauge_stream,
    machine_metrics_series,
)

START = 1_600_000_000
NUM_SHARDS = 4


def build_store(kind="counter", n_series=37, n_samples=240):
    """37 series: not a multiple of any mesh axis, so the shard axis pads;
    240 samples over 4 shards exercises the time axis too."""
    ms = TimeSeriesMemStore()
    for s in range(NUM_SHARDS):
        ms.setup("timeseries", s, StoreConfig(max_chunk_size=100,
                                              groups_per_shard=4))
    if kind == "counter":
        keys = counter_series(n_series, metric="http_requests_total")
        stream = counter_stream(keys, n_samples, start_ms=START * 1000,
                                interval_ms=10_000, seed=7)
    else:
        keys = machine_metrics_series(n_series, metric="gauge_metric")
        stream = gauge_stream(keys, n_samples, start_ms=START * 1000,
                              interval_ms=10_000, seed=7)
    ingest_routed(ms, "timeseries", stream, NUM_SHARDS, spread=1)
    # uneven tails: a third of the series keep reporting for another 40
    # samples, so per-series counts (and the padded valid mask) differ
    extra = counter_stream(keys[::3],
                           40, start_ms=(START + n_samples * 10) * 1000,
                           interval_ms=10_000, seed=8) \
        if kind == "counter" else \
        gauge_stream(keys[::3], 40,
                     start_ms=(START + n_samples * 10) * 1000,
                     interval_ms=10_000, seed=8)
    ingest_routed(ms, "timeseries", extra, NUM_SHARDS, spread=1)
    return ms


def mesh_and_exec(ms, query, start=START + 600, step=60,
                  end=START + 2800):
    """Evaluate one query on the mesh (it must lower: no silent fallback)
    and through the exec tree (the result cache is off on a bare
    QueryService, so the mesh run hits the device)."""
    svc = QueryService(ms, "timeseries", NUM_SHARDS, spread=1,
                       engine="mesh")
    eng = svc.mesh_engine
    low = eng._lower(parse_query(query, TimeStepParams(start, step, end)))
    assert low is not None, f"{query} must lower"
    on_mesh = eng.execute_lowered_many([low], ms, "timeseries")[0]
    ref = QueryService(ms, "timeseries", NUM_SHARDS, spread=1,
                       engine="exec").query_range(query, start, step, end)
    return on_mesh.materialize(), ref.result.materialize(), svc


def assert_close(a, b):
    assert sorted(map(str, a.keys)) == sorted(map(str, b.keys))
    oa = np.argsort([str(k) for k in a.keys])
    ob = np.argsort([str(k) for k in b.keys])
    np.testing.assert_allclose(np.asarray(a.values)[oa],
                               np.asarray(b.values)[ob],
                               rtol=1e-9, atol=1e-7, equal_nan=True)


def _fn_query(fn):
    metric = "http_requests_total" if fn in ("rate", "increase") \
        else "gauge_metric"
    return f"sum({fn}({metric}[5m])) by (_ns_)"


class TestSplitEqualsExec:
    """The split path against the scatter-gather exec reference."""

    @pytest.fixture(scope="class")
    def counter_store(self):
        return build_store("counter")

    @pytest.fixture(scope="class")
    def gauge_store(self):
        return build_store("gauge")

    @pytest.mark.parametrize("query", [
        "sum(rate(http_requests_total[5m]))",
        "sum(rate(http_requests_total[5m])) by (instance)",
        "avg(increase(http_requests_total[3m])) by (instance)",
        "rate(http_requests_total[5m])",
        'sum(delta(http_requests_total{_ns_="App-0"}[4m]))',
        # every fn with a split form, grouped
        *[_fn_query(fn) for fn in SPLIT_FNS],
        # every aggregate over one inner rate
        *[f"{agg}(rate(http_requests_total[5m]))"
          for agg in ("avg", "min", "max", "count", "stddev")],
        # per series, no aggregate, off the prefix sums
        "avg_over_time(gauge_metric[5m])",
    ])
    def test_exec_parity(self, counter_store, gauge_store, query):
        ms = gauge_store if "gauge_metric" in query else counter_store
        on_mesh, ref, _ = mesh_and_exec(ms, query)
        assert_close(on_mesh, ref)

    def test_windows_outside_data_all_nan(self, counter_store):
        # staleness shape: where every window precedes the data no series
        # comes back, from either engine; where the grid straddles the
        # first sample, the windows that hold <2 samples are NaN steps in
        # both
        query = "sum(rate(http_requests_total[5m]))"
        on_mesh, ref, _ = mesh_and_exec(counter_store, query,
                                        start=START - 3600, end=START - 600)
        assert len(on_mesh.keys) == len(ref.keys) == 0
        on_mesh, ref, _ = mesh_and_exec(counter_store, query,
                                        start=START - 900, end=START + 900)
        assert_close(on_mesh, ref)
        vals = np.asarray(on_mesh.values)
        assert np.isnan(vals[:, :15]).all() and not np.isnan(vals).all()

    def test_delta_counter_schema_reset_corrected(self, counter_store):
        """The uneven-tail restart (values drop back near zero) is a
        counter reset: delta on a COUNTER schema mirrors the exec
        kernels — reset-corrected like rate/increase, but never
        extrapolate-to-zero clamped — so windows spanning the reset stay
        non-negative instead of swinging ~-30000."""
        on_mesh, ref, _ = mesh_and_exec(
            counter_store, "sum(delta(http_requests_total[4m]))")
        assert_close(on_mesh, ref)
        assert np.nanmin(np.asarray(on_mesh.values)) >= 0

    def test_split_dispatch_counted(self, counter_store):
        before = _M_DISPATCH["split"].value
        mesh_and_exec(counter_store,
                      "sum(increase(http_requests_total[5m]))")
        assert _M_DISPATCH["split"].value == before + 1

    def test_eval_cache_shared_across_aggs(self, counter_store):
        """Different aggregations over the same inner range function hit
        ONE cached per-series evaluation — the point of keeping grouping
        out of the eval stage."""
        svc = QueryService(ms := counter_store, "timeseries", NUM_SHARDS,
                           spread=1, engine="mesh")
        eng = svc.mesh_engine
        misses0, hits0 = _M_EVAL["miss"].value, _M_EVAL["hit"].value
        for agg in ("sum", "avg", "max"):
            plan = parse_query(f"{agg}(rate(http_requests_total[5m]))",
                               TimeStepParams(START + 600, 60,
                                              START + 2800))
            eng.execute_lowered_many([eng._lower(plan)], ms,
                                     "timeseries")[0].materialize()
        assert _M_EVAL["miss"].value == misses0 + 1
        assert _M_EVAL["hit"].value == hits0 + 2


class TestOneFormPerFn:
    """Which programs run is decided by the range function alone: the
    environment cannot send a fn with a split form anywhere else."""

    @pytest.fixture(scope="class")
    def gauge_store(self):
        return build_store("gauge", n_series=12, n_samples=120)

    @pytest.mark.parametrize("fn", MESH_FNS)
    def test_dispatch_form(self, gauge_store, fn, monkeypatch):
        # the name in two pieces: a search of the tree for the variable
        # is to find no reader, and this is not one
        monkeypatch.setenv("FILODB_MESH_" "SPLIT", "0")
        svc = QueryService(gauge_store, "timeseries", NUM_SHARDS, spread=1,
                           engine="mesh")
        before = {f: c.value for f, c in _M_DISPATCH.items()}
        res = svc.query_range(f"sum({fn}(gauge_metric[5m]))", START + 600,
                              60, START + 1100)
        assert len(res.result.keys) == 1
        form, other = ("split", "fused") if fn in SPLIT_FNS \
            else ("fused", "split")
        assert _M_DISPATCH[form].value == before[form] + 1
        assert _M_DISPATCH[other].value == before[other]


class TestCacheBehavior:
    def test_caches_invalidate_on_version_bump(self):
        """Prepared correction, bounds, and eval entries are keyed by the
        dataset data_version: new ingest must flow into the next answer,
        not a stale cached evaluation."""
        ms = build_store("counter", n_series=12, n_samples=120)
        svc = QueryService(ms, "timeseries", NUM_SHARDS, spread=1,
                           engine="mesh")
        exec_svc = QueryService(ms, "timeseries", NUM_SHARDS, spread=1)
        args = ("sum(increase(http_requests_total[5m]))", START + 600, 60,
                START + 1100)
        first = svc.query_range(*args).result.materialize()
        keys = counter_series(12, metric="http_requests_total")
        more = counter_stream(keys, 60, start_ms=(START + 1200) * 1000,
                              interval_ms=10_000, seed=9)
        ingest_routed(ms, "timeseries", more, NUM_SHARDS, spread=1)
        args2 = (args[0], START + 600, 60, START + 1700)
        after = svc.query_range(*args2).result.materialize()
        ref = exec_svc.query_range(*args2).result.materialize()
        assert_close(after, ref)
        assert np.asarray(after.values).shape != \
            np.asarray(first.values).shape


class TestPrecisionGate:
    def test_x64_always_ok(self):
        assert _device_correction_ok(np.array([[1e12, np.inf, np.nan]]))

    def test_f32_gate(self, monkeypatch):
        import jax.numpy as jnp

        from filodb_tpu.query.engine import kernels

        monkeypatch.setattr(kernels, "fdtype", lambda: jnp.float32)
        small = np.array([[0.0, 123.5, F32_SAFE_MAX - 1]])
        big = np.array([[0.0, F32_SAFE_MAX]])
        assert _device_correction_ok(small)
        assert not _device_correction_ok(big)
        # non-finite values are masked out by the kernels; only finite
        # magnitudes decide the lane
        assert _device_correction_ok(
            np.array([[np.nan, np.inf, -np.inf, 5.0]]))
        assert _device_correction_ok(np.array([[np.nan]]))
