"""Mesh query engine parity: PromQL → planner → (shard × time) device mesh.

The mesh path (``parallel/mesh_engine.py``) must return byte-comparable
results to the scatter-gather exec path for every supported plan shape, on
the virtual 8-device CPU mesh (conftest sets
``--xla_force_host_platform_device_count=8``). Reference boundary replaced:
``query/src/main/scala/filodb/query/exec/ExecPlan.scala:41`` scatter-gather.
"""

import numpy as np
import pytest

from filodb_tpu.coordinator.ingestion import ingest_routed
from filodb_tpu.coordinator.query_service import QueryService
from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.core.store.config import StoreConfig
from filodb_tpu.testing.data import (
    counter_series,
    counter_stream,
    gauge_stream,
    machine_metrics_series,
)

START = 1_600_000_000
NUM_SHARDS = 4


def build_store(kind="counter", n_series=24, n_samples=240):
    ms = TimeSeriesMemStore()
    for s in range(NUM_SHARDS):
        ms.setup("timeseries", s, StoreConfig(max_chunk_size=100,
                                              groups_per_shard=4))
    if kind == "counter":
        keys = counter_series(n_series, metric="http_requests_total")
        stream = counter_stream(keys, n_samples, start_ms=START * 1000,
                                interval_ms=10_000, seed=3)
    else:
        keys = machine_metrics_series(n_series, metric="gauge_metric")
        stream = gauge_stream(keys, n_samples, start_ms=START * 1000,
                              interval_ms=10_000, seed=3)
    ingest_routed(ms, "timeseries", stream, NUM_SHARDS, spread=1)
    return ms


def services(ms):
    exec_svc = QueryService(ms, "timeseries", NUM_SHARDS, spread=1)
    mesh_svc = QueryService(ms, "timeseries", NUM_SHARDS, spread=1,
                            engine="mesh")
    return exec_svc, mesh_svc


def assert_same(r_exec, r_mesh):
    e, m = r_exec.result, r_mesh.result
    assert sorted(map(str, e.keys)) == sorted(map(str, m.keys))
    np.testing.assert_array_equal(e.steps_ms, m.steps_ms)
    order_e = np.argsort([str(k) for k in e.keys])
    order_m = np.argsort([str(k) for k in m.keys])
    np.testing.assert_allclose(e.values[order_e], m.values[order_m],
                               rtol=1e-6, atol=1e-9, equal_nan=True)


class TestMeshParity:
    @pytest.fixture(scope="class")
    def counter_store(self):
        return build_store("counter")

    @pytest.fixture(scope="class")
    def gauge_store(self):
        return build_store("gauge")

    def q(self, svc, query):
        return svc.query_range(query, START + 600, 60, START + 1800)

    def test_sum_rate_global(self, counter_store):
        e, m = services(counter_store)
        query = 'sum(rate(http_requests_total[5m]))'
        assert_same(self.q(e, query), self.q(m, query))

    def test_sum_rate_by_labels(self, counter_store):
        e, m = services(counter_store)
        query = 'sum(rate(http_requests_total[5m])) by (_ns_)'
        assert_same(self.q(e, query), self.q(m, query))

    def test_sum_rate_with_filters(self, counter_store):
        e, m = services(counter_store)
        query = 'sum(rate(http_requests_total{_ns_="App-0"}[2m])) by (instance)'
        assert_same(self.q(e, query), self.q(m, query))

    @pytest.mark.parametrize("fn", ["sum_over_time", "count_over_time",
                                    "avg_over_time", "min_over_time",
                                    "max_over_time", "last_over_time"])
    @pytest.mark.parametrize("agg", ["sum", "avg", "count", "min", "max"])
    def test_agg_fn_matrix(self, gauge_store, fn, agg):
        e, m = services(gauge_store)
        query = f'{agg}({fn}(gauge_metric[3m])) by (_ns_)'
        assert_same(self.q(e, query), self.q(m, query))

    def test_by_metric_label_groups_on_nothing(self, counter_store):
        # exec drops the metric label from range-fn output keys before
        # grouping; by (_metric_) must therefore collapse to one group
        e, m = services(counter_store)
        query = 'sum(rate(http_requests_total[5m])) by (_metric_)'
        re, rm = self.q(e, query), self.q(m, query)
        assert_same(re, rm)
        assert rm.result.num_series == 1
        assert rm.result.keys[0].labels == ()

    def test_sample_limit_enforced_on_mesh_path(self, counter_store):
        from filodb_tpu.query.model import (
            PlannerParams,
            QueryContext,
            QueryLimitExceeded,
        )
        _, m = services(counter_store)
        qctx = QueryContext(planner_params=PlannerParams(
            enforce_sample_limit=True, sample_limit=3))
        with pytest.raises(QueryLimitExceeded):
            m.query_range('sum(rate(http_requests_total[5m])) by (instance)',
                          START + 600, 60, START + 1800, qcontext=qctx)

    def test_instant_query(self, counter_store):
        e, m = services(counter_store)
        query = 'sum(rate(http_requests_total[5m])) by (_ns_)'
        re = e.query_instant(query, START + 1200)
        rm = m.query_instant(query, START + 1200)
        assert_same(re, rm)

    def test_empty_selector(self, counter_store):
        e, m = services(counter_store)
        query = 'sum(rate(no_such_metric[5m]))'
        re, rm = self.q(e, query), self.q(m, query)
        assert re.result.num_series == rm.result.num_series == 0

    def test_mesh_used_not_fallback(self, counter_store):
        _, m = services(counter_store)
        plan_hits = []
        orig = m.mesh_engine.execute

        def spy(*a, **kw):
            out = orig(*a, **kw)
            plan_hits.append(out is not None)
            return out

        m.mesh_engine.execute = spy
        self.q(m, 'sum(rate(http_requests_total[5m])) by (_ns_)')
        assert plan_hits == [True]

    def test_unsupported_shapes_fall_back(self, counter_store):
        _, m = services(counter_store)
        # offset / unsupported fn / binary join: exec path answers them
        for query in [
            'sum(rate(http_requests_total[5m] offset 1m))',
            'sum(deriv(http_requests_total[5m]))',
            'topk(2, rate(http_requests_total[5m]))',
            'rate(http_requests_total[5m])',
        ]:
            r = self.q(m, query)
            assert r is not None  # executes via fallback without raising

    def test_mesh_skipped_when_shards_partial(self):
        # a coordinator facade in a multi-node cluster holds only its own
        # shards; the mesh must not serve partial data
        ms = TimeSeriesMemStore()
        for s in range(2):  # only 2 of 4 shards local
            ms.setup("timeseries", s, StoreConfig())
        svc = QueryService(ms, "timeseries", num_shards=4, spread=1,
                           engine="mesh")
        assert not svc._mesh_eligible()
        called = []
        svc.mesh_engine.execute = lambda *a, **kw: called.append(1)
        # the exec fallback needs remote dispatchers for the missing shards
        # (not wired in this test); the point is the mesh never engages
        with pytest.raises(KeyError):
            svc.query_range('sum(rate(x[5m]))', START, 60, START + 600)
        assert not called

    def test_topk_wrapper_on_mesh(self, counter_store):
        e, m = services(counter_store)
        query = 'topk(2, sum(rate(http_requests_total[5m])) by (instance))'
        re, rm = self.q(e, query), self.q(m, query)
        assert_same(re, rm)
        # the mesh path actually engaged (not the exec fallback)
        hits = []
        orig = m.mesh_engine.execute
        m.mesh_engine.execute = lambda *a, **kw: (hits.append(1),
                                                  orig(*a, **kw))[1]
        self.q(m, query)
        assert hits


class TestDeviceFailureSurfaces:
    """A failure inside the device engine (compile refusal, out of memory)
    is the outcome of the query that caused it. It is never re-answered
    from the exec tree: that would serve a device fault as a success."""

    QUERIES = [('sum(rate(http_requests_total[5m])) by (_ns_)',
                START + 600, 60, START + 1500),
               ('max(max_over_time(http_requests_total[3m]))',
                START + 600, 60, START + 1500),
               # not a mesh shape: answered by the exec tree either way
               ('sum(deriv(http_requests_total[5m]))',
                START + 600, 60, START + 1500)]

    @pytest.fixture(scope="class")
    def counter_store(self):
        return build_store("counter")

    @staticmethod
    def _poison(svc, fn_name):
        orig = svc.mesh_engine.execute_lowered_many

        def failing(lows, *a, **kw):
            if lows[0].fn == fn_name:
                raise MemoryError("RESOURCE_EXHAUSTED: out of device memory")
            return orig(lows, *a, **kw)

        svc.mesh_engine.execute_lowered_many = failing

    def test_batch_member_gets_its_own_failure(self, counter_store):
        from filodb_tpu.parallel.mesh_engine import _M_FALLBACK
        e, m = services(counter_store)
        self._poison(m, "rate")
        errors0 = _M_FALLBACK["error"].value
        out = m.query_range_many(self.QUERIES, return_errors=True)
        assert isinstance(out[0], MemoryError)
        assert _M_FALLBACK["error"].value > errors0
        for got, q in zip(out[1:], self.QUERIES[1:]):
            assert_same(e.query_range(*q), got)

    def test_without_return_errors_the_failure_raises(self, counter_store):
        _, m = services(counter_store)
        self._poison(m, "rate")
        with pytest.raises(MemoryError):
            m.query_range_many(self.QUERIES)

    def test_single_query_failure_raises(self, counter_store):
        _, m = services(counter_store)
        self._poison(m, "rate")
        with pytest.raises(MemoryError):
            m.query_range(*self.QUERIES[0])


class TestMeshWidenedCoverage:
    """The widened plan family: offsets, without,
    raw/un-aggregated selectors, instant-selector staleness, more range fns
    and agg ops, instant-fn/scalar post-transforms, and batched multi-query
    execution."""

    @pytest.fixture(scope="class")
    def counter_store(self):
        return build_store("counter")

    @pytest.fixture(scope="class")
    def gauge_store(self):
        return build_store("gauge")

    def q(self, svc, query):
        return svc.query_range(query, START + 600, 60, START + 1800)

    def _mesh_engaged(self, m, query):
        eng = m.mesh_engine
        calls = []
        orig = eng.execute
        eng.execute = lambda *a, **kw: (calls.append(1), orig(*a, **kw))[1]
        try:
            self.q(m, query)
        finally:
            eng.execute = orig
        return bool(calls)

    @pytest.mark.parametrize("query", [
        'sum(rate(http_requests_total[5m] offset 2m))',
        'sum(rate(http_requests_total[5m] offset 2m)) by (_ns_)',
        'avg(increase(http_requests_total[5m]))',
        'sum(delta(http_requests_total[5m]))',
    ])
    def test_offsets_and_counter_family(self, counter_store, query):
        e, m = services(counter_store)
        assert_same(self.q(e, query), self.q(m, query))
        assert self._mesh_engaged(m, query)

    @pytest.mark.parametrize("query", [
        'sum(sum_over_time(gauge_metric[3m])) without (instance)',
        'stddev(max_over_time(gauge_metric[3m])) by (_ns_)',
        'stdvar(avg_over_time(gauge_metric[3m]))',
        'group(last_over_time(gauge_metric[3m])) by (_ns_)',
        'sum(present_over_time(gauge_metric[3m]))',
        'avg(stddev_over_time(gauge_metric[3m])) by (_ns_)',
        'max(stdvar_over_time(gauge_metric[3m]))',
    ])
    def test_without_and_new_fns_aggs(self, gauge_store, query):
        e, m = services(gauge_store)
        assert_same(self.q(e, query), self.q(m, query))
        assert self._mesh_engaged(m, query)

    @pytest.mark.parametrize("query", [
        'http_requests_total',                  # raw instant selector
        'http_requests_total{_ns_="App-0"}',
        'rate(http_requests_total[5m])',        # un-aggregated range fn
        'max_over_time(http_requests_total[4m])',
    ])
    def test_per_series_outputs(self, counter_store, query):
        e, m = services(counter_store)
        assert_same(self.q(e, query), self.q(m, query))
        assert self._mesh_engaged(m, query)

    @pytest.mark.parametrize("query", [
        'abs(sum(rate(http_requests_total[5m])) by (_ns_))',
        'clamp_max(sum(rate(http_requests_total[5m])), 0.5)',
        'sqrt(avg(rate(http_requests_total[5m])))',
        '2 * sum(rate(http_requests_total[5m])) by (_ns_)',
        'sum(rate(http_requests_total[5m])) by (_ns_) > 0.2',
        'sum(rate(http_requests_total[5m])) by (_ns_) > bool 0.2',
        'topk(2, rate(http_requests_total[5m]))',
    ])
    def test_post_transforms(self, counter_store, query):
        e, m = services(counter_store)
        assert_same(self.q(e, query), self.q(m, query))
        assert self._mesh_engaged(m, query)

    def test_execute_many_batches_one_program(self, counter_store):
        # distinct step grids, same signature → one kernel call, sliced back
        e, m = services(counter_store)
        eng = m.mesh_engine
        query = 'sum(rate(http_requests_total[5m])) by (_ns_)'
        ranges = [(START + 600 + 120 * i, 60, START + 1500 + 60 * i)
                  for i in range(5)]
        qs = [(query, s, st, en) for (s, st, en) in ranges]
        lowered_calls = []
        orig = eng.execute_lowered_many
        eng.execute_lowered_many = lambda lows, *a, **kw: (
            lowered_calls.append(len(lows)), orig(lows, *a, **kw))[1]
        rm = m.query_range_many(qs)
        eng.execute_lowered_many = orig
        assert lowered_calls == [5]  # one program for the whole group
        for (s, st, en), r in zip(ranges, rm):
            re = e.query_range(query, s, st, en)
            assert_same(re, r)

    def test_execute_many_mixed_support(self, counter_store):
        # unsupported member of the batch falls back to the exec path
        e, m = services(counter_store)
        query_ok = 'sum(rate(http_requests_total[5m]))'
        query_fb = 'sum(deriv(http_requests_total[5m]))'
        qs = [(query_ok, START + 600, 60, START + 1800),
              (query_fb, START + 600, 60, START + 1800)]
        rm = m.query_range_many(qs)
        for (qq, s, st, en), r in zip(qs, rm):
            assert_same(e.query_range(qq, s, st, en), r)

    def test_hit_rate_accounting(self, counter_store):
        _, m = services(counter_store)
        self.q(m, 'sum(rate(http_requests_total[5m]))')
        self.q(m, 'sum(deriv(http_requests_total[5m]))')
        eng = m.mesh_engine
        assert eng.hits >= 1 and eng.misses >= 1
        assert 0.0 < eng.hit_rate < 1.0


class TestMeshODP:
    """Cold data must reach the mesh path via on-demand paging, exactly as
    it reaches the exec path (regression: after a restart, replayed shards
    hold only post-checkpoint tails — the mesh engine returned NaN for all
    flushed history until it learned to call ``page_partitions``)."""

    def test_mesh_reads_evicted_chunks(self, tmp_path):
        from filodb_tpu.core.store.localstore import (
            LocalDiskColumnStore,
            LocalDiskMetaStore,
        )

        cs = LocalDiskColumnStore(str(tmp_path / "data"))
        meta = LocalDiskMetaStore(str(tmp_path / "data"))
        ms = TimeSeriesMemStore(cs, meta)
        ms.setup("timeseries", 0, StoreConfig(max_chunk_size=50,
                                              groups_per_shard=4))
        keys = machine_metrics_series(4)
        shard = ms.get_shard("timeseries", 0)
        for sd in gauge_stream(keys, 300, start_ms=START * 1000):
            shard.ingest(sd)
        shard.flush_all(ingestion_time=1)
        assert sum(shard.evict_partition_chunks(p.part_id)
                   for p in shard.partitions if p) > 0

        exec_svc = QueryService(ms, "timeseries", 1, spread=0)
        mesh_svc = QueryService(ms, "timeseries", 1, spread=0, engine="mesh")
        q = 'count_over_time(heap_usage[55m])'
        re = exec_svc.query_range(q, START + 3000, 60, START + 3000)
        rm = mesh_svc.query_range(q, START + 3000, 60, START + 3000)
        assert_same(re, rm)
        assert rm.result.num_series == 4
        np.testing.assert_array_equal(np.asarray(rm.result.values)[:, 0],
                                      300.0)


def build_hist_store(n_series=8, n_samples=240):
    from filodb_tpu.testing.data import histogram_series, histogram_stream
    ms = TimeSeriesMemStore()
    for s in range(NUM_SHARDS):
        ms.setup("timeseries", s, StoreConfig(max_chunk_size=100,
                                              groups_per_shard=4))
    keys = histogram_series(n_series, metric="http_req_latency")
    stream = histogram_stream(keys, n_samples, start_ms=START * 1000,
                              interval_ms=10_000, seed=11)
    ingest_routed(ms, "timeseries", stream, NUM_SHARDS, spread=1)
    return ms


class TestMeshHistogram:
    """First-class histograms on the mesh path: buckets
    flatten into the series axis; results must match the exec path."""

    @pytest.fixture(scope="class")
    def hist_store(self):
        return build_hist_store()

    def q(self, svc, query):
        return svc.query_range(query, START + 600, 60, START + 1800)

    def _mesh_must_handle(self, m_svc, query):
        eng = m_svc.mesh_engine
        hits0 = eng.hits
        r = self.q(m_svc, query)
        assert eng.hits > hits0, f"mesh engine fell back for {query}"
        return r

    def test_hist_quantile_sum_rate(self, hist_store):
        e, m = services(hist_store)
        query = ('histogram_quantile(0.9, '
                 'sum(rate(http_req_latency[5m])))')
        re = self.q(e, query)
        rm = self._mesh_must_handle(m, query)
        assert_same(re, rm)

    def test_hist_quantile_sum_rate_by_app(self, hist_store):
        e, m = services(hist_store)
        query = ('histogram_quantile(0.5, '
                 'sum(rate(http_req_latency[5m])) by (app))')
        assert_same(self.q(e, query), self._mesh_must_handle(m, query))

    def test_hist_sum_rate_raw_buckets(self, hist_store):
        # no quantile: result is a histogram matrix; still mesh-served
        e, m = services(hist_store)
        query = 'sum(rate(http_req_latency[5m])) by (app)'
        re, rm = self.q(e, query), self._mesh_must_handle(m, query)
        ev, mv = re.result, rm.result
        assert ev.is_histogram and mv.is_histogram
        assert_same(re, rm)

    def test_hist_per_series_rate(self, hist_store):
        e, m = services(hist_store)
        query = 'rate(http_req_latency[5m])'
        assert_same(self.q(e, query), self._mesh_must_handle(m, query))

    def test_hist_increase_quantile(self, hist_store):
        e, m = services(hist_store)
        query = ('histogram_quantile(0.99, '
                 'sum(increase(http_req_latency[10m])))')
        assert_same(self.q(e, query), self._mesh_must_handle(m, query))

    def test_hist_unsupported_agg_falls_back(self, hist_store):
        # min is not bucket-wise meaningful here; exec path must serve it
        e, m = services(hist_store)
        query = 'min(rate(http_req_latency[5m]))'
        assert_same(self.q(e, query), self.q(m, query))

    def test_unsupported_agg_after_cached_sum(self, hist_store):
        # regression: a hist batch cached under sum(...) must not satisfy a
        # later min(...) over the same selector via the cache-hit branch
        e, m = services(hist_store)
        q_sum = 'sum(rate(http_req_latency[5m]))'
        q_min = 'min(rate(http_req_latency[5m]))'
        self.q(m, q_sum)  # populate the batch cache
        assert_same(self.q(e, q_min), self.q(m, q_min))
