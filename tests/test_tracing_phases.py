"""What a traced query leaves behind since the mesh engine was opened up:
span start offsets on the wall clock, the engine's phase spans, one trace
for a ``query_range_many`` batch, the render histogram and the device
programs' named scopes."""

import dataclasses
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from filodb_tpu.coordinator.ingestion import ingest_routed
from filodb_tpu.coordinator.query_service import QueryService
from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.core.store.config import StoreConfig
from filodb_tpu.query.engine.batch import build_batch
from filodb_tpu.testing.data import gauge_stream, machine_metrics_series
from filodb_tpu.utils import tracing

NUM_SHARDS = 4
START = 1_600_000_000
PROMQL = "avg by (host)(avg_over_time(gauge_metric[5m]))"
PRE = ("mesh-lookup", "decode", "mesh-group", "mesh-pad", "mesh-place")
POST = ("mesh-dispatch", "mesh-fetch", "mesh-assemble")


@pytest.fixture(scope="module")
def store():
    ms = TimeSeriesMemStore()
    for s in range(NUM_SHARDS):
        ms.setup("timeseries", s, StoreConfig(max_chunk_size=100,
                                              groups_per_shard=4))
    keys = machine_metrics_series(240, metric="gauge_metric")
    ingest_routed(ms, "timeseries",
                  gauge_stream(keys, 240, start_ms=START * 1000,
                               interval_ms=10_000, seed=3),
                  NUM_SHARDS, spread=1)
    return ms


@pytest.fixture(autouse=True)
def trace_everything():
    prev = dataclasses.asdict(tracing.config())
    tracing.configure(sample_rate=1.0, slow_query_threshold_ms=1e-9)
    tracing.flight_recorder().clear()
    yield
    tracing.configure(**prev)
    tracing.flight_recorder().clear()


def mesh_service(store, **kw):
    return QueryService(store, "timeseries", NUM_SHARDS, spread=1,
                        engine="mesh", **kw)


def children(spans, parent):
    return [s for s in spans if s["parent_id"] == parent["span_id"]]


def entries(kind):
    return [e for e in tracing.slow_queries() if e["kind"] == kind]


class TestStartOffsets:
    def test_start_ms_inside_parent_and_before_when(self, store):
        svc = mesh_service(store, result_cache={"extent_steps": 8})
        before_ns = time.time_ns()
        svc.query_range(PROMQL, START + 600, 60, START + 1800)
        (e,) = entries("query")
        assert before_ns <= e["t0_unix_ns"] <= time.time_ns()
        by_id = {s["span_id"]: s for s in e["spans"]}
        assert len(e["spans"]) > 10
        for s in e["spans"]:
            assert s["start_ms"] >= 0.0
            end_ms = s["start_ms"] + s["duration_ms"]
            # 1 ms of room: the entry's clocks are read one after another
            assert e["t0_unix_ns"] / 1e9 + end_ms / 1e3 <= e["when"] + 1e-3
            assert end_ms <= e["duration_ms"] + 0.01
            p = by_id.get(s["parent_id"])
            if p is not None:
                assert p["start_ms"] <= s["start_ms"]
                assert end_ms <= p["start_ms"] + p["duration_ms"] + 0.01

    def test_siblings_do_not_overlap(self, store):
        svc = mesh_service(store)
        svc.query_range(PROMQL, START + 600, 60, START + 1800)
        (e,) = entries("query")
        (eng,) = [s for s in e["spans"] if s["name"] == "mesh-execute"]
        kids = children(e["spans"], eng)
        for a, b in zip(kids, kids[1:]):
            assert a["start_ms"] + a["duration_ms"] <= b["start_ms"] + 0.01

    def test_traced_operation_entry_has_the_clock_pair(self):
        with tracing.traced_operation("rules", group="g"):
            with tracing.span("inner"):
                pass
        (e,) = entries("rules")
        assert e["t0_unix_ns"] / 1e9 <= e["when"]
        assert [s["name"] for s in e["spans"]] == ["rules", "inner"]
        assert all(s["start_ms"] >= 0.0 for s in e["spans"])

    def test_unsampled_slow_query_still_has_t0(self, store):
        tracing.configure(sample_rate=0.0, slow_query_threshold_ms=1e-9)
        mesh_service(store).query_range(PROMQL, START + 600, 60,
                                        START + 1800)
        (e,) = entries("query")
        assert e["spans"] == [] and e["t0_unix_ns"] > 0

    def test_graft_spans_round_trips_start_ms(self):
        with tracing.start_trace() as remote:
            with tracing.span("scan", shard=1):
                with tracing.span("decode"):
                    pass
            with tracing.span("reduce"):
                pass
        shipped = remote.as_dicts()
        with tracing.start_trace() as local:
            with tracing.span("exec-dispatch"):
                with tracing.span("dispatch", peer="p") as d:
                    tracing.graft_spans(shipped, d, node="p")
        got = {s["name"]: s for s in local.as_dicts()}
        base = got["dispatch"]["start_ms"]
        for s in shipped:
            g = got[s["name"]]
            assert g["start_ms"] == pytest.approx(base + s["start_ms"],
                                                  abs=2e-3)
            assert g["duration_ms"] == pytest.approx(s["duration_ms"],
                                                     abs=2e-3)
        assert got["scan"]["tags"] == {"shard": 1, "node": "p"}
        assert got["decode"]["parent_id"] == got["scan"]["span_id"]

    def test_graft_of_a_span_without_start_sits_at_its_parent(self):
        # a peer that runs the previous release ships no start_ms
        with tracing.start_trace() as local:
            with tracing.span("dispatch") as d:
                tracing.graft_spans(
                    [{"name": "scan", "depth": 0, "duration_ms": 1.0,
                      "span_id": 7, "parent_id": 0}], d)
        got = {s["name"]: s for s in local.as_dicts()}
        assert got["scan"]["start_ms"] == got["dispatch"]["start_ms"]


class TestSurfaces:
    def test_slow_queries_endpoint_and_slowlog_cli(self, store, capsys):
        import argparse
        import json

        from filodb_tpu import cli
        from filodb_tpu.http.fastserver import FastHttpServer
        svc = mesh_service(store)
        srv = FastHttpServer({"timeseries": svc}, port=0).start()
        try:
            svc.query_range_many(TestBatchTrace.QUERIES[:2])
            url = (f"http://127.0.0.1:{srv.port}/promql/timeseries/api/v1/"
                   f"debug/slow_queries")
            with urllib.request.urlopen(url, timeout=30) as r:
                got = json.loads(r.read())["data"]["slow_queries"]
            (batch,) = [e for e in got if e["kind"] == "query-batch"]
            assert batch["members"] == 2 and batch["t0_unix_ns"] > 0
            assert all("start_ms" in s for s in batch["spans"])
            cli.cmd_slowlog(argparse.Namespace(
                host=f"127.0.0.1:{srv.port}", dataset="timeseries",
                limit=0, json=False))
        finally:
            srv.stop()
        out = capsys.readouterr().out
        assert "query-batch" in out and "members=2" in out
        assert f"t0_unix_ns={batch['t0_unix_ns']}" in out
        fetch = [ln for ln in out.splitlines() if "mesh-fetch +" in ln]
        assert fetch and fetch[0].rstrip().endswith("]")  # tags follow


class TestMeshPhases:
    def test_every_phase_once_an_extent_and_they_tile(self, store):
        svc = mesh_service(store, result_cache={"extent_steps": 8})
        svc.query_range(PROMQL, START + 600, 60, START + 1800)  # compiles
        tiled = 0.0
        # another phase of the step grid: new extents, new batches. The
        # shape is held on every attempt, the tiling on the best of three
        # (a descheduled test process can land between two spans)
        for shift in (30, 45, 15):
            tracing.flight_recorder().clear()
            svc.query_range(PROMQL, START + 600 + shift, 60,
                            START + 1800 + shift)
            (e,) = entries("query")
            spans = e["spans"]
            engines = [s for s in spans if s["name"] == "mesh-execute"]
            (cache,) = [s for s in spans if s["name"] == "cache"]
            assert len(engines) == cache["tags"]["misses"] >= 2
            shares = []
            for eng in engines:
                kids = children(spans, eng)
                assert [k["name"] for k in kids] == list(PRE + POST)
                shares.append(sum(k["duration_ms"] for k in kids)
                              / eng["duration_ms"])
                (dec,) = [k for k in kids if k["name"] == "decode"]
                assert [k["name"] for k in children(spans, dec)] == \
                    ["batch-read", "batch-stack"]
                assert dec["tags"]["partitions"] == 240
                assert dec["tags"]["samples"] > 0
                assert dec["tags"]["shape"][0] == 256  # 240 series, padded
            # the tails above the engine: one finish an extent, one merge
            under_cache = children(spans, cache)
            names = [k["name"] for k in under_cache]
            assert names.count("finish") == len(engines)
            assert names[-1] == "cache-merge"
            shares.append(sum(k["duration_ms"] for k in under_cache)
                          / cache["duration_ms"])
            tiled = max(tiled, min(shares))
            if tiled >= 0.9:
                break
        assert tiled >= 0.9

    def test_phase_tags_are_the_counts_of_the_work(self, store):
        svc = mesh_service(store)
        svc.query_range(PROMQL, START + 600, 60, START + 1800)
        (e,) = entries("query")
        tags = {s["name"]: s.get("tags", {}) for s in e["spans"]}
        assert tags["mesh-lookup"] == {"shards": NUM_SHARDS,
                                       "partitions": 240, "paged": 0}
        assert tags["mesh-group"]["groups"] > 0
        assert tags["mesh-pad"]["lane"] == "raw"
        assert tags["mesh-place"]["bytes"] > 240 * 240 * 8
        assert tags["mesh-dispatch"]["form"] == "split"
        assert tags["mesh-dispatch"]["programs"] == 1
        assert tags["mesh-dispatch"]["eval_cache"] == "miss"
        assert tags["mesh-dispatch"]["bounds"] == "search"  # a CPU mesh
        assert tags["mesh-fetch"]["bytes"] > 0
        assert tags["mesh-assemble"]["rows"] == tags["mesh-group"]["groups"]
        assert tags["finish"]["series"] == tags["mesh-group"]["groups"]

    def test_another_end_compiles_nothing_and_names_the_bounds_form(
            self, store):
        """A query at another ``end`` is a new grid over a new batch: the
        bounds cache misses and the bounds program runs again, in the form
        the ``mesh-dispatch`` span and the counter name (the search, on a
        CPU mesh) — and no program is traced or compiled for it."""
        from filodb_tpu.utils.metrics import get_counter

        svc = mesh_service(store)
        svc.query_range(PROMQL, START + 600, 60, START + 1800)
        fns = svc.mesh_engine._fns
        compiled = {k: f._cache_size() for k, f in fns.items()}
        assert ("bounds",) in compiled and set(compiled.values()) == {1}
        missed = get_counter("filodb_mesh_bounds_cache",
                             {"event": "miss", "method": "search"})
        before = missed.value
        tracing.flight_recorder().clear()
        svc.query_range(PROMQL, START + 607, 60, START + 1807)
        assert {k: f._cache_size() for k, f in fns.items()} == compiled
        (e,) = entries("query")
        (dispatch,) = [s for s in e["spans"] if s["name"] == "mesh-dispatch"]
        assert dispatch["tags"]["eval_cache"] == "miss"
        assert dispatch["tags"]["bounds"] == "search"
        assert missed.value == before + 1

    def test_batch_cache_hit_opens_the_device_phases_only(self, store):
        svc = mesh_service(store)
        args = (PROMQL, START + 600, 60, START + 1800)
        first = svc.query_range(*args)
        tracing.flight_recorder().clear()
        again = svc.query_range(*args)
        np.testing.assert_array_equal(first.result.values,
                                      again.result.values)
        (e,) = entries("query")
        (eng,) = [s for s in e["spans"] if s["name"] == "mesh-execute"]
        kids = children(e["spans"], eng)
        assert [k["name"] for k in kids] == list(POST)
        assert kids[0]["tags"]["eval_cache"] == "hit"

    @pytest.mark.parametrize("native", [True, False],
                             ids=["native-shards", "python-shards"])
    def test_batch_read_and_stack_once_a_build_and_they_tile_decode(
            self, store, native):
        """``mesh_decode_ms`` and ``mesh_stack_ms`` read these two: each
        opens once a build, under ``decode``, whichever way the rows are
        read, and ``batch-read`` says how many went which way."""
        if native:
            from filodb_tpu.core.memstore.native_shard import native_available
            if not native_available():
                pytest.skip("native library unavailable")
            ms = store
        else:
            ms = TimeSeriesMemStore()
            for s in range(NUM_SHARDS):
                ms.setup("timeseries", s, StoreConfig(
                    max_chunk_size=100, groups_per_shard=4,
                    native_ingest=False))
            ingest_routed(ms, "timeseries", gauge_stream(
                machine_metrics_series(240, metric="gauge_metric"), 240,
                start_ms=START * 1000, interval_ms=10_000, seed=3),
                NUM_SHARDS, spread=1)
        svc = mesh_service(ms)
        svc.query_range(PROMQL, START + 600, 60, START + 1800)  # compiles
        tiled = 0.0
        for shift in (30, 45, 15):
            tracing.flight_recorder().clear()
            svc.query_range(PROMQL, START + 600 + shift, 60,
                            START + 1800 + shift)
            (e,) = entries("query")
            spans = e["spans"]
            (dec,) = [s for s in spans if s["name"] == "decode"]
            read, stack = children(spans, dec)
            assert (read["name"], stack["name"]) == ("batch-read",
                                                     "batch-stack")
            assert [s["name"] for s in spans].count("batch-read") == 1
            assert [s["name"] for s in spans].count("batch-stack") == 1
            rows = (read["tags"]["native_rows"], read["tags"]["fallback_rows"])
            assert rows == ((240, 0) if native else (0, 240))
            assert sum(rows) == read["tags"]["partitions"] == 240
            assert stack["tags"]["shape"] == dec["tags"]["shape"]
            tiled = max(tiled, (read["duration_ms"] + stack["duration_ms"])
                        / dec["duration_ms"])
            if tiled >= 0.9:
                break
        assert tiled >= 0.9

    def test_build_batch_without_a_trace_allocates_no_span(
            self, store, monkeypatch):
        parts = [p for sh in store.shards_for("timeseries")
                 for p in sh.partitions if p][:8]

        def no_span(*a, **kw):
            raise AssertionError("a Span was built with no trace active")

        monkeypatch.setattr(tracing, "Span", no_span)
        assert tracing.current_trace() is None
        batch = build_batch(parts, START * 1000, (START + 2400) * 1000)
        assert int(batch.counts.sum()) == 8 * 240

    def test_new_stage_names_feed_the_stage_histograms(self, store):
        hist = tracing._stage_hists
        for name in PRE + POST + ("batch-read", "batch-stack", "finish",
                                  "cache-merge", "batch-fetch"):
            assert name in hist, name
        n0 = hist["mesh-fetch"].count
        mesh_service(store).query_range(PROMQL, START + 600, 60,
                                        START + 1800)
        assert hist["mesh-fetch"].count == n0 + 1


class TestBatchTrace:
    QUERIES = [(PROMQL, START + 600, 60, START + 1800),
               ("sum(max_over_time(gauge_metric[3m]))",
                START + 600, 60, START + 1500),
               # not a mesh shape: falls through to the exec tree
               ("sum(deriv(gauge_metric[5m]))",
                START + 600, 60, START + 1500)]

    def test_one_query_batch_entry_for_three_members(self, store):
        svc = mesh_service(store)
        out = svc.query_range_many(self.QUERIES)
        assert all(r.result.num_series for r in out)
        (e,) = entries("query-batch")
        assert e["members"] == 3 and e["sampled"] is True
        assert e["dataset"] == "timeseries"
        assert e["t0_unix_ns"] / 1e9 + e["duration_ms"] / 1e3 \
            <= e["when"] + 1e-3
        top = [s["name"] for s in e["spans"] if s["depth"] == 0]
        assert top[0] == "parse" and top[-2:] == ["batch-fetch", "finish"]
        assert "mesh-execute" in top and "exec-dispatch" in top
        engines = [s for s in e["spans"] if s["name"] == "mesh-execute"]
        # every member is offered to the engine, which declines the third
        assert len(engines) == 1 and engines[0]["tags"]["members"] == 3
        # one device program a signature, each with its phase spans
        fetches = [s for s in e["spans"] if s["name"] == "mesh-fetch"]
        assert len(fetches) == 2
        assert all(s["depth"] == 1 for s in fetches)
        # the per-member stats entries stay as they were
        members = [m for m in entries("query") if m.get("batched")]
        assert len(members) == 3 and all(m["spans"] == [] for m in members)

    def test_members_with_a_result_cache_open_their_own_cache_spans(
            self, store):
        svc = mesh_service(store, result_cache={"extent_steps": 8})
        svc.query_range_many(self.QUERIES[:2])
        (e,) = entries("query-batch")
        top = [s["name"] for s in e["spans"] if s["depth"] == 0]
        assert top.count("cache") == 2

    def test_unsampled_batch_leaves_no_batch_entry(self, store):
        tracing.configure(sample_rate=0.0, slow_query_threshold_ms=1e-9)
        sampled0 = tracing._sampled.value
        mesh_service(store).query_range_many(self.QUERIES)
        assert entries("query-batch") == []
        assert tracing._sampled.value == sampled0
        assert len(entries("query")) == 3  # stats only, as before

    def test_fast_batch_feeds_histograms_but_not_the_recorder(self, store):
        tracing.configure(sample_rate=1.0, slow_query_threshold_ms=3.6e6)
        n0 = tracing._stage_hists["batch-fetch"].count
        mesh_service(store).query_range_many(self.QUERIES)
        assert tracing._stage_hists["batch-fetch"].count == n0 + 1
        assert tracing.slow_queries() == []

    def test_batch_inside_an_active_trace_joins_it(self, store):
        with tracing.start_trace() as outer:
            mesh_service(store).query_range_many(self.QUERIES[:2])
        assert entries("query-batch") == []
        assert outer.find("mesh-execute") and outer.find("batch-fetch")

    def test_single_member_takes_the_traced_query_path(self, store):
        mesh_service(store).query_range_many(self.QUERIES[:1])
        assert entries("query-batch") == []
        (e,) = entries("query")
        assert "mesh-execute" in [s["name"] for s in e["spans"]]


class TestRenderHistogram:
    @pytest.mark.parametrize("front", ["fast", "threaded"])
    def test_both_fronts_observe_render(self, store, front):
        from filodb_tpu.http.server import FiloHttpServer, render_seconds
        if front == "fast":
            from filodb_tpu.http.fastserver import FastHttpServer as Front
        else:
            Front = FiloHttpServer
        srv = Front({"timeseries": mesh_service(store)}, port=0).start()
        n0, sum0 = render_seconds.count, render_seconds.sum
        try:
            url = (f"http://127.0.0.1:{srv.port}/promql/timeseries/api/v1/"
                   f"query_range?query=sum(gauge_metric)"
                   f"&start={START + 600}&end={START + 1200}&step=60")
            with urllib.request.urlopen(url, timeout=60) as r:
                assert r.status == 200
        finally:
            srv.stop()
        assert render_seconds.count == n0 + 1
        assert render_seconds.sum > sum0


class TestNamedScopes:
    """Metadata only: the programs' operations carry stable scope names."""

    @pytest.fixture(scope="class")
    def mesh(self):
        from jax.sharding import Mesh
        return Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                    ("shard", "time"))

    P, S, K = 8, 16, 4

    def lowered(self, fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
        return fn.lower(*args).as_text(debug_info=True)

    @pytest.mark.parametrize("kind,scope", [("counter", "prepare/correct"),
                                            ("prefix", "prepare/prefix")])
    def test_prepare(self, mesh, kind, scope):
        from filodb_tpu.parallel.dist_query import make_mesh_prepare
        text = self.lowered(make_mesh_prepare(mesh, kind),
                            ((self.P, self.S), jnp.float32),
                            ((self.P, self.S), jnp.bool_))
        assert scope in text

    def test_bounds(self, mesh):
        from filodb_tpu.parallel.dist_query import make_mesh_bounds
        text = self.lowered(make_mesh_bounds(mesh),
                            ((self.P, self.S), jnp.int32),
                            ((self.K,), jnp.int32), ((), jnp.int32))
        assert "bounds/search" in text

    def test_eval_simple(self, mesh):
        from filodb_tpu.parallel.dist_query import make_mesh_eval_simple
        ps, pk = (self.P, self.S), (self.P, self.K)
        pre = (self.P, self.S + 1)
        text = self.lowered(
            make_mesh_eval_simple(mesh, "avg_over_time"),
            (ps, jnp.int32), (ps, jnp.float32), (ps, jnp.bool_),
            (pre, jnp.float32), (pre, jnp.float32), (pre, jnp.float32),
            (pk, jnp.int32), (pk, jnp.int32), ((self.K,), jnp.int32),
            ((), jnp.int32))
        assert "eval/avg_over_time" in text

    def test_eval_delta(self, mesh):
        from filodb_tpu.parallel.dist_query import make_mesh_eval_delta
        ps, pk = (self.P, self.S), (self.P, self.K)
        text = self.lowered(
            make_mesh_eval_delta(mesh, "delta"),
            (ps, jnp.int32), (ps, jnp.float32), (ps, jnp.bool_),
            (pk, jnp.int32), (pk, jnp.int32), ((self.K,), jnp.int32),
            ((), jnp.int32))
        assert "eval/delta" in text

    @pytest.mark.parametrize("agg", ["sum", "avg", "max"])
    def test_reduce(self, mesh, agg):
        from filodb_tpu.parallel.dist_query import make_mesh_group_reduce
        text = self.lowered(make_mesh_group_reduce(mesh, 4, agg),
                            ((self.P, self.K), jnp.float32),
                            ((self.P,), jnp.int32))
        assert f"reduce/{agg}" in text

    def test_fused_program_nests_the_names(self, mesh):
        from filodb_tpu.parallel.dist_query import (
            make_distributed_range_agg,
        )
        ps = (self.P, self.S)
        text = self.lowered(
            make_distributed_range_agg(mesh, "max_over_time", 4, "max"),
            (ps, jnp.int32), (ps, jnp.float32), (ps, jnp.bool_),
            ((self.P,), jnp.int32), ((self.K,), jnp.int32),
            ((), jnp.int32))
        assert "eval/max_over_time/bounds/search" in text
        assert "reduce/max" in text
