"""Trace-driven adaptive planner (PR 18): the online cost model behind
every either/or planning decision.

Covers the estimator (EWMA + reservoir, LRU bound), the decide/classify
routing contract, the deferred-settle plumbing, metastore persistence
(restart survival), cold-start static parity (below ``min_samples`` —
and under ``FILODB_ADAPTIVE=0`` — every site reproduces the static
heuristic bit-for-bit), predicted-cost result-cache admission under
byte pressure, the governor's live Retry-After provider, and the
``/api/v1/debug/costmodel`` endpoint on both HTTP fronts.
"""

import json
import urllib.parse
import urllib.request

import numpy as np
import pytest

from filodb_tpu.coordinator import adaptive_planner as ap
from filodb_tpu.coordinator.ingestion import ingest_routed
from filodb_tpu.coordinator.query_service import QueryService
from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.core.store.config import StoreConfig
from filodb_tpu.core.store.localstore import LocalDiskMetaStore
from filodb_tpu.query import cost_model as cm
from filodb_tpu.query.cost_model import CostModel, Decision
from filodb_tpu.query.model import RangeVectorKey, StepMatrix
from filodb_tpu.query.result_cache import ResultCache, ResultCacheConfig
from filodb_tpu.testing.data import gauge_stream, machine_metrics_series
from filodb_tpu.utils import governor as gov

START = 1_600_000_000


# --------------------------------------------------------------------------
# estimator

class TestEstimator:
    def test_ewma_warm_up_then_smooth(self):
        m = CostModel(min_samples=2)
        m.observe("paging", "s", "exact", 1.0)
        assert m.estimate("paging", "s", "exact") is None  # n=1 < 2
        m.observe("paging", "s", "exact", 3.0)
        # first two samples replace (PR 14 _LaneCost semantics)
        assert m.estimate("paging", "s", "exact") == 3.0
        m.observe("paging", "s", "exact", 13.0)
        assert m.estimate("paging", "s", "exact") == pytest.approx(
            3.0 + 0.3 * (13.0 - 3.0))

    def test_percentiles_from_reservoir(self):
        m = CostModel(min_samples=1)
        for v in range(1, 11):
            m.observe("admit", "class:expensive", "wall", float(v))
        assert m.percentile("admit", "class:expensive", "wall", 0.5) \
            == pytest.approx(5.0)
        assert m.percentile("admit", "class:expensive", "wall", 0.9) \
            == pytest.approx(9.0)
        assert m.percentile("admit", "missing", "wall", 0.9) is None

    def test_signature_table_is_lru_bounded(self):
        m = CostModel(min_samples=1)
        m.max_signatures = 4  # constructor clamps to >=16; pin for the test
        for i in range(8):
            m.observe("paging", f"sig{i}", "exact", 0.01)
        assert len(m._stats) == 4
        # newest signatures survive
        assert ("paging", "sig7") in m._stats
        assert ("paging", "sig0") not in m._stats

    def test_signature_key_is_stable_not_hash_randomized(self):
        # persisted signatures must survive interpreter restarts, so
        # non-string signatures hash with blake2b, never Python hash()
        assert cm.signature_key("short:sig") == "short:sig"
        k = cm.signature_key(("a", 17))
        assert k == cm.signature_key(("a", 17))
        assert len(k) == 16


# --------------------------------------------------------------------------
# decide / classify contract

class TestDecide:
    def test_cold_model_returns_static_arm(self):
        m = CostModel()
        for site in cm.SITES:
            d = m.decide(site, "sig", ("a", "b"), static_arm="b")
            assert (d.arm, d.source) == ("b", "static")

    def test_warm_model_routes_to_cheaper_arm(self):
        m = CostModel(min_samples=2)
        for _ in range(3):
            m.observe("sidecar", "s", "sidecar", 0.001)
            m.observe("sidecar", "s", "decode", 0.5)
        d = m.decide("sidecar", "s", ("sidecar", "decode"),
                     static_arm="decode")
        assert (d.arm, d.source) == ("sidecar", "model")
        assert d.predicted == pytest.approx(0.001)

    def test_one_cold_arm_pins_static_when_require_all(self):
        # natural traffic only settles the taken arm; require_all keeps
        # the model from flipping on one-sided evidence
        m = CostModel(min_samples=2)
        for _ in range(5):
            m.observe("sidecar", "s", "decode", 0.5)
        d = m.decide("sidecar", "s", ("sidecar", "decode"),
                     static_arm="decode")
        assert (d.arm, d.source) == ("decode", "static")

    def test_require_all_false_keeps_min_over_known(self):
        # route by whatever is warm: the cheapest of the known arms
        m = CostModel(min_samples=2)
        for _ in range(3):
            m.observe("paging", "b4", "exact", 0.002)
        d = m.decide("paging", "b4", ("exact", "wide"),
                     static_arm="wide", require_all=False)
        assert (d.arm, d.source) == ("exact", "model")

    def test_env_kill_switch_pins_static(self, monkeypatch):
        monkeypatch.setenv("FILODB_ADAPTIVE", "0")
        m = CostModel(min_samples=1)
        m.observe("sidecar", "s", "sidecar", 0.001)
        m.observe("sidecar", "s", "decode", 0.5)
        d = m.decide("sidecar", "s", ("sidecar", "decode"),
                     static_arm="decode")
        assert (d.arm, d.source) == ("decode", "static")

    def test_override_wins_over_warm_model(self):
        m = CostModel(min_samples=1)
        m.observe("sidecar", "s", "sidecar", 9.0)
        m.observe("sidecar", "s", "decode", 0.1)
        d = m.decide("sidecar", "s", ("sidecar", "decode"),
                     static_arm="decode", override="sidecar")
        assert (d.arm, d.source) == ("sidecar", "override")

    def test_classify_threshold_and_wall_settle(self):
        m = CostModel(min_samples=2)
        d = m.classify("admit", "class", 0.05, below_arm="cheap",
                       above_arm="expensive", static_arm="expensive")
        assert (d.arm, d.source) == ("expensive", "static")
        for _ in range(3):
            m.observe("admit", "class", "wall", 0.001)
        d = m.classify("admit", "class", 0.05, below_arm="cheap",
                       above_arm="expensive", static_arm="expensive")
        assert (d.arm, d.source) == ("cheap", "model")
        # settles under the wall arm regardless of the chosen class
        m.record_actual(d, 0.002)
        assert m.samples("admit", "class", "wall") == 4


# --------------------------------------------------------------------------
# deferred settle

class _Carrier:
    pass


class TestDeferredSettle:
    def test_defer_then_settle_feeds_taken_arm(self):
        m = CostModel(min_samples=1)
        carrier = _Carrier()
        d = m.decide("sidecar", "s", ("sidecar", "decode"),
                     static_arm="sidecar")
        m.defer(carrier, d)
        CostModel.settle_deferred(carrier, 0.25)
        assert m.samples("sidecar", "s", "sidecar") == 1
        assert m.estimate("sidecar", "s", "sidecar") == pytest.approx(0.25)
        # list drained: a second settle is a no-op
        CostModel.settle_deferred(carrier, 9.9)
        assert m.samples("sidecar", "s", "sidecar") == 1

    def test_relabel_on_bypass_settles_fallback_arm(self):
        # mid-fold _Bypass: the sidecar arm never ran to completion, so
        # the wall time must land under "decode" with no calibration hit
        m = CostModel(min_samples=1)
        m.observe("sidecar", "s", "sidecar", 0.001)
        m.observe("sidecar", "s", "decode", 0.001)
        carrier = _Carrier()
        d = m.decide("sidecar", "s", ("sidecar", "decode"),
                     static_arm="decode")
        m.defer(carrier, d)
        CostModel.relabel_deferred(carrier, "sidecar", "decode")
        CostModel.settle_deferred(carrier, 0.5)
        assert m.samples("sidecar", "s", "decode") == 2
        assert m.samples("sidecar", "s", "sidecar") == 1

    def test_calibration_error_tracks_prediction_quality(self):
        m = CostModel(min_samples=1)
        for _ in range(3):
            m.observe("paging", "s", "exact", 0.1)
        d = m.decide("paging", "s", ("exact",), static_arm="exact",
                     require_all=False)
        assert d.source == "model"
        m.record_actual(d, 0.1)
        assert m.calibration()["paging"] == pytest.approx(0.0, abs=1e-6)
        ring = m.recent()
        assert ring and ring[-1]["site"] == "paging"


# --------------------------------------------------------------------------
# persistence (satellite 3): restart survival via the metastore

class TestPersistence:
    def _warm(self, m):
        for _ in range(10):
            m.observe("sidecar", "fold:pw1024", "sidecar", 0.002)
            m.observe("sidecar", "fold:pw1024", "decode", 0.4)

    def test_bytes_round_trip_preserves_routing(self):
        m = CostModel(dataset="ds", min_samples=2)
        self._warm(m)
        fresh = CostModel(dataset="ds", min_samples=2)
        assert fresh.from_bytes(m.to_bytes())
        d = fresh.decide("sidecar", "fold:pw1024", ("sidecar", "decode"),
                         static_arm="decode")
        assert (d.arm, d.source) == ("sidecar", "model")
        assert fresh.estimate("sidecar", "fold:pw1024", "decode") \
            == m.estimate("sidecar", "fold:pw1024", "decode")
        assert fresh.percentile("sidecar", "fold:pw1024", "decode", 0.9) \
            == m.percentile("sidecar", "fold:pw1024", "decode", 0.9)

    def test_restart_survival_via_local_meta_store(self, tmp_path):
        meta = LocalDiskMetaStore(str(tmp_path))
        m = CostModel(dataset="timeseries", min_samples=2)
        self._warm(m)
        m.save(meta)
        # "restart": a brand-new process-level model for the dataset
        reborn = CostModel(dataset="timeseries", min_samples=2)
        assert reborn.load(meta)
        d = reborn.decide("sidecar", "fold:pw1024", ("sidecar", "decode"),
                          static_arm="decode")
        assert (d.arm, d.source) == ("sidecar", "model")

    def test_load_missing_blob_is_clean_cold_start(self, tmp_path):
        meta = LocalDiskMetaStore(str(tmp_path))
        m = CostModel(dataset="never-saved")
        assert not m.load(meta)
        d = m.decide("sidecar", "s", ("a", "b"), static_arm="b")
        assert (d.arm, d.source) == ("b", "static")

    def test_corrupt_blob_is_clean_cold_start(self):
        m = CostModel(dataset="ds")
        assert not m.from_bytes(b"not json at all")
        assert len(m._stats) == 0

    def test_install_and_persist_lifecycle(self, tmp_path):
        meta = LocalDiskMetaStore(str(tmp_path))
        m = ap.install("timeseries", meta, {"min_samples": 2})
        self._warm(m)
        ap.persist("timeseries", meta)
        cm.reset_models()
        m2 = ap.install("timeseries", meta, {"min_samples": 2})
        d = m2.decide("sidecar", "fold:pw1024", ("sidecar", "decode"),
                      static_arm="decode")
        assert (d.arm, d.source) == ("sidecar", "model")


# --------------------------------------------------------------------------
# cold-start static parity (satellite 3): below min_samples and with the
# kill switch, the adaptive path reproduces the static plan bit-for-bit

NUM_SHARDS = 2


@pytest.fixture(scope="module")
def store():
    ms = TimeSeriesMemStore()
    for s in range(NUM_SHARDS):
        ms.setup("timeseries", s, StoreConfig(max_chunk_size=64))
    keys = machine_metrics_series(6)
    ingest_routed(ms, "timeseries",
                  gauge_stream(keys, 600, start_ms=START * 1000,
                               interval_ms=10_000, seed=3),
                  NUM_SHARDS, spread=1)
    return ms


class TestColdStartParity:
    QUERIES = [
        "avg_over_time(heap_usage[3m])",
        "sum(avg_over_time(heap_usage[5m]))",
        "quantile_over_time(0.9, heap_usage[5m])",
    ]

    def _run_all(self, store):
        svc = QueryService(store, "timeseries", NUM_SHARDS, spread=1)
        out = []
        for q in self.QUERIES:
            r = svc.query_range(q, START + 600, 60, START + 4000)
            out.append((r.result.num_series,
                        np.asarray(r.result.values).tobytes()))
        return out

    def test_cold_adaptive_matches_disabled_bit_for_bit(
            self, store, monkeypatch):
        monkeypatch.setenv("FILODB_ADAPTIVE", "0")
        static = self._run_all(store)
        cm.reset_models()
        monkeypatch.setenv("FILODB_ADAPTIVE", "1")
        adaptive = self._run_all(store)
        for (ns, sb), (na, ab) in zip(static, adaptive):
            assert ns == na
            assert sb == ab

    def test_cold_queries_never_depart_from_static(self, store):
        # every decision the cold run made must carry source="static"
        # (or "override"); nothing routes by model before warm-up
        self._run_all(store)
        for model in cm.models().values():
            for row in model.recent():
                assert row.get("source", "static") != "model"


# --------------------------------------------------------------------------
# result-cache admission under byte pressure (satellite 1)

def _matrix(steps=64, series=2, seed=0):
    rng = np.random.default_rng(seed)
    keys = [RangeVectorKey.of({"k": f"s{seed}-{i}"}) for i in range(series)]
    return StepMatrix(keys, rng.random((series, steps)),
                      np.arange(steps, dtype=np.int64) * 60_000)


class TestCacheByteArbitration:
    def test_decode_extent_outlives_pyramid_served_extent(self):
        one = _matrix(seed=1)
        nbytes = int(one.values.nbytes) + int(one.steps_ms.nbytes)
        c = ResultCache(ResultCacheConfig(max_bytes=int(nbytes * 3.5)))
        c._put(("cheap-old",), None, _matrix(seed=1), cheap=True)
        c._put(("costly-old",), None, _matrix(seed=2), cheap=False)
        c._put(("cheap-new",), None, _matrix(seed=3), cheap=True)
        # budget forces one eviction: strict LRU would evict costly-old
        # (oldest is cheap-old... ) — cheap entries must go first
        c._put(("costly-new",), None, _matrix(seed=4), cheap=False)
        with c._lock:
            keys = set(c._lru)
        assert ("costly-old",) in keys, \
            "expensive-to-recompute extent was evicted before cheap ones"
        assert ("cheap-old",) not in keys
        assert c.nbytes <= c.config.max_bytes

    def test_cheap_exhausted_falls_back_to_lru(self):
        one = _matrix(seed=1)
        nbytes = int(one.values.nbytes) + int(one.steps_ms.nbytes)
        c = ResultCache(ResultCacheConfig(max_bytes=int(nbytes * 2.5)))
        c._put(("a",), None, _matrix(seed=1), cheap=False)
        c._put(("b",), None, _matrix(seed=2), cheap=False)
        c._put(("c",), None, _matrix(seed=3), cheap=False)
        with c._lock:
            keys = list(c._lru)
        assert ("a",) not in keys  # plain LRU once no cheap entry exists

    def test_reinsert_clears_cheap_bit(self):
        c = ResultCache(ResultCacheConfig(max_bytes=1 << 20))
        c._put(("k",), None, _matrix(seed=1), cheap=True)
        assert ("k",) in c._cheap
        c._put(("k",), None, _matrix(seed=1), cheap=False)
        assert ("k",) not in c._cheap


# --------------------------------------------------------------------------
# governor Retry-After from live percentiles

class TestRetryAfter:
    def teardown_method(self):
        gov.reset()

    def test_provider_none_falls_back_to_static(self):
        assert gov._advised_retry_after("capacity", 1.0) == 1.0
        gov.set_retry_after_provider(lambda reason: None)
        assert gov._advised_retry_after("capacity", 1.0) == 1.0

    def test_provider_exception_falls_back(self):
        def boom(reason):
            raise RuntimeError("no")
        gov.set_retry_after_provider(boom)
        assert gov._advised_retry_after("capacity", 1.0) == 1.0

    def test_provider_value_clamped(self):
        gov.set_retry_after_provider(lambda reason: 500.0)
        assert gov._advised_retry_after("capacity", 1.0) == 60.0
        gov.set_retry_after_provider(lambda reason: 0.0001)
        assert gov._advised_retry_after("capacity", 1.0) == 0.05

    def test_live_percentile_flows_from_settled_queries(self):
        m = cm.model_for("timeseries")
        m.configure(min_samples=1)
        for v in (0.2, 0.4, 0.6, 0.8, 1.0):
            m.observe("admit", f"class:{gov.EXPENSIVE}", "wall", v)
        advised = ap.retry_after_provider("capacity")
        assert advised == pytest.approx(1.0)  # p90 of the reservoir
        assert ap.retry_after_provider("rules") is None  # cold class

    def test_reset_clears_provider(self):
        gov.set_retry_after_provider(lambda reason: 2.0)
        gov.reset()
        assert gov._advised_retry_after("capacity", 1.0) == 1.0


# --------------------------------------------------------------------------
# /api/v1/debug/costmodel on both HTTP fronts (satellite 2)

@pytest.fixture(params=["threaded", "fast"])
def server(request, store):
    svc = QueryService(store, "timeseries", NUM_SHARDS, spread=1)
    if request.param == "fast":
        from filodb_tpu.http.fastserver import FastHttpServer
        srv = FastHttpServer({"timeseries": svc}, port=0).start()
    else:
        from filodb_tpu.http.server import FiloHttpServer
        srv = FiloHttpServer({"timeseries": svc}, port=0).start()
    yield srv
    srv.stop()


def _get(server, path, **params):
    qs = urllib.parse.urlencode(params, doseq=True)
    url = f"http://127.0.0.1:{server.port}{path}" + (f"?{qs}" if qs else "")
    with urllib.request.urlopen(url) as r:
        return r.status, json.loads(r.read())


class TestCostModelEndpoint:
    def test_debug_costmodel_snapshot(self, server):
        m = cm.model_for("timeseries")
        for _ in range(3):
            m.observe("sidecar", "fold:pw512", "sidecar", 0.002)
        code, body = _get(server,
                          "/promql/timeseries/api/v1/debug/costmodel")
        assert code == 200 and body["status"] == "success"
        snap = body["data"]
        assert snap["dataset"] == "timeseries"
        assert snap["signatures"] >= 1
        rows = snap["estimates"]
        assert any(r["site"] == "sidecar" and r["arm"] == "sidecar"
                   and r["n"] == 3 for r in rows)
        assert {"p50_s", "p90_s", "warm", "estimate_s"} <= set(rows[0])

    def test_debug_costmodel_limit(self, server):
        m = cm.model_for("timeseries")
        for i in range(5):
            m.observe("paging", f"page:span{i}", "exact", 0.01)
        code, body = _get(server,
                          "/promql/timeseries/api/v1/debug/costmodel",
                          limit=2)
        assert code == 200
        assert len(body["data"]["estimates"]) == 2
