"""Mesh engine under shard imbalance and ingest churn.

The round-3 dryrun only exercised 30 balanced, static series; these tests
stress the two production realities it skipped:
- skewed shard→series distributions (shard-key hashing is never uniform),
- concurrent ingest ticking ``data_version`` so the device-resident batch
  cache must invalidate, rebuild and re-upload without serving stale data.
"""

import numpy as np
import pytest

from filodb_tpu.coordinator.query_service import QueryService
from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.core.partkey import PartKey
from filodb_tpu.core.record import IngestRecord, RecordContainer, SomeData
from filodb_tpu.core.store.config import StoreConfig

START = 1_600_000_000
NUM_SHARDS = 4


def skewed_store(per_shard=(50, 5, 5, 5), n_samples=120):
    """Shard 0 carries 10x the series of the others (10:1 imbalance)."""
    ms = TimeSeriesMemStore()
    for s in range(NUM_SHARDS):
        ms.setup("timeseries", s, StoreConfig(max_chunk_size=100,
                                              groups_per_shard=4))
    rng = np.random.default_rng(5)
    for shard_num, count in enumerate(per_shard):
        shard = ms.get_shard("timeseries", shard_num)
        keys = [PartKey.create("prom-counter", {
            "_metric_": "skew_total", "_ws_": "demo", "_ns_": "App-0",
            "shardtag": f"s{shard_num}", "instance": f"i{shard_num}-{j}"})
            for j in range(count)]
        vals = np.cumsum(rng.integers(1, 10, size=(count, n_samples)),
                         axis=1)
        for t in range(n_samples):
            c = RecordContainer()
            for k, key in enumerate(keys):
                c.add(IngestRecord(key, (START + t * 10) * 1000,
                                   (float(vals[k, t]),)))
            shard.ingest(SomeData(c, t))
    return ms


def services(ms):
    exec_svc = QueryService(ms, "timeseries", NUM_SHARDS, spread=1)
    mesh_svc = QueryService(ms, "timeseries", NUM_SHARDS, spread=1,
                            engine="mesh")
    return exec_svc, mesh_svc


def assert_same(r_exec, r_mesh):
    e, m = r_exec.result, r_mesh.result
    assert sorted(map(str, e.keys)) == sorted(map(str, m.keys))
    order_e = np.argsort([str(k) for k in e.keys])
    order_m = np.argsort([str(k) for k in m.keys])
    np.testing.assert_allclose(e.values[order_e], m.values[order_m],
                               rtol=1e-6, atol=1e-9, equal_nan=True)


class TestSkewedShards:
    @pytest.fixture(scope="class")
    def store(self):
        return skewed_store()

    def q(self, svc, query):
        return svc.query_range(query, START + 300, 60, START + 1100)

    def test_sum_rate_parity_under_skew(self, store):
        e, m = services(store)
        for query in ('sum(rate(skew_total[5m]))',
                      'sum(rate(skew_total[5m])) by (shardtag)',
                      'rate(skew_total[5m])'):
            assert_same(self.q(e, query), self.q(m, query))

    def test_all_shards_contribute(self, store):
        _, m = services(store)
        r = self.q(m, 'sum(rate(skew_total[5m])) by (shardtag)').result
        tags = {k.label_map.get("shardtag") for k in r.keys}
        assert tags == {"s0", "s1", "s2", "s3"}

    def test_extreme_skew_single_hot_shard(self):
        ms = skewed_store(per_shard=(64, 1, 1, 1))
        e, m = services(ms)
        q = 'sum(rate(skew_total[5m])) by (shardtag)'
        assert_same(self.q(e, q), self.q(m, q))


class TestIngestChurn:
    def _tick(self, ms, keys_by_shard, t, value):
        for shard_num, keys in keys_by_shard.items():
            shard = ms.get_shard("timeseries", shard_num)
            c = RecordContainer()
            for key in keys:
                c.add(IngestRecord(key, (START + t * 10) * 1000, (value,)))
            shard.ingest(SomeData(c, 100_000 + t))

    def test_churn_invalidates_batch_cache(self):
        """Every ingest tick bumps data_version; queries must never serve
        stale cached batches, and the cache must recover (hit again) once
        data stops changing."""
        ms = skewed_store(per_shard=(20, 2, 2, 2), n_samples=60)
        _, m = services(ms)
        eng = m.mesh_engine
        keys_by_shard = {
            s: [PartKey.create("prom-counter", {
                "_metric_": "skew_total", "_ws_": "demo", "_ns_": "App-0",
                "shardtag": f"s{s}", "instance": f"i{s}-0"})]
            for s in range(NUM_SHARDS)}
        query = 'sum(increase(skew_total[10m]))'

        def total(res):
            v = res.result.values
            return float(np.nansum(v))

        # churn phase: interleave ingest ticks and queries; the counter
        # keeps increasing, so increase() must reflect every tick
        last = None
        for t in range(60, 72):
            self._tick(ms, keys_by_shard, t, 10_000.0 + t * 50)
            r = m.query_range(query, START + t * 10, 10, START + t * 10)
            cur = total(r)
            if last is not None:
                assert cur >= last - 1e-6, "stale batch served under churn"
            last = cur
        # quiescent phase: identical repeated queries reuse the cached
        # device-resident batch (no rebuilds)
        args = (START + 700, 10, START + 710)
        m.query_range(query, *args)
        cache = eng._batch_cache
        entries_before = {k: id(v) for k, v in cache.items()}
        for _ in range(3):
            m.query_range(query, *args)
        entries_after = {k: id(v) for k, v in cache.items()}
        assert entries_before == entries_after, \
            "cache rebuilt without data changes"

    def test_churn_with_new_series_appearing(self):
        """New series mid-stream change the batch SHAPE (row count), not
        just versions — results must include them immediately."""
        ms = skewed_store(per_shard=(10, 1, 1, 1), n_samples=60)
        _, m = services(ms)
        q = 'sum(rate(skew_total[5m])) by (shardtag)'
        r1 = m.query_range(q, START + 590, 10, START + 590).result
        rows1 = len(r1.keys)
        # a brand-new series on the hot shard
        shard = ms.get_shard("timeseries", 0)
        c = RecordContainer()
        newkey = PartKey.create("prom-counter", {
            "_metric_": "skew_total", "_ws_": "demo", "_ns_": "App-0",
            "shardtag": "s-new", "instance": "fresh"})
        for t in range(55, 60):
            c.add(IngestRecord(newkey, (START + t * 10) * 1000,
                               (float(t * 7),)))
        shard.ingest(SomeData(c, 999_999))
        r2 = m.query_range(q, START + 590, 10, START + 590).result
        tags = {k.label_map.get("shardtag") for k in r2.keys}
        assert "s-new" in tags
        assert len(r2.keys) == rows1 + 1

    def test_mesh_hit_rate_accounting(self):
        ms = skewed_store(per_shard=(10, 1, 1, 1), n_samples=30)
        _, m = services(ms)
        eng = m.mesh_engine
        for _ in range(5):
            m.query_range('sum(rate(skew_total[5m]))',
                          START + 250, 10, START + 280)
        assert eng.hits >= 5
        assert eng.hit_rate > 0.9
