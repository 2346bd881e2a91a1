"""Durability & recovery end-to-end tests.

Mirrors the reference's full recovery story
(``standalone/src/multi-jvm/scala/filodb/standalone/
IngestionAndRecoverySpec.scala``): ingest through a replayable log with
flush/checkpoint, "crash" (new process state), recover index from the column
store, replay the log from min(checkpoint) honoring group watermarks, and
verify query correctness — plus on-demand paging of evicted chunks.
"""

import numpy as np
import pytest

from filodb_tpu.coordinator.query_service import QueryService
from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.core.record import RecordContainer
from filodb_tpu.core.store.config import StoreConfig
from filodb_tpu.core.store.localstore import (
    LocalDiskColumnStore,
    LocalDiskMetaStore,
)
from filodb_tpu.kafka.log import FileLog, InMemoryLog
from filodb_tpu.testing.data import gauge_stream, machine_metrics_series

START = 1_600_000_000


class TestFileLog:
    def test_append_read(self, tmp_path):
        log = FileLog(str(tmp_path / "shard0.log"))
        keys = machine_metrics_series(3)
        offs = []
        for sd in gauge_stream(keys, 50, start_ms=START * 1000):
            offs.append(log.append(sd.container))
        assert offs == list(range(len(offs)))
        entries = list(log.read_from(0))
        assert len(entries) == len(offs)
        assert entries[0].offset == 0
        total = sum(len(e.container) for e in entries)
        assert total == 3 * 50

    def test_read_from_middle(self, tmp_path):
        log = FileLog(str(tmp_path / "s.log"), index_every=4)
        keys = machine_metrics_series(1)
        for sd in gauge_stream(keys, 100, batch=10, start_ms=START * 1000):
            log.append(sd.container)
        entries = list(log.read_from(7))
        assert entries[0].offset == 7

    def test_reopen_persists(self, tmp_path):
        p = str(tmp_path / "s.log")
        log = FileLog(p)
        keys = machine_metrics_series(1)
        for sd in gauge_stream(keys, 30, start_ms=START * 1000):
            log.append(sd.container)
        n = log.latest_offset
        log.close()
        log2 = FileLog(p)
        assert log2.latest_offset == n
        assert len(list(log2.read_from(0))) == n + 1

    def test_serialization_round_trip(self):
        keys = machine_metrics_series(2)
        sd = next(gauge_stream(keys, 2, start_ms=0))
        data = sd.container.serialize()
        out = RecordContainer.deserialize(data)
        assert len(out) == len(sd.container)
        r0, r1 = out.records[0], sd.container.records[0]
        assert r0.part_key == r1.part_key
        assert r0.timestamp == r1.timestamp
        assert r0.values == r1.values


class TestLocalDiskStore:
    def test_chunks_round_trip(self, tmp_path):
        from filodb_tpu.core.memstore.partition import TimeSeriesPartition
        from filodb_tpu.core.schemas import DEFAULT_SCHEMAS
        cs = LocalDiskColumnStore(str(tmp_path))
        key = machine_metrics_series(1)[0]
        part = TimeSeriesPartition(0, key, DEFAULT_SCHEMAS["gauge"],
                                   max_chunk_size=50)
        for i in range(100):
            part.ingest(i * 1000, (float(i),))
        chunks = part.make_flush_chunks()
        cs.write_chunks("ds", 0, key, chunks, ingestion_time=999)
        back = cs.read_chunks("ds", 0, key, 0, 10**15)
        assert len(back) == len(chunks)
        ts = np.concatenate([c.decode_column(0) for c in back])
        assert len(ts) == 100
        # idempotent rewrite (recovery re-flush)
        cs.write_chunks("ds", 0, key, chunks, ingestion_time=999)
        assert len(cs.read_chunks("ds", 0, key, 0, 10**15)) == len(chunks)
        # ingestion-time scan (downsampler path)
        scanned = list(cs.scan_chunks_by_ingestion_time("ds", 0, 0, 10**12))
        assert len(scanned) == 1 and scanned[0][0] == key
        cs.close()

    def test_partkeys_upsert(self, tmp_path):
        from filodb_tpu.core.store.api import PartKeyRecord
        cs = LocalDiskColumnStore(str(tmp_path))
        key = machine_metrics_series(1)[0]
        cs.write_part_keys("ds", 0, [PartKeyRecord(key, 100, 200)])
        cs.write_part_keys("ds", 0, [PartKeyRecord(key, 150, 500)])
        recs = cs.scan_part_keys("ds", 0)
        assert len(recs) == 1
        assert recs[0].start_time == 100 and recs[0].end_time == 500
        cs.close()

    def test_concurrent_scans_each_read_every_row(self, tmp_path):
        """Threads that run the same SELECT on the store's one connection
        (the chunk server's handler threads do) each get their own rows:
        the statement sqlite3 caches for that SQL text is never stepped by
        two of them at once."""
        import sys
        import time
        from concurrent.futures import ThreadPoolExecutor

        from filodb_tpu.core.store.api import PartKeyRecord
        cs = LocalDiskColumnStore(str(tmp_path))
        keys = machine_metrics_series(48)
        cs.write_part_keys("ds", 0, [PartKeyRecord(k, 1, 2) for k in keys])
        want = sorted(str(k) for k in keys)

        def scan(_):
            return sorted(str(r.part_key)
                          for r in cs.scan_part_keys("ds", 0))

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            deadline = time.monotonic() + 3.0
            with ThreadPoolExecutor(max_workers=16) as ex:
                rounds = 0
                while time.monotonic() < deadline and rounds < 400:
                    for got in ex.map(scan, range(16)):
                        assert got == want
                    rounds += 1
        finally:
            sys.setswitchinterval(interval)
            cs.close()
        assert rounds > 0


_SERVERS: dict = {}


def _mk_store(tmp_path, kind="local"):
    """Build a memstore on a local-disk column store, or on a REMOTE
    chunk-server fronting the same disk layout (both impls must pass every
    durability scenario — proving the store API abstracts)."""
    if kind == "remote":
        from filodb_tpu.core.store.remotestore import (
            ChunkStoreServer, RemoteColumnStore, RemoteMetaStore)
        srv = _SERVERS.get(str(tmp_path))
        if srv is None:
            srv = _SERVERS[str(tmp_path)] = ChunkStoreServer(
                root=str(tmp_path / "data")).start()
        cs = RemoteColumnStore("127.0.0.1", srv.port)
        meta = RemoteMetaStore("127.0.0.1", srv.port)
    elif kind == "object":
        from filodb_tpu.core.store.objectstore import (
            ObjectStoreColumnStore, ObjectStoreMetaStore)
        from filodb_tpu.testing.fake_s3 import FakeS3
        # dir-backed fake: a new store instance over the same root models a
        # process restart reading back from the object service
        cs = ObjectStoreColumnStore(FakeS3(root=str(tmp_path / "s3")),
                                    segment_target_bytes=64 * 1024)
        meta = ObjectStoreMetaStore(cs)
    else:
        cs = LocalDiskColumnStore(str(tmp_path / "data"))
        meta = LocalDiskMetaStore(str(tmp_path / "data"))
    ms = TimeSeriesMemStore(cs, meta)
    ms.setup("timeseries", 0, StoreConfig(max_chunk_size=50,
                                          groups_per_shard=4))
    return ms


@pytest.fixture(params=["local", "remote", "object"])
def store_kind(request):
    return request.param


class TestCrashRecovery:
    def test_full_recovery_cycle(self, tmp_path, store_kind):
        keys = machine_metrics_series(8)
        log = FileLog(str(tmp_path / "log" / "shard0.log"))
        stream = list(gauge_stream(keys, 200, start_ms=START * 1000,
                                   batch=50))
        for sd in stream:
            log.append(sd.container)

        # phase 1: ingest 60%, flush, ingest 20% more unflushed, "crash"
        ms1 = _mk_store(tmp_path, store_kind)
        shard1 = ms1.get_shard("timeseries", 0)
        n60 = int(len(stream) * 0.6)
        n80 = int(len(stream) * 0.8)
        for sd in log.read_from(0):
            if sd.offset >= n60:
                break
            shard1.ingest(sd)
        shard1.flush_all(ingestion_time=1)
        for sd in log.read_from(n60):
            if sd.offset >= n80:
                break
            shard1.ingest(sd)
        # crash: no flush of the last 20%; drop everything in-memory
        ms1.column_store.close()
        ms1.meta_store.close()

        # phase 2: restart, recover, replay
        ms2 = _mk_store(tmp_path, store_kind)
        shard2 = ms2.get_shard("timeseries", 0)
        restored = shard2.recover_index()
        assert restored == 8
        start_offset = shard2.setup_watermarks_for_recovery()
        assert start_offset == n60 - 1
        for sd in log.read_from(start_offset):
            shard2.ingest(sd)

        # phase 3: verify no data loss and no duplication via a full query
        svc = QueryService(ms2, "timeseries", 1, spread=0)
        r = svc.query_range(
            'count_over_time(heap_usage[45m])',
            START + 2400, 60, START + 2400).result
        # 200 samples @10s per series; 45m window at +2400s covers them all
        # (windows are left-exclusive (t-w, t], so 40m would miss t=START)
        assert r.num_series == 8
        np.testing.assert_array_equal(r.values[:, 0], 200.0)

    def test_odp_after_eviction(self, tmp_path):
        keys = machine_metrics_series(4)
        ms = _mk_store(tmp_path)
        shard = ms.get_shard("timeseries", 0)
        for sd in gauge_stream(keys, 300, start_ms=START * 1000):
            shard.ingest(sd)
        shard.flush_all(ingestion_time=1)
        # evict persisted chunks from memory
        evicted = sum(shard.evict_partition_chunks(p.part_id)
                      for p in shard.partitions if p)
        assert evicted > 0
        svc = QueryService(ms, "timeseries", 1, spread=0)
        r = svc.query_range('count_over_time(heap_usage[55m])',
                            START + 3000, 60, START + 3000).result
        assert r.num_series == 4
        np.testing.assert_array_equal(r.values[:, 0], 300.0)
        from filodb_tpu.core.memstore.odp import odp_chunks_paged
        assert odp_chunks_paged.value > 0

    def test_odp_cache_hit_second_query(self, tmp_path):
        keys = machine_metrics_series(2)
        ms = _mk_store(tmp_path)
        shard = ms.get_shard("timeseries", 0)
        for sd in gauge_stream(keys, 100, start_ms=START * 1000):
            shard.ingest(sd)
        shard.flush_all(ingestion_time=1)
        for p in shard.partitions:
            if p:
                shard.evict_partition_chunks(p.part_id)
        svc = QueryService(ms, "timeseries", 1, spread=0)
        q = lambda: svc.query_range(  # noqa: E731
            'sum_over_time(heap_usage[10m])', START + 900, 60,
            START + 900).result
        r1, r2 = q(), q()
        np.testing.assert_array_equal(r1.values, r2.values)


class TestSegmentedLog:
    def _fill(self, log, n, keys=None):
        keys = keys or machine_metrics_series(1)
        offs = []
        for sd in gauge_stream(keys, n, batch=1, start_ms=START * 1000):
            offs.append(log.append(sd.container))
        return offs

    def test_rolls_segments(self, tmp_path):
        from filodb_tpu.kafka.log import SegmentedFileLog
        log = SegmentedFileLog(str(tmp_path / "wal"), segment_entries=10)
        offs = self._fill(log, 35)
        assert offs == list(range(35))
        import os
        segs = [f for f in os.listdir(tmp_path / "wal")
                if f.startswith("seg-")]
        assert len(segs) == 4
        assert [sd.offset for sd in log.read_from(0)] == list(range(35))
        assert [sd.offset for sd in log.read_from(17)] == list(range(17, 35))

    def test_truncate_before(self, tmp_path):
        from filodb_tpu.kafka.log import SegmentedFileLog
        log = SegmentedFileLog(str(tmp_path / "wal"), segment_entries=10)
        self._fill(log, 35)
        removed = log.truncate_before(25)
        assert removed == 2  # segments [0..9], [10..19] gone; [20..29] kept
        assert log.earliest_offset == 20
        assert [sd.offset for sd in log.read_from(0)][0] == 20
        assert [sd.offset for sd in log.read_from(28)] == list(range(28, 35))

    def test_reopen_preserves_offsets(self, tmp_path):
        from filodb_tpu.kafka.log import SegmentedFileLog
        p = str(tmp_path / "wal")
        log = SegmentedFileLog(p, segment_entries=10)
        self._fill(log, 25)
        log.truncate_before(15)
        log.close()
        log2 = SegmentedFileLog(p, segment_entries=10)
        assert log2.latest_offset == 24
        assert log2.earliest_offset == 10
        offs = [sd.offset for sd in log2.read_from(0)]
        assert offs == list(range(10, 25))
        # appends continue from the global offset
        more = self._fill(log2, 1)
        assert more == [25]
