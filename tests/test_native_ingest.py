"""Native ingest lane: C++ shard core parity with the host path.

Reference boundary replaced: the per-shard ingest hot loop
(``core/src/main/scala/filodb.core/memstore/TimeSeriesShard.scala:570``,
``TimeSeriesPartition.scala:137``). The binary-container lane must produce
identical query results, flush artifacts, and recovery behavior as the
Python record loop.
"""

import numpy as np
import pytest

from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.core.memstore.native_shard import native_available
from filodb_tpu.core.record import BytesContainer, SomeData
from filodb_tpu.core.store.api import InMemoryColumnStore, InMemoryMetaStore
from filodb_tpu.core.store.config import StoreConfig
from filodb_tpu.testing.data import (
    counter_stream,
    gauge_stream,
    histogram_stream,
    histogram_series,
    machine_metrics_series,
)

pytestmark = pytest.mark.skipif(not native_available(),
                                reason="native library unavailable")


def to_bytes_stream(stream):
    for sd in stream:
        yield SomeData(BytesContainer(sd.container.serialize()), sd.offset)


def build(native: bool, stream):
    ms = TimeSeriesMemStore(InMemoryColumnStore(), InMemoryMetaStore())
    shard = ms.setup("ds", 0, StoreConfig(max_chunk_size=50,
                                          groups_per_shard=4,
                                          native_ingest=native))
    for sd in stream:
        shard.ingest(sd)
    return ms, shard


class TestNativeParity:
    def test_lane_engages(self):
        keys = machine_metrics_series(3)
        stream = list(to_bytes_stream(gauge_stream(keys, 10, batch=1)))
        _, shard = build(True, stream)
        assert shard._native_core is not None
        assert shard._native_core.stat(0) > 0  # rows went through C++
        assert type(shard.partitions[0]).__name__ == "NativeBackedPartition"

    def test_query_results_match_python_path(self):
        keys = machine_metrics_series(6)
        base = list(gauge_stream(keys, 300, batch=20, seed=11))
        stream_b = list(to_bytes_stream(base))
        _, nat = build(True, stream_b)
        _, py = build(False, base)
        assert nat._native_core is not None and py._native_core is None
        for pid in range(len(keys)):
            t1, v1 = nat.partitions[pid].read_samples(0, 10**15)
            t2, v2 = py.partitions[pid].read_samples(0, 10**15)
            np.testing.assert_array_equal(t1, t2)
            np.testing.assert_array_equal(v1, v2)
            # chunk artifacts byte-identical (same codecs, same boundaries)
            c1 = nat.partitions[pid].chunks
            c2 = py.partitions[pid].chunks
            assert [c.id for c in c1] == [c.id for c in c2]
            assert [c.vectors for c in c1] == [c.vectors for c in c2]

    def test_flush_and_recovery_parity(self):
        keys = machine_metrics_series(4)
        base = list(gauge_stream(keys, 120, batch=1, seed=2))
        cs, meta = InMemoryColumnStore(), InMemoryMetaStore()
        ms = TimeSeriesMemStore(cs, meta)
        shard = ms.setup("ds", 0, StoreConfig(max_chunk_size=50,
                                              groups_per_shard=2))
        half = len(base) // 2
        for sd in to_bytes_stream(base[:half]):
            shard.ingest(sd)
        shard.flush_all()
        # restart: recover index + watermarks, replay everything
        ms2 = TimeSeriesMemStore(cs, meta)
        shard2 = ms2.setup("ds", 0, StoreConfig(max_chunk_size=50,
                                                groups_per_shard=2))
        assert shard2.recover_index() == 4
        shard2.setup_watermarks_for_recovery()
        for sd in to_bytes_stream(base):
            shard2.ingest(sd)
        assert shard2.stats.rows_skipped.value > 0  # below-watermark skip
        shard2.flush_all()
        for key in keys:
            chunks = cs.read_chunks("ds", 0, key, 0, 10**15)
            all_ts = [t for c in chunks for t in c.decode_column(0)]
            assert len(all_ts) == len(set(all_ts))
            assert len(set(all_ts)) == 120

    def test_histogram_containers_ingest_natively(self):
        hkeys = histogram_series(2)
        stream = list(to_bytes_stream(histogram_stream(hkeys, 30, batch=1)))
        _, shard = build(True, stream)
        # hist containers take the native lane: partitions
        # are native-backed and read back full histogram columns
        assert shard.stats.rows_ingested.value == 60
        assert type(shard.partitions[0]).__name__ == "NativeBackedPartition"
        t, v = shard.partitions[0].read_samples(0, 10**15)
        assert len(t) == 30
        from filodb_tpu.memory.codecs import HistogramColumn
        assert isinstance(v, HistogramColumn)
        assert v.rows.shape[0] == 30 and v.rows.shape[1] == len(v.les)
        # cumulative bucket counts are monotone non-decreasing per row
        assert (np.diff(v.rows, axis=1) >= 0).all()
        # sum/count scalar columns ride the same native records
        t1, sums = shard.partitions[0].read_samples(0, 10**15, col=1)
        assert len(t1) == 30 and np.isfinite(sums).all()

    def test_mixed_scalar_and_hist_pid_alignment(self):
        gkeys = machine_metrics_series(2)
        hkeys = histogram_series(1)
        g1 = list(to_bytes_stream(gauge_stream(gkeys, 5, batch=1)))
        h1 = [SomeData(sd.container, sd.offset + 100) for sd in
              to_bytes_stream(histogram_stream(hkeys, 5, batch=1))]
        g2 = [SomeData(BytesContainer(sd.container.serialize()),
                       sd.offset + 200)
              for sd in gauge_stream(gkeys, 5, batch=1, start_ms=10**9)]
        ms, shard = build(True, g1 + h1 + g2)
        assert shard.num_partitions == 3
        for pid, part in enumerate(shard.partitions):
            assert part.part_id == pid
        # native pids stay aligned after the python-backed hist partition
        total = sum(p.num_samples for p in shard.partitions)
        assert total == 2 * 10 + 5

    def test_concurrent_reads_during_ingest(self):
        # readers copy native buffers while the ingest thread appends and
        # seals; without the core lock this is a use-after-free on vector
        # realloc (the C++ analog of the reference's ChunkMap latch)
        import threading
        keys = machine_metrics_series(8)
        ms = TimeSeriesMemStore(InMemoryColumnStore(), InMemoryMetaStore())
        shard = ms.setup("ds", 0, StoreConfig(max_chunk_size=64,
                                              groups_per_shard=2))
        stream = [SomeData(BytesContainer(sd.container.serialize()),
                           sd.offset)
                  for sd in gauge_stream(keys, 2000, batch=64)]
        errors = []
        stop = threading.Event()

        def reader():
            try:
                while not stop.is_set():
                    for p in list(shard.partitions):
                        if p is None:
                            continue
                        t, v = p.read_samples(0, 10**15)
                        assert len(t) == len(v)
                        if len(t) > 1:
                            assert (np.diff(t) > 0).all()
                        _ = p.chunks
            except Exception as e:  # pragma: no cover - the regression
                errors.append(e)

        threads = [threading.Thread(target=reader) for _ in range(3)]
        for t in threads:
            t.start()
        for sd in stream:
            shard.ingest(sd)
        stop.set()
        for t in threads:
            t.join()
        assert not errors, errors
        total = sum(p.num_samples for p in shard.partitions if p)
        assert total == 8 * 2000

    def test_purge_frees_slot_for_python_backed_partition(self):
        # a histogram (python-backed) partition still owns a native slot;
        # purge must free it or re-creating the series breaks pid alignment
        hkeys = histogram_series(1)
        ms = TimeSeriesMemStore(InMemoryColumnStore(), InMemoryMetaStore())
        shard = ms.setup("ds", 0, StoreConfig(max_chunk_size=10,
                                              groups_per_shard=1,
                                              retention_ms=1_000_000))
        for sd in to_bytes_stream(histogram_stream(hkeys, 3, batch=1)):
            shard.ingest(sd)
        assert shard._native_core is not None
        assert shard.purge_expired(now_ms=10_000_000) == 1
        # same series comes back: must create cleanly at the NEW pid
        fresh = [SomeData(sd.container, sd.offset + 100) for sd in
                 to_bytes_stream(histogram_stream(hkeys, 3, batch=1,
                                                  start_ms=20_000_000))]
        for sd in fresh:
            shard.ingest(sd)
        assert shard.num_partitions == 1
        assert shard.partitions[1] is not None

    def test_eviction_and_purge(self):
        keys = machine_metrics_series(2)
        cs, meta = InMemoryColumnStore(), InMemoryMetaStore()
        ms = TimeSeriesMemStore(cs, meta)
        shard = ms.setup("ds", 0, StoreConfig(max_chunk_size=10,
                                              groups_per_shard=1,
                                              retention_ms=1_000_000))
        for sd in to_bytes_stream(gauge_stream(keys, 25, batch=1)):
            shard.ingest(sd)
        shard.flush_all()
        p = shard.partitions[0]
        assert p.evict_flushed_chunks() >= 2
        assert not p.ingest(1000, (5.0,))  # floor holds after eviction
        # purge drops the native slot and the key
        purged = shard.purge_expired(now_ms=10_000_000)
        assert purged == 2
        assert shard.num_partitions == 0
        # re-creating the same series works (new native pid); offsets must
        # sit above the flush watermark
        fresh = [SomeData(sd.container, sd.offset + 1000) for sd in
                 to_bytes_stream(gauge_stream(keys, 3, batch=1,
                                              start_ms=20_000_000))]
        for sd in fresh:
            shard.ingest(sd)
        assert shard.num_partitions == 2


class TestMalformedContainers:
    """ADVICE r2 high: a crafted container whose later record carries a
    different value count than the partition's column count must not leave
    columns shorter than ts (seal-time encoders read ts.size() elements —
    heap OOB on the divergent layout)."""

    def _container(self, key, rows):
        from filodb_tpu.core.record import IngestRecord, RecordContainer
        c = RecordContainer()
        for ts, values in rows:
            c.add(IngestRecord(key, ts, values))
        return BytesContainer(c.serialize())

    def test_shrinking_value_count_pads_nan(self):
        key = machine_metrics_series(1)[0]
        ms = TimeSeriesMemStore(InMemoryColumnStore(), InMemoryMetaStore())
        shard = ms.setup("ds", 0, StoreConfig(max_chunk_size=2,
                                              groups_per_shard=1,
                                              native_ingest=True))
        # first record establishes 2 columns; second carries only 1 value.
        # max_chunk_size=2 seals immediately — the encoder walk over
        # ts.size() elements is exactly the OOB read being regressed.
        bad = self._container(key, [(1000, (1.0, 2.0)), (2000, (3.0,))])
        shard.ingest(SomeData(bad, 0))
        assert shard._native_core is not None
        part = shard.partitions[0]
        ts, vals = part.read_samples(0, 10**15)
        np.testing.assert_array_equal(ts, [1000, 2000])
        np.testing.assert_array_equal(vals, [1.0, 3.0])
        # the SECOND column is where the divergence lived: it must have
        # grown in lockstep (NaN pad), and the sealed encoding of exactly
        # ts.size() elements must round-trip
        from filodb_tpu.memory.codecs import decode_any
        [chunk] = part.chunks
        col1 = decode_any(chunk.vectors[2])
        assert len(col1) == 2
        assert col1[0] == 2.0 and np.isnan(col1[1])

    def test_growing_value_count_drops_extras(self):
        key = machine_metrics_series(1)[0]
        ms = TimeSeriesMemStore(InMemoryColumnStore(), InMemoryMetaStore())
        shard = ms.setup("ds", 0, StoreConfig(max_chunk_size=2,
                                              groups_per_shard=1,
                                              native_ingest=True))
        bad = self._container(key, [(1000, (1.0,)), (2000, (3.0, 9.0))])
        shard.ingest(SomeData(bad, 0))
        ts, vals = shard.partitions[0].read_samples(0, 10**15)
        np.testing.assert_array_equal(ts, [1000, 2000])
        np.testing.assert_array_equal(vals, [1.0, 3.0])
