"""Native-backed shard ingest: Python wrappers over the C++ shard core.

The reference's ingest hot loop is native-tier code: per-shard single-writer
appenders over off-heap write buffers with O(1) part-key lookup
(``core/src/main/scala/filodb.core/memstore/TimeSeriesShard.scala:570``,
``TimeSeriesPartition.scala:137``, ``PartitionSet.scala``). Here the hot loop
lives in ``native/filodb_native.cpp`` (``shard_core_ingest``): binary
RecordContainer bytes are parsed, routed, appended and sealed into encoded
chunks entirely in C++ — Python sees only whole sealed chunks, partition
-creation events, and counters.

``NativeBackedPartition`` presents the ``TimeSeriesPartition`` protocol over
a native partition so the entire query/flush/eviction path works unchanged.
"""

from __future__ import annotations

import ctypes
import struct

import numpy as np

from filodb_tpu.core.partkey import PartKey
from filodb_tpu.core.schemas import Schema
from filodb_tpu.memory import native
from filodb_tpu.memory.chunk import Chunk
from filodb_tpu.memory.codecs import CODEC_XOR_DOUBLE


def native_available() -> bool:
    return native.get_lib() is not None


def part_key_blob(key: PartKey) -> bytes:
    """Canonical key bytes — byte-identical to the container v2 record's
    schema-id + label section (the native map key); one shared codec."""
    from filodb_tpu.core.record import _schema_ids, encode_labels
    return struct.pack("<H", _schema_ids(key.schema)) \
        + encode_labels(key.labels)


def part_key_from_blob(blob: bytes, schemas) -> PartKey:
    from filodb_tpu.core.record import decode_labels
    (sid,) = struct.unpack_from("<H", blob, 0)
    labels, _ = decode_labels(blob, 2)
    return PartKey(schemas.by_id(sid).name, labels)


class NativeShardCore:
    """Handle on one shard's C++ ingest core.

    ``lock`` serializes every C++ call that can touch a partition's vectors:
    the host query path reads lock-free under the GIL, but ctypes releases
    the GIL, so a reader copying a buffer while the ingest thread reallocs
    it would be a use-after-free. This is the native analog of the
    reference's ChunkMap read/write latch (``ChunkMap.scala:15-44``).
    """

    def __init__(self, max_chunk_size: int, groups: int):
        import threading
        self._lib = native.get_lib()
        if self._lib is None:
            raise RuntimeError("native library unavailable")
        self._core = ctypes.c_void_p(
            self._lib.shard_core_create(max_chunk_size, groups))
        self.lock = threading.RLock()

    def __del__(self):
        core, self._core = getattr(self, "_core", None), None
        if core:
            self._lib.shard_core_destroy(core)

    # -- ingest --

    def ingest(self, raw: bytes, offset: int) -> int:
        """Returns rows ingested, or -1 when the container holds value
        shapes the native lane doesn't cover (caller falls back)."""
        with self.lock:
            # bytes are immutable and the C side takes const — zero-copy
            return int(self._lib.shard_core_ingest(self._core, raw,
                                                   len(raw), offset))

    def set_watermark(self, group: int, offset: int) -> None:
        self._lib.shard_core_set_watermark(self._core, group, offset)

    def stat(self, which: int) -> int:
        return int(self._lib.shard_core_stat(self._core, which))

    def drain_new_parts(self) -> list[int]:
        with self.lock:
            n = self.stat(4)
            if not n:
                return []
            out = (ctypes.c_int32 * n)()
            got = self._lib.shard_core_drain_new(self._core, out, n)
            return list(out[:got])

    def key_blob(self, pid: int) -> bytes:
        with self.lock:
            n = self._lib.shard_core_key_len(self._core, pid)
            out = (ctypes.c_uint8 * max(n, 1))()
            self._lib.shard_core_key_copy(self._core, pid, out)
            return bytes(out[:n])

    def create_part(self, key: PartKey, ncols: int) -> int:
        blob = part_key_blob(key)
        buf = (ctypes.c_uint8 * len(blob)).from_buffer_copy(blob)
        with self.lock:
            return int(self._lib.shard_core_create_part(
                self._core, buf, len(blob), key.part_hash, ncols))

    def part_hash(self, pid: int) -> int:
        return int(self._lib.shard_core_part_hash(self._core, pid))

    def buf_fold(self, pids, t0s, t1s, col: int):
        """Batched sequential window fold over write buffers (the sidecar
        query lane's buffer tail): one C call for all partitions instead of
        a ctypes buffer copy per partition. Returns (stats [P, W, 12] f64,
        flags [P] i32) — see ``shard_buf_fold`` in filodb_native.cpp — or
        None when the loaded .so predates the entry point."""
        if not hasattr(self._lib, "shard_buf_fold"):
            return None
        pids = np.ascontiguousarray(pids, np.int32)
        t0s = np.ascontiguousarray(t0s, np.int64)
        t1s = np.ascontiguousarray(t1s, np.int64)
        P, W = len(pids), len(t0s)
        out = np.empty((P, W, 12), np.float64)
        flags = np.empty(P, np.int32)
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64p = ctypes.POINTER(ctypes.c_int64)
        with self.lock:
            self._lib.shard_buf_fold(
                self._core, pids.ctypes.data_as(i32p), P,
                t0s.ctypes.data_as(i64p), t1s.ctypes.data_as(i64p), W, col,
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                flags.ctypes.data_as(i32p))
        return out, flags

    def batch_count(self, pids: np.ndarray, col: int, t0: int, t1: int):
        """One C call for the series ``pids`` (int32), value column ``col``
        (index into the native columns: schema column - 1): how many samples
        of [t0, t1] a batch keeps of each (in range, not NaN), over in-range
        sealed chunks and the write buffer, under the shard's lock. Returns
        (counts, chunks, flags), each int32 [len(pids)] — ``chunks`` the
        sealed chunks in range, ``flags`` nonzero where the native reader
        declines the series (see ``shard_batch_count`` in filodb_native.cpp)
        — or None when the loaded .so predates the entry point."""
        if not hasattr(self._lib, "shard_batch_count"):
            return None
        if pids.dtype != np.int32 or not pids.flags.c_contiguous:
            raise ValueError("batch_count: pids must be contiguous int32")
        n = len(pids)
        counts, chunks, flags = (np.empty(n, np.int32) for _ in range(3))
        i32p = ctypes.POINTER(ctypes.c_int32)
        with self.lock:
            self._lib.shard_batch_count(
                self._core, pids.ctypes.data_as(i32p), n, col, t0, t1,
                counts.ctypes.data_as(i32p), chunks.ctypes.data_as(i32p),
                flags.ctypes.data_as(i32p))
        return counts, chunks, flags

    def batch_fill(self, pids: np.ndarray, col: int, t0: int, t1: int,
                   rows: np.ndarray, caps: np.ndarray, ts: np.ndarray,
                   vals: np.ndarray, counts: np.ndarray) -> None:
        """The second call: decode the same series again and write the
        i-th one's samples — ``ts - t0`` and the value — into row
        ``rows[i]`` (< 0: skip) of the C-contiguous ``ts`` int32 [P, S] and
        ``vals`` f32/f64 [P, S], at most ``caps[i]`` of them
        (``batch_count``'s count), and how many into ``counts[rows[i]]``.
        What lies beyond a row's count, and every row not named, is left as
        it is: the padding is the allocator's, which ``build_batch`` asks
        for arrays already filled with it (new, or a staging buffer of the
        mesh engine refilled)."""
        n = len(pids)
        if not (ts.dtype == np.int32 and ts.flags.c_contiguous
                and vals.flags.c_contiguous and vals.shape == ts.shape
                and vals.dtype in (np.float32, np.float64)
                and pids.dtype == rows.dtype == caps.dtype == counts.dtype
                == np.int32 and len(rows) == len(caps) == n
                and len(counts) >= ts.shape[0]
                and (n == 0 or (rows.max() < ts.shape[0]
                                and caps.max() <= ts.shape[1]))):
            raise ValueError("batch_fill: arrays do not fit the batch")
        i32p = ctypes.POINTER(ctypes.c_int32)
        with self.lock:
            self._lib.shard_batch_fill(
                self._core, pids.ctypes.data_as(i32p), n, col, t0,
                t1, rows.ctypes.data_as(i32p), caps.ctypes.data_as(i32p),
                ts.ctypes.data_as(i32p), ts.shape[1],
                vals.ctypes.data_as(ctypes.c_void_p), vals.shape[1],
                int(vals.dtype == np.float32), counts.ctypes.data_as(i32p))

    def lookup(self, key_blob: bytes) -> int:
        """pid for canonical key bytes, or -1 — the authoritative key map
        for restored shards (no host-language dictionary needed)."""
        buf = (ctypes.c_uint8 * len(key_blob)).from_buffer_copy(key_blob)
        with self.lock:
            return int(self._lib.shard_core_lookup(self._core, buf,
                                                   len(key_blob)))

    def bootstrap(self, buf: bytes) -> int:
        """Bulk-create partitions from snapshot entries (one C call)."""
        with self.lock:
            n = int(self._lib.shard_core_bootstrap(self._core, buf,
                                                   len(buf)))
        if n < 0:
            raise ValueError("malformed bootstrap buffer or non-empty core")
        return n

    def seed_floors(self, pids: np.ndarray, floors: np.ndarray) -> None:
        pids = np.ascontiguousarray(pids, np.int32)
        floors = np.ascontiguousarray(floors, np.int64)
        with self.lock:
            self._lib.shard_core_seed_floors(
                self._core,
                pids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                floors.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                len(pids))

    def part_floor(self, pid: int) -> int:
        return int(self._lib.part_floor(self._core, pid))

    def export_entries(self, n: int) -> tuple[bytes, np.ndarray, np.ndarray]:
        """(core_section, key_off i64[n], key_len i32[n]) — the snapshot's
        partition registry section, built in one C++ pass."""
        with self.lock:
            size = int(self._lib.shard_core_export_size(self._core))
            buf = (ctypes.c_uint8 * size)()
            key_off = np.empty(max(n, 1), np.int64)
            key_len = np.empty(max(n, 1), np.int32)
            self._lib.shard_core_export(
                self._core, buf,
                key_off.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                key_len.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
        return bytes(buf), key_off[:n], key_len[:n]

    def floors(self, n: int) -> np.ndarray:
        out = np.empty(max(n, 1), np.int64)
        with self.lock:
            self._lib.shard_core_floors(
                self._core,
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), n)
        return out[:n]


class NativeBackedPartition:
    """``TimeSeriesPartition``-protocol view over a native partition.

    Sealed chunks materialize lazily as ``Chunk`` objects (cached per native
    version); the active buffer materializes as a ``_Buffers`` snapshot on
    access. All mutation goes through the core.
    """

    __slots__ = ("part_id", "max_chunk_size", "shard",
                 "device_pages", "_core", "_lib", "_chunks_cache",
                 "_chunks_ver", "_part_key", "_schema", "_key_blob",
                 "_schemas", "_sc_cache")

    def __init__(self, core: NativeShardCore, part_id: int,
                 part_key: PartKey | None = None,
                 schema: Schema | None = None, max_chunk_size: int = 400,
                 shard: int = 0, key_blob: bytes | None = None,
                 schemas=None):
        """Either (part_key, schema) or (key_blob, schemas): snapshot
        restore passes blobs so a million keys don't materialize at boot —
        ``part_key``/``schema`` parse lazily on first access."""
        self._core = core
        self._lib = core._lib
        self.part_id = part_id
        self._part_key = part_key
        self._schema = schema
        self._key_blob = key_blob
        self._schemas = schemas
        self.max_chunk_size = max_chunk_size
        self.shard = shard
        self.device_pages = False
        # lazily allocated on first chunk read: an empty list per series
        # is ~56B x 1M series of dead weight at scale
        self._chunks_cache: list[Chunk] | None = None
        self._chunks_ver = -1

    @property
    def bucket_les(self) -> np.ndarray | None:
        """Current bucket bounds for the native hist column (None for
        all-scalar partitions) — the host partition's ``bucket_les``."""
        with self._core.lock:
            nb = int(self._lib.part_hist_nb(self._core._core, self.part_id))
            if nb <= 0:
                return None
            out = np.empty(nb, np.float64)
            self._lib.part_hist_les(
                self._core._core, self.part_id,
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
            return out

    @property
    def part_key(self) -> PartKey:
        if self._part_key is None:
            self._part_key = part_key_from_blob(self._key_blob, self._schemas)
            self._part_key.__dict__["part_hash"] = \
                self._core.part_hash(self.part_id)
        return self._part_key

    @property
    def schema(self) -> Schema:
        if self._schema is None:
            (sid,) = struct.unpack_from("<H", self._key_blob, 0)
            self._schema = self._schemas.by_id(sid)
        return self._schema

    # -- ingest (rare path: replay of object containers, tests) --

    def ingest(self, ts: int, values: tuple) -> bool:
        hist_at = next((i for i, v in enumerate(values)
                        if isinstance(v, tuple)
                        or (isinstance(v, np.ndarray) and v.ndim)), -1)
        if hist_at >= 0:
            les, counts = values[hist_at]
            les = np.ascontiguousarray(les, np.float64)
            counts = np.ascontiguousarray(counts, np.int64)
            dvals = np.array([float(v) if i != hist_at else np.nan
                              for i, v in enumerate(values)], np.float64)
            with self._core.lock:
                return bool(self._lib.part_append_hist(
                    self._core._core, self.part_id, ts,
                    dvals.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                    len(dvals),
                    les.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                    counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                    len(les), hist_at))
        vals = np.asarray(values, np.float64)
        with self._core.lock:
            return bool(self._lib.part_append(
                self._core._core, self.part_id, ts,
                vals.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                len(vals)))

    # -- state --

    @property
    def latest_ts(self) -> int:
        return int(self._lib.part_latest_ts(self._core._core, self.part_id))

    @property
    def earliest_ts(self) -> int:
        return int(self._lib.part_earliest_ts(self._core._core, self.part_id))

    @property
    def num_samples(self) -> int:
        return int(self._lib.part_num_samples(self._core._core, self.part_id))

    @property
    def first_ts(self) -> int:
        return int(self._lib.part_first_ts(self._core._core, self.part_id))

    def seed_dedup_floor(self, ts: int) -> None:
        self._lib.part_seed_floor(self._core._core, self.part_id, ts)

    @property
    def _flushed_id(self) -> int:
        return int(self._lib.part_flushed_id(self._core._core, self.part_id))

    # -- chunks --

    @property
    def chunks(self) -> list[Chunk]:
        core, pid = self._core._core, self.part_id
        with self._core.lock:
            ver = int(self._lib.part_version(core, pid))
            if ver == self._chunks_ver and self._chunks_cache is not None:
                return self._chunks_cache
            n = self._lib.part_num_sealed(core, pid)
            ncols = self._lib.part_ncols(core, pid)
            out: list[Chunk] = []
            meta = (ctypes.c_int64 * 4)()
            for i in range(n):
                self._lib.part_sealed_meta(core, pid, i, meta)
                vectors = []
                for col in range(ncols + 1):
                    ln = self._lib.part_sealed_veclen(core, pid, i, col)
                    buf = (ctypes.c_uint8 * ln)()
                    self._lib.part_sealed_veccopy(core, pid, i, col, buf)
                    vectors.append(bytes(buf))
                out.append(Chunk(int(meta[0]), int(meta[3]), int(meta[1]),
                                 int(meta[2]), tuple(vectors)))
            self._chunks_cache = out
            self._chunks_ver = ver
            return out

    @property
    def _buf(self):
        from filodb_tpu.core.memstore.partition import _Buffers
        core, pid = self._core._core, self.part_id
        with self._core.lock:
            n = self._lib.part_buf_count(core, pid)
            ncols = self._lib.part_ncols(core, pid)
            ts = np.empty(max(n, 1), np.int64)
            cols = np.empty((ncols, max(n, 1)), np.float64)
            if n:
                n = self._lib.part_buf_copy(
                    core, pid, n,
                    ts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                    cols.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
            out_cols = [cols[i] for i in range(ncols)]
            hist_col = int(self._lib.part_hist_col(core, pid))
            if hist_col >= 0 and n:
                nb = int(self._lib.part_hist_nb(core, pid))
                rows = np.zeros((n, max(nb, 1)), np.int64)
                got = self._lib.part_buf_hist_copy(
                    core, pid, n,
                    rows.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
                out_cols[hist_col] = rows[:got] if got == n else \
                    np.vstack([rows[:got],
                               np.zeros((n - got, max(nb, 1)), np.int64)])
        return _Buffers(ts, out_cols, n)

    def switch_buffers(self) -> None:
        with self._core.lock:
            self._lib.part_seal_buffer(self._core._core, self.part_id)

    def make_flush_chunks(self, flush_buffer: bool = True) -> list[Chunk]:
        from filodb_tpu.memory.chunk import ensure_summary
        with self._core.lock:
            if flush_buffer:
                self._lib.part_seal_buffer(self._core._core, self.part_id)
            flushed = self._flushed_id
            out = [c for c in self.chunks if c.id > flushed]
        # natively-sealed chunks carry no summary yet: attach before the
        # chunks leave for the column store (decode memoizes on the Chunk,
        # and the version-keyed chunks cache keeps the attachment)
        for c in out:
            ensure_summary(c)
        return out

    def mark_flushed(self, up_to_id: int) -> None:
        self._lib.part_mark_flushed(self._core._core, self.part_id, up_to_id)

    def evict_flushed_chunks(self) -> int:
        with self._core.lock:
            return int(self._lib.part_evict_flushed(self._core._core,
                                                    self.part_id))

    def has_unpersisted_data(self) -> bool:
        """True while buffer samples or un-flushed sealed chunks remain
        (call after ``evict_flushed_chunks``, which drops flushed ones)."""
        with self._core.lock:
            core, pid = self._core._core, self.part_id
            return bool(self._lib.part_buf_count(core, pid)) \
                or bool(self._lib.part_num_sealed(core, pid))

    @property
    def chunk_nbytes(self) -> int:
        """Encoded chunk bytes without materializing Chunk objects."""
        with self._core.lock:
            return int(self._lib.part_chunk_bytes(self._core._core,
                                                  self.part_id))

    @property
    def unflushed_count(self) -> int:
        with self._core.lock:
            flushed = self._flushed_id
            n = sum(1 for c in self.chunks if c.id > flushed)
            if self._lib.part_buf_count(self._core._core, self.part_id):
                n += 1
            return n

    def free(self) -> None:
        with self._core.lock:
            self._lib.part_free(self._core._core, self.part_id)

    # -- reads: borrow the host partition's implementations (they only use
    #    the protocol surface: chunks / _buf / schema / bucket_les) --

    def chunks_in_range(self, start: int, end: int,
                        include_buffer: bool = True) -> list[Chunk]:
        from filodb_tpu.core.memstore.partition import TimeSeriesPartition
        return TimeSeriesPartition.chunks_in_range(self, start, end,
                                                   include_buffer)

    def _buffer_chunk(self) -> Chunk:
        from filodb_tpu.core.memstore.partition import TimeSeriesPartition
        return TimeSeriesPartition._buffer_chunk(self)

    def read_samples(self, start: int, end: int, col: int = None,
                     extra_chunks: list | None = None):
        from filodb_tpu.core.memstore.partition import TimeSeriesPartition
        return TimeSeriesPartition.read_samples(self, start, end, col,
                                                extra_chunks)


# sanity: the native value codec id must match what decode_any dispatches on
assert CODEC_XOR_DOUBLE == 3
