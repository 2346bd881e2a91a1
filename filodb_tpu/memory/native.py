"""ctypes bindings to the C++ native runtime (``native/filodb_native.cpp``).

Builds the shared library on demand (rebuilt whenever the hash of its sources
differs from the one recorded beside the library) and exposes:
- fast NibblePack pack/unpack, zigzag, XOR-double prep — byte-identical to
  the numpy reference implementations; used by the ingest/flush hot path.
- the block arena (reference ``BlockManager`` semantics).

Falls back gracefully (``HAVE_NATIVE = False``) when no compiler is present.
"""

from __future__ import annotations

import ctypes
import fcntl
import hashlib
import logging
import os
import subprocess
import threading

import numpy as np

log = logging.getLogger(__name__)

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")
_SO_PATH = os.path.join(_NATIVE_DIR, "libfilodb_native.so")
_SRC_PATHS = (os.path.join(_NATIVE_DIR, "filodb_native.cpp"),
              os.path.join(_NATIVE_DIR, "Makefile"))
# hash of the sources the library beside it was built from, written only
# after a complete build
_HASH_PATH = _SO_PATH + ".sha256"

_lib = None
_lock = threading.Lock()
HAVE_NATIVE = False


def _source_hash() -> str:
    h = hashlib.sha256()
    for path in _SRC_PATHS:
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _built_hash() -> str | None:
    try:
        with open(_HASH_PATH) as f:
            return f.read().strip()
    except OSError:
        return None


def _ensure_built() -> bool:
    """Make the library on disk the one the sources here produce. Staleness
    is decided by content, not mtime: a copied tree (a checkout, an rsync,
    the chip tool) keeps a git-ignored library whose mtime says nothing
    about which source it came from. Builds are serialized across processes
    by a file lock so that concurrent importers (pytest-xdist workers on a
    fresh checkout) wait for one complete library instead of loading a
    half-written one."""
    want = _source_hash()

    def current() -> bool:
        return os.path.exists(_SO_PATH) and _built_hash() == want

    if current():
        return True
    with open(_SO_PATH + ".lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if current():
            return True  # another process built it while we waited
        try:
            # -B: make itself goes by mtime
            subprocess.run(["make", "-B", "-C", _NATIVE_DIR, "-s"],
                           check=True, capture_output=True, timeout=120)
        except (OSError, subprocess.SubprocessError) as e:
            log.warning("native build failed, using numpy codecs: %s", e)
            return False
        with open(_HASH_PATH, "w") as f:
            f.write(want + "\n")
        return True


def _load():
    global _lib, HAVE_NATIVE
    with _lock:
        if _lib is not None:
            return _lib
        if not _ensure_built():
            return None
        try:
            lib = ctypes.CDLL(_SO_PATH)
        except OSError as e:  # pragma: no cover
            log.warning("native load failed: %s", e)
            return None
        u8p = ctypes.POINTER(ctypes.c_uint8)
        u64p = ctypes.POINTER(ctypes.c_uint64)
        i64p = ctypes.POINTER(ctypes.c_int64)
        f64p = ctypes.POINTER(ctypes.c_double)
        i64 = ctypes.c_int64
        lib.nibble_pack.argtypes = [u64p, i64, u8p]
        lib.nibble_pack.restype = i64
        lib.nibble_unpack.argtypes = [u8p, i64, u64p, i64]
        lib.nibble_unpack.restype = i64
        lib.murmur3_32.argtypes = [u8p, i64, ctypes.c_uint32]
        lib.murmur3_32.restype = ctypes.c_uint32
        lib.zigzag_encode_i64.argtypes = [i64p, u64p, i64]
        lib.zigzag_decode_u64.argtypes = [u64p, i64p, i64]
        lib.xor_encode_f64.argtypes = [f64p, u64p, i64]
        lib.xor_decode_f64.argtypes = [u64p, f64p, i64]
        lib.delta_delta_residuals.argtypes = [i64p, i64, i64, i64, i64p]
        lib.delta_delta_residuals.restype = ctypes.c_int
        lib.delta_delta_reconstruct.argtypes = [i64p, i64, i64, i64, i64p]
        lib.arena_create.argtypes = [i64]
        lib.arena_create.restype = ctypes.c_void_p
        lib.arena_alloc_block.argtypes = [ctypes.c_void_p, i64]
        lib.arena_alloc_block.restype = ctypes.c_void_p
        lib.block_alloc.argtypes = [ctypes.c_void_p, i64]
        lib.block_alloc.restype = i64
        lib.block_data.argtypes = [ctypes.c_void_p]
        lib.block_data.restype = u8p
        lib.block_remaining.argtypes = [ctypes.c_void_p]
        lib.block_remaining.restype = i64
        lib.arena_reclaim_owner.argtypes = [ctypes.c_void_p, i64]
        lib.arena_reclaim_owner.restype = i64
        lib.arena_stats.argtypes = [ctypes.c_void_p, i64]
        lib.arena_stats.restype = i64
        lib.arena_destroy.argtypes = [ctypes.c_void_p]
        # shard ingest core
        vp, i32 = ctypes.c_void_p, ctypes.c_int32
        lib.shard_core_create.argtypes = [i32, i32]
        lib.shard_core_create.restype = vp
        lib.shard_core_destroy.argtypes = [vp]
        lib.shard_core_set_watermark.argtypes = [vp, i32, i64]
        lib.shard_core_ingest.argtypes = [vp, ctypes.c_char_p, i64, i64]
        lib.shard_core_ingest.restype = i64
        lib.shard_core_stat.argtypes = [vp, i32]
        lib.shard_core_stat.restype = i64
        lib.shard_core_drain_new.argtypes = [vp, ctypes.POINTER(i32), i32]
        lib.shard_core_drain_new.restype = i32
        lib.shard_core_create_part.argtypes = [vp, u8p, i32,
                                               ctypes.c_uint32, i32]
        lib.shard_core_create_part.restype = i32
        lib.shard_core_lookup.argtypes = [vp, u8p, i32]
        lib.shard_core_lookup.restype = i32
        lib.shard_core_bootstrap.argtypes = [vp, ctypes.c_char_p, i64]
        lib.shard_core_bootstrap.restype = i64
        lib.shard_core_seed_floors.argtypes = [vp, ctypes.POINTER(i32), i64p,
                                               i64]
        lib.part_floor.argtypes = [vp, i32]
        lib.part_floor.restype = i64
        lib.shard_core_floors.argtypes = [vp, i64p, i64]
        lib.shard_core_export_size.argtypes = [vp]
        lib.shard_core_export_size.restype = i64
        lib.shard_core_chunk_bytes.argtypes = [vp]
        lib.shard_core_chunk_bytes.restype = i64
        lib.shard_core_export.argtypes = [vp, u8p, i64p,
                                          ctypes.POINTER(i32)]
        lib.shard_core_key_len.argtypes = [vp, i32]
        lib.shard_core_key_len.restype = i32
        lib.shard_core_key_copy.argtypes = [vp, i32, u8p]
        lib.shard_core_part_hash.argtypes = [vp, i32]
        lib.shard_core_part_hash.restype = ctypes.c_uint32
        lib.part_append.argtypes = [vp, i32, i64, f64p, i32]
        lib.part_append.restype = i64
        lib.part_append_hist.argtypes = [vp, i32, i64, f64p, i32, f64p,
                                         i64p, i32, i32]
        lib.part_append_hist.restype = i64
        lib.part_hist_col.argtypes = [vp, i32]
        lib.part_hist_col.restype = i32
        lib.part_hist_nb.argtypes = [vp, i32]
        lib.part_hist_nb.restype = i32
        lib.part_hist_les.argtypes = [vp, i32, f64p]
        lib.part_buf_hist_copy.argtypes = [vp, i32, i32, i64p]
        lib.part_buf_hist_copy.restype = i32
        for fn in ("part_latest_ts", "part_first_ts", "part_earliest_ts",
                   "part_num_samples", "part_version", "part_flushed_id",
                   "part_chunk_bytes"):
            getattr(lib, fn).argtypes = [vp, i32]
            getattr(lib, fn).restype = i64
        for fn in ("part_buf_count", "part_ncols", "part_num_sealed"):
            getattr(lib, fn).argtypes = [vp, i32]
            getattr(lib, fn).restype = i32
        lib.part_buf_copy.argtypes = [vp, i32, i32, i64p, f64p]
        lib.part_buf_copy.restype = i32
        lib.part_seal_buffer.argtypes = [vp, i32]
        lib.part_seal_buffer.restype = i32
        lib.part_sealed_meta.argtypes = [vp, i32, i32, i64p]
        lib.part_sealed_veclen.argtypes = [vp, i32, i32, i32]
        lib.part_sealed_veclen.restype = i64
        lib.part_sealed_veccopy.argtypes = [vp, i32, i32, i32, u8p]
        lib.part_mark_flushed.argtypes = [vp, i32, i64]
        lib.part_evict_flushed.argtypes = [vp, i32]
        lib.part_evict_flushed.restype = i32
        lib.part_seed_floor.argtypes = [vp, i32, i64]
        lib.part_free.argtypes = [vp, i32]
        # batched buffer window fold (sidecar lane); absent on .so builds
        # older than the sidecar PR — callers must hasattr-gate
        if hasattr(lib, "shard_buf_fold"):
            lib.shard_buf_fold.argtypes = [vp, ctypes.POINTER(i32), i32,
                                           i64p, i64p, i32, i32, f64p,
                                           ctypes.POINTER(i32)]
            lib.shard_buf_fold.restype = i32
        # batched series read (query/engine/batch.py:build_batch); absent
        # on an older .so — NativeShardCore.batch_count hasattr-gates
        if hasattr(lib, "shard_batch_count"):
            p32 = ctypes.POINTER(i32)
            lib.shard_batch_count.argtypes = [vp, p32, i32, i32, i64, i64,
                                              p32, p32, p32]
            lib.shard_batch_count.restype = i32
            lib.shard_batch_fill.argtypes = [vp, p32, i32, i32, i64, i64,
                                             p32, p32, p32, i64, vp, i64,
                                             i32, p32]
            lib.shard_batch_fill.restype = i32
        # TagIndex (native part-key inverted index hot paths)
        i32p = ctypes.POINTER(ctypes.c_int32)
        u32p = ctypes.POINTER(ctypes.c_uint32)
        cp = ctypes.c_char_p
        lib.tagindex_create.restype = vp
        lib.tagindex_destroy.argtypes = [vp]
        lib.tagindex_add.argtypes = [vp, i32, u8p, i32]
        lib.tagindex_add.restype = i32
        lib.tagindex_purge_pid.argtypes = [vp, i32]
        lib.tagindex_add_batch.argtypes = [vp, ctypes.POINTER(i32), i64,
                                           u8p, i64p]
        lib.tagindex_add_batch.restype = i32
        lib.tagindex_equals.argtypes = [vp, cp, i64, cp, i64, i32p, i64]
        lib.tagindex_equals.restype = i64
        # raw-address args: the equals fast path passes cached integer
        # pointers to skip per-call ctypes marshalling
        lib.tagindex_query_equals.argtypes = [vp, ctypes.c_void_p, i32,
                                              ctypes.c_void_p,
                                              ctypes.c_void_p,
                                              i64, i64, i64,
                                              ctypes.c_void_p, i64]
        lib.tagindex_query_equals.restype = i64
        lib.tagindex_query_equals_allow.argtypes = [
            vp, ctypes.c_void_p, i32, ctypes.c_void_p, i64,
            ctypes.c_void_p, ctypes.c_void_p, i64, i64, i64,
            ctypes.c_void_p, i64]
        lib.tagindex_query_equals_allow.restype = i64
        lib.tagindex_intersect_equals.argtypes = [vp, u8p, i32, i32p, i64]
        lib.tagindex_intersect_equals.restype = i64
        lib.tagindex_label_all.argtypes = [vp, cp, i64, i32p, i64]
        lib.tagindex_label_all.restype = i64
        lib.tagindex_values_size.argtypes = [vp, cp, i64]
        lib.tagindex_values_size.restype = i64
        lib.tagindex_values.argtypes = [vp, cp, i64, u8p]
        lib.tagindex_union_values.argtypes = [vp, cp, i64, i32p, i64, i32p,
                                              i64]
        lib.tagindex_union_values.restype = i64
        lib.tagindex_num_labels.argtypes = [vp]
        lib.tagindex_num_labels.restype = i64
        lib.tagindex_labels_size.argtypes = [vp]
        lib.tagindex_labels_size.restype = i64
        lib.tagindex_labels.argtypes = [vp, u8p]
        lib.tagindex_export_sizes.argtypes = [vp, cp, i64, i32p, i64, i64p]
        lib.tagindex_export_sizes.restype = i64
        lib.tagindex_export_label.argtypes = [vp, u32p, u8p, i64p, i32p]
        lib.tagindex_load_label.argtypes = [vp, cp, i64, u32p, i64, u8p, i64,
                                            i64p, i32p, i64]
        _lib = lib
        HAVE_NATIVE = True
        return lib


def get_lib():
    return _load()


def _as_ptr(arr: np.ndarray, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


def murmur3_32_native(data: bytes, seed: int = 0) -> int | None:
    lib = _load()
    if lib is None:
        return None
    buf = (ctypes.c_uint8 * len(data)).from_buffer_copy(data) if data \
        else (ctypes.c_uint8 * 1)()
    return int(lib.murmur3_32(buf, len(data), seed))


def nibble_pack_native(values: np.ndarray) -> bytes | None:
    lib = _load()
    if lib is None:
        return None
    vals = np.ascontiguousarray(values, dtype=np.uint64)
    n = len(vals)
    out = np.empty(2 + 10 * max(n, 8), np.uint8)
    written = lib.nibble_pack(_as_ptr(vals, ctypes.c_uint64), n,
                              _as_ptr(out, ctypes.c_uint8))
    return out[:written].tobytes()


def nibble_unpack_native(data: bytes, count: int) -> np.ndarray | None:
    lib = _load()
    if lib is None:
        return None
    buf = np.frombuffer(data, dtype=np.uint8)
    out = np.empty(count, np.uint64)
    consumed = lib.nibble_unpack(_as_ptr(buf, ctypes.c_uint8), len(buf),
                                 _as_ptr(out, ctypes.c_uint64), count)
    if consumed < 0:
        raise ValueError("truncated NibblePack stream")
    return out


def xor_encode_native(values: np.ndarray) -> np.ndarray | None:
    lib = _load()
    if lib is None:
        return None
    v = np.ascontiguousarray(values, dtype=np.float64)
    out = np.empty(len(v), np.uint64)
    lib.xor_encode_f64(_as_ptr(v, ctypes.c_double),
                       _as_ptr(out, ctypes.c_uint64), len(v))
    return out


def xor_decode_native(xored: np.ndarray) -> np.ndarray | None:
    lib = _load()
    if lib is None:
        return None
    x = np.ascontiguousarray(xored, dtype=np.uint64)
    out = np.empty(len(x), np.float64)
    lib.xor_decode_f64(_as_ptr(x, ctypes.c_uint64),
                       _as_ptr(out, ctypes.c_double), len(x))
    return out


class NativeArena:
    """Block arena handle (reference ``PageAlignedBlockManager``)."""

    def __init__(self, block_size: int = 1 << 20):
        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._arena = lib.arena_create(block_size)
        self.block_size = block_size

    def alloc_block(self, owner: int) -> ctypes.c_void_p:
        return ctypes.c_void_p(self._lib.arena_alloc_block(self._arena, owner))

    def block_alloc(self, block, nbytes: int) -> int:
        return self._lib.block_alloc(block, nbytes)

    def block_remaining(self, block) -> int:
        return self._lib.block_remaining(block)

    def write(self, block, offset: int, data: bytes) -> None:
        ptr = self._lib.block_data(block)
        ctypes.memmove(ctypes.addressof(ptr.contents) + offset, data,
                       len(data))

    def read(self, block, offset: int, n: int) -> bytes:
        ptr = self._lib.block_data(block)
        return ctypes.string_at(ctypes.addressof(ptr.contents) + offset, n)

    def reclaim_owner(self, owner: int) -> int:
        return self._lib.arena_reclaim_owner(self._arena, owner)

    @property
    def stats(self) -> dict:
        return {
            "allocated_blocks": self._lib.arena_stats(self._arena, 0),
            "reclaimed_blocks": self._lib.arena_stats(self._arena, 1),
            "bytes_in_use": self._lib.arena_stats(self._arena, 2),
        }

    def close(self):
        if self._arena:
            self._lib.arena_destroy(self._arena)
            self._arena = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class TagIndexNative:
    """Handle on a C++ TagIndex — the postings store behind PartKeyIndex
    (reference ``PartKeyLuceneIndex`` postings + query hot paths,
    ``PartKeyLuceneIndex.scala:455,494``). Times and tombstones stay on the
    Python side; this holds label→value→pid postings only."""

    __slots__ = ("_lib", "_h", "_buf", "_buf_addr", "_lock", "_pend",
                 "generation")

    _FLUSH_AT = 4096

    def __init__(self):
        self._lib = get_lib()
        if self._lib is None:
            raise RuntimeError("native library unavailable")
        self._h = self._lib.tagindex_create()
        self._buf = np.empty(4096, np.int32)
        self._buf_addr = self._buf.ctypes.data
        # ctypes releases the GIL and the C++ maps are not concurrent-safe
        # (ingest thread writes while query threads read) — serialize calls,
        # the native analog of ChunkMap's read/write latch
        self._lock = threading.Lock()
        # buffered adds, flushed in one native batch call on any read (the
        # Lucene analog: IndexWriter RAM buffer + NRT refresh — here with
        # strict read-your-writes, PartKeyLuceneIndex.startFlushThread:167).
        # One list of (pid, blob) tuples: a single GIL-atomic append per add
        # lets the single-writer ingest thread skip the lock entirely.
        self._pend: list[tuple[int, bytes]] = []
        # bumps on every postings mutation; callers key value-scan caches
        self.generation = 0

    def close(self):
        if self._h:
            self._lib.tagindex_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def add(self, pid: int, key_blob: bytes) -> None:
        self._pend.append((pid, key_blob))
        self.generation += 1
        if len(self._pend) >= self._FLUSH_AT:
            with self._lock:
                self._flush()

    def _flush(self) -> None:
        """Push buffered adds into the native index (caller holds _lock)."""
        if not self._pend:
            return
        pend, self._pend = self._pend, []  # atomic swap vs concurrent adds
        pids = np.fromiter((p for p, _ in pend), np.int32, len(pend))
        blob = b"".join(b for _, b in pend)
        offs = np.zeros(len(pend) + 1, np.int64)
        np.cumsum([len(b) for _, b in pend], out=offs[1:])
        rc = self._lib.tagindex_add_batch(
            self._h, _as_ptr(pids, ctypes.c_int32), len(pids),
            ctypes.cast(blob, ctypes.POINTER(ctypes.c_uint8)),
            _as_ptr(offs, ctypes.c_int64))
        if rc != 0:
            raise ValueError("malformed part-key blob in batch")

    def purge_pid(self, pid: int) -> None:
        with self._lock:
            self._flush()
            self.generation += 1
            self._lib.tagindex_purge_pid(self._h, pid)

    def _out_locked(self, fn, *args) -> np.ndarray:
        n = fn(self._h, *args, _as_ptr(self._buf, ctypes.c_int32),
               len(self._buf))
        if n < 0:
            self._buf = np.empty(int(-n) + 64, np.int32)
            self._buf_addr = self._buf.ctypes.data
            n = fn(self._h, *args, _as_ptr(self._buf, ctypes.c_int32),
                   len(self._buf))
        return self._buf[: int(n)].copy()

    def equals(self, label: str, value: str) -> np.ndarray:
        with self._lock:
            self._flush()
            lb, vb = label.encode(), value.encode()
            return self._out_locked(self._lib.tagindex_equals,
                                    lb, len(lb), vb, len(vb))

    @staticmethod
    def encode_pairs(pairs: list[tuple[str, str]]) -> bytes:
        import struct
        buf = bytearray()
        for k, v in pairs:
            kb, vb = k.encode(), v.encode()
            buf += struct.pack("<H", len(kb)) + kb
            buf += struct.pack("<H", len(vb)) + vb
        return bytes(buf)

    @staticmethod
    def addr_of(buf) -> int:
        """Stable raw address of a bytes object / numpy array (caller must
        keep the object alive for as long as the address is used)."""
        if isinstance(buf, bytes):
            return ctypes.cast(buf, ctypes.c_void_p).value or 0
        return buf.ctypes.data

    def intersect_equals(self, pairs: list[tuple[str, str]]) -> np.ndarray:
        with self._lock:
            self._flush()
            bb = self.encode_pairs(pairs)
            return self._out_locked(
                lambda h, o, c: self._lib.tagindex_intersect_equals(
                    h, ctypes.cast(bb, ctypes.POINTER(ctypes.c_uint8)),
                    len(pairs), o, c))

    def query_equals(self, pairs_addr: int, npairs: int,
                     starts_addr: int, ends_addr: int, bounds_len: int,
                     start_t: int, end_t: int) -> list[int]:
        """Full equals fast path: postings intersection + time predicate in
        one native call; returns live pids as a list. Callers pass raw
        addresses (``addr_of``) and must keep the backing objects alive."""
        with self._lock:
            if self._pend:
                self._flush()
            n = self._lib.tagindex_query_equals(
                self._h, pairs_addr, npairs, starts_addr, ends_addr,
                bounds_len, start_t, end_t, self._buf_addr, len(self._buf))
            if n < 0:
                self._buf = np.empty(int(-n) + 64, np.int32)
                self._buf_addr = self._buf.ctypes.data
                n = self._lib.tagindex_query_equals(
                    self._h, pairs_addr, npairs, starts_addr, ends_addr,
                    bounds_len, start_t, end_t, self._buf_addr,
                    len(self._buf))
            return self._buf[: int(n)].tolist()

    def query_equals_allow(self, pairs_addr: int, npairs: int,
                           allow: np.ndarray, starts_addr: int,
                           ends_addr: int, bounds_len: int,
                           start_t: int, end_t: int) -> list[int]:
        """Equals postings ∩ sorted allow-list (cached regex postings) ∩
        time predicate, one native call — the regex-filter fast path."""
        allow = np.ascontiguousarray(allow, np.int32)
        aptr = allow.ctypes.data
        with self._lock:
            if self._pend:
                self._flush()
            n = self._lib.tagindex_query_equals_allow(
                self._h, pairs_addr, npairs, aptr, len(allow), starts_addr,
                ends_addr, bounds_len, start_t, end_t, self._buf_addr,
                len(self._buf))
            if n < 0:
                self._buf = np.empty(int(-n) + 64, np.int32)
                self._buf_addr = self._buf.ctypes.data
                n = self._lib.tagindex_query_equals_allow(
                    self._h, pairs_addr, npairs, aptr, len(allow),
                    starts_addr, ends_addr, bounds_len, start_t, end_t,
                    self._buf_addr, len(self._buf))
            return self._buf[: int(n)].tolist()

    def label_all(self, label: str) -> np.ndarray:
        with self._lock:
            self._flush()
            lb = label.encode()
            return self._out_locked(self._lib.tagindex_label_all, lb, len(lb))

    def values(self, label: str) -> list[str]:
        with self._lock:
            self._flush()
            lb = label.encode()
            sz = self._lib.tagindex_values_size(self._h, lb, len(lb))
            if sz == 0:
                return []
            raw = np.empty(int(sz), np.uint8)
            self._lib.tagindex_values(self._h, lb, len(lb),
                                      _as_ptr(raw, ctypes.c_uint8))
            out = []
            data = raw.tobytes()
            off = 0
            while off < len(data):
                n = int.from_bytes(data[off : off + 4], "little")
                off += 4
                out.append(data[off : off + n].decode())
                off += n
            return out

    def union_values(self, label: str, vids: np.ndarray) -> np.ndarray:
        with self._lock:
            self._flush()
            lb = label.encode()
            vids = np.ascontiguousarray(vids, np.int32)
            return self._out_locked(
                lambda h, o, c: self._lib.tagindex_union_values(
                    h, lb, len(lb), _as_ptr(vids, ctypes.c_int32), len(vids),
                    o, c))

    def labels(self) -> list[str]:
        with self._lock:
            self._flush()
            sz = self._lib.tagindex_labels_size(self._h)
            if sz == 0:
                return []
            raw = np.empty(int(sz), np.uint8)
            self._lib.tagindex_labels(self._h, _as_ptr(raw, ctypes.c_uint8))
            out = []
            data = raw.tobytes()
            off = 0
            while off < len(data):
                n = int.from_bytes(data[off : off + 4], "little")
                off += 4
                out.append(data[off : off + n].decode())
                off += n
            return out

    def export_label(self, label: str, deleted: np.ndarray):
        """(voff, vblob, poff, pids) snapshot arrays for one label, with
        ``deleted`` (sorted int32) pids dropped. Empty labels yield nv=0."""
        with self._lock:
            self._flush()
            lb = label.encode()
            deleted = np.ascontiguousarray(deleted, np.int32)
            sizes = np.empty(3, np.int64)
            self._lib.tagindex_export_sizes(
                self._h, lb, len(lb), _as_ptr(deleted, ctypes.c_int32),
                len(deleted), _as_ptr(sizes, ctypes.c_int64))
            nv, vlen, npids = (int(x) for x in sizes)
            voff = np.empty(nv + 1, np.uint32)
            vblob = np.empty(vlen, np.uint8)
            poff = np.empty(nv + 1, np.int64)
            pids = np.empty(npids, np.int32)
            self._lib.tagindex_export_label(
                self._h, _as_ptr(voff, ctypes.c_uint32),
                _as_ptr(vblob, ctypes.c_uint8), _as_ptr(poff, ctypes.c_int64),
                _as_ptr(pids, ctypes.c_int32))
            return voff, vblob.tobytes(), poff, pids

    def load_label(self, label: str, voff, vblob: bytes, poff, pids) -> None:
        with self._lock:
            lb = label.encode()
            voff = np.ascontiguousarray(voff, np.uint32)
            poff = np.ascontiguousarray(poff, np.int64)
            pids = np.ascontiguousarray(pids, np.int32)
            self._lib.tagindex_load_label(
                self._h, lb, len(lb), _as_ptr(voff, ctypes.c_uint32),
                len(voff) - 1,
                ctypes.cast(vblob, ctypes.POINTER(ctypes.c_uint8)), len(vblob),
                _as_ptr(poff, ctypes.c_int64), _as_ptr(pids, ctypes.c_int32),
                len(pids))
