"""Native (C++) codec parity + arena tests.

The native tier mirrors the reference's off-heap layer (UnsafeUtils/jffi,
NibblePack.scala, BlockManager.scala); these tests pin byte-identical output
against the pure-python reference implementation.
"""

import os

import numpy as np
import pytest

from filodb_tpu.memory import native
from filodb_tpu.memory.nibblepack import (
    nibble_pack_py,
    nibble_unpack_py,
)

pytestmark = pytest.mark.skipif(native.get_lib() is None,
                                reason="native toolchain unavailable")


class TestNativeNibblePack:
    def cases(self):
        rng = np.random.default_rng(9)
        yield np.zeros(100, np.uint64)
        yield np.arange(1, 100, dtype=np.uint64)
        yield rng.integers(0, 2**63, 1000, dtype=np.uint64)
        yield rng.integers(0, 16, 777, dtype=np.uint64)
        yield np.array([2**64 - 1, 0, 1, 0xFFF0, 0x1000], np.uint64)
        yield (rng.integers(0, 2**40, 64, dtype=np.uint64) << np.uint64(12))
        yield np.array([], np.uint64)

    def test_pack_byte_identical(self):
        for v in self.cases():
            assert native.nibble_pack_native(v) == nibble_pack_py(v)

    def test_unpack_round_trip(self):
        for v in self.cases():
            packed = nibble_pack_py(v)
            out = native.nibble_unpack_native(packed, len(v))
            np.testing.assert_array_equal(out, v)

    def test_unpack_python_packed_native(self):
        v = np.random.default_rng(1).integers(0, 2**50, 333, dtype=np.uint64)
        packed = native.nibble_pack_native(v)
        np.testing.assert_array_equal(nibble_unpack_py(packed, len(v)), v)

    def test_truncated_stream_raises(self):
        v = np.arange(100, dtype=np.uint64) * 1000
        packed = nibble_pack_py(v)
        with pytest.raises(ValueError):
            native.nibble_unpack_native(packed[: len(packed) // 2], 100)


class TestNativeXor:
    def test_round_trip(self):
        v = np.random.default_rng(2).normal(size=500)
        enc = native.xor_encode_native(v)
        out = native.xor_decode_native(enc)
        np.testing.assert_array_equal(out, v)

    def test_matches_numpy(self):
        v = np.array([1.5, 1.5, 2.25, -0.5, np.nan, 0.0])
        enc = native.xor_encode_native(v)
        bits = v.view(np.uint64)
        prev = np.concatenate([[np.uint64(0)], bits[:-1]])
        np.testing.assert_array_equal(enc, bits ^ prev)


class TestArena:
    def test_alloc_write_read(self):
        arena = native.NativeArena(block_size=4096)
        b = arena.alloc_block(owner=7)
        off = arena.block_alloc(b, 100)
        assert off == 0
        arena.write(b, off, b"hello world")
        assert arena.read(b, off, 11) == b"hello world"
        off2 = arena.block_alloc(b, 50)
        assert off2 == 104  # 8-byte aligned bump
        arena.close()

    def test_block_full(self):
        arena = native.NativeArena(block_size=4096)
        b = arena.alloc_block(owner=1)
        assert arena.block_alloc(b, 4000) == 0
        assert arena.block_alloc(b, 200) == -1  # full
        assert arena.block_remaining(b) == 4096 - 4000
        arena.close()

    def test_reclaim_and_reuse(self):
        arena = native.NativeArena(block_size=4096)
        for _ in range(5):
            arena.alloc_block(owner=1)
        arena.alloc_block(owner=2)
        stats = arena.stats
        assert stats["allocated_blocks"] == 6
        assert stats["bytes_in_use"] == 6 * 4096
        assert arena.reclaim_owner(1) == 5
        assert arena.stats["bytes_in_use"] == 4096
        # reclaimed blocks are reused, not re-allocated
        for _ in range(5):
            arena.alloc_block(owner=3)
        assert arena.stats["allocated_blocks"] == 6
        arena.close()


class TestBuiltFromTheSourcesOnDisk:
    """The library is rebuilt when its sources' content changes, whatever
    the mtimes say: a copied tree keeps a git-ignored ``.so`` whose mtime
    means nothing."""

    @pytest.fixture
    def sandbox(self, tmp_path, monkeypatch):
        import shutil
        for name in ("filodb_native.cpp", "Makefile"):
            shutil.copy(os.path.join(native._NATIVE_DIR, name), tmp_path)
        so = str(tmp_path / "libfilodb_native.so")
        monkeypatch.setattr(native, "_NATIVE_DIR", str(tmp_path))
        monkeypatch.setattr(native, "_SO_PATH", so)
        monkeypatch.setattr(native, "_HASH_PATH", so + ".sha256")
        monkeypatch.setattr(native, "_SRC_PATHS", (
            str(tmp_path / "filodb_native.cpp"), str(tmp_path / "Makefile")))
        return tmp_path

    def test_content_decides_not_mtime(self, sandbox):
        so = sandbox / "libfilodb_native.so"
        assert native._ensure_built() and so.exists()
        assert native._built_hash() == native._source_hash()
        built = so.stat().st_mtime_ns
        # a source that merely LOOKS newer leaves the library alone
        os.utime(sandbox / "filodb_native.cpp")
        assert native._ensure_built() and so.stat().st_mtime_ns == built
        # a library that looks newer than a CHANGED source is still rebuilt
        with open(sandbox / "filodb_native.cpp", "a") as f:
            f.write("\n// changed\n")
        os.utime(so, (2**31, 2**31))
        assert native._ensure_built()
        assert so.stat().st_mtime_ns != 2**31 * 10**9
        assert native._built_hash() == native._source_hash()

    def test_failed_build_reports_false(self, sandbox):
        (sandbox / "filodb_native.cpp").write_text("this is not C++")
        assert native._ensure_built() is False
        assert native._built_hash() is None
