"""Device-page format tests: host encode ↔ device decode parity (pure-jax
and Pallas-interpret paths)."""

import jax.numpy as jnp
import numpy as np
import pytest

from filodb_tpu.memory.device_pages import (
    BLOCK,
    decode_f32_page_jax,
    decode_ts_page_jax,
    decode_ts_page_pallas,
    encode_f32_page,
    encode_ts_page,
    page_to_arrays,
)


def ts_series(n, jitter=50, seed=0):
    rng = np.random.default_rng(seed)
    return (np.arange(n, dtype=np.int64) * 10_000
            + rng.integers(-jitter, jitter + 1, n) + 1_600_000_000_000)


class TestTsPages:
    @pytest.mark.parametrize("n", [1, 100, 128, 129, 1000])
    def test_round_trip_jax(self, n):
        ts = ts_series(n)
        page = encode_ts_page(ts)
        bases, slopes, widths, words = page_to_arrays(page)
        offsets = np.asarray(decode_ts_page_jax(bases, slopes, widths, words))
        out = (page.bases[:, None] + offsets.astype(np.int64)).ravel()[:n]
        np.testing.assert_array_equal(out, ts)

    def test_regular_timestamps_zero_width(self):
        ts = np.arange(256, dtype=np.int64) * 10_000
        page = encode_ts_page(ts)
        assert (page.widths == 0).all()  # perfect slope: no residual bits

    def test_round_trip_pallas_interpret(self):
        ts = ts_series(300, seed=3)
        page = encode_ts_page(ts)
        _, slopes, widths, words = page_to_arrays(page)
        offsets = np.asarray(decode_ts_page_pallas(
            slopes, widths, words, interpret=True))
        out = (page.bases[:, None] + offsets.astype(np.int64)).ravel()[:300]
        np.testing.assert_array_equal(out, ts)

    def test_pallas_matches_jax(self):
        ts = ts_series(513, seed=9, jitter=5000)
        page = encode_ts_page(ts)
        bases, slopes, widths, words = page_to_arrays(page)
        a = np.asarray(decode_ts_page_jax(bases, slopes, widths, words))
        b = np.asarray(decode_ts_page_pallas(slopes, widths, words,
                                             interpret=True))
        np.testing.assert_array_equal(a, b)

    def test_compression(self):
        ts = ts_series(10_000, jitter=20)
        page = encode_ts_page(ts)
        # jittered 10s timestamps: well under raw 8B/sample
        assert page.words[:, :].astype(bool).sum() * 4 < ts.nbytes / 4


class TestF32Pages:
    @pytest.mark.parametrize("n", [1, 127, 128, 500])
    def test_round_trip(self, n):
        rng = np.random.default_rng(1)
        v = rng.normal(100, 5, n).astype(np.float32)
        page = encode_f32_page(v)
        bases, shifts, widths, words = page_to_arrays(page)
        out = np.asarray(decode_f32_page_jax(bases, shifts, widths,
                                             words)).ravel()[:n]
        np.testing.assert_array_equal(out, v)

    def test_constant_block_zero_width(self):
        v = np.full(128, 42.5, np.float32)
        page = encode_f32_page(v)
        assert (page.widths == 0).all()

    def test_nan_values(self):
        v = np.array([1.0, np.nan, 3.0, np.inf, -np.inf], np.float32)
        page = encode_f32_page(v)
        bases, shifts, widths, words = page_to_arrays(page)
        out = np.asarray(decode_f32_page_jax(bases, shifts, widths,
                                             words)).ravel()[:5]
        np.testing.assert_array_equal(out, v)


class TestF32Pallas:
    def test_pallas_matches_jax(self):
        from filodb_tpu.memory.device_pages import (
            decode_f32_page_pallas,
        )
        rng = np.random.default_rng(4)
        v = rng.normal(100, 5, 513).astype(np.float32)
        page = encode_f32_page(v)
        bases, shifts, widths, words = page_to_arrays(page)
        a = np.asarray(decode_f32_page_jax(bases, shifts, widths, words))
        b = np.asarray(decode_f32_page_pallas(bases, shifts, widths, words,
                                              interpret=True))
        np.testing.assert_array_equal(a, b)
