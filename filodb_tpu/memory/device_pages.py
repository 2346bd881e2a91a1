"""Device-decodable chunk pages: bit-packed columns that decode ON the TPU.

The NibblePack wire format (byte-granular, data-dependent layout) is ideal
for host/C++ decode but hostile to SIMD/TPU lanes. For the query hot path we
re-encode chunks into **device pages**: fixed 128-value blocks (one VPU lane
row) with per-block fixed bit widths — decode is pure shifts/masks/prefix
sums with no data-dependent control flow, implemented twice:

- ``decode_*_jax``   — pure jnp (works everywhere, XLA-fused)
- ``decode_*_pallas``— Pallas TPU kernel (grid over blocks, VMEM tiles),
  with ``interpret=True`` fallback used in CPU tests

Timestamp layout (delta-delta, reference ``DeltaDeltaVector`` semantics):
  per block: base i64, slope i32, width w; 128 zigzag residuals bit-packed
  into ``ceil(128*w/32)`` u32 words. value[i] = base + slope*i + zz(resid).

Float layout (XOR against block's first value, f32 lanes):
  per block: first u32 bit pattern, width w; 128 XOR deltas bit-packed.
  Unlike the reference's f64 stream XOR, deltas XOR against the *block
  first* value, not the previous sample — this removes the sequential
  dependency so lanes decode independently (trailing zero bits dropped).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 128
WORDS_PER_BLOCK_MAX = BLOCK  # at w=32: 128*32/32


@dataclass
class DevicePage:
    """One column encoded for device decode."""

    n: int                      # valid values
    kind: str                   # "ts" | "f32"
    bases: np.ndarray           # ts: int64 [nb]; f32: uint32 [nb]
    slopes: np.ndarray          # ts: int32 [nb]; f32: zeros
    widths: np.ndarray          # int32 [nb], bits per packed value
    words: np.ndarray           # uint32 [nb, words_per_block] (padded)

    @property
    def num_blocks(self) -> int:
        return len(self.bases)

    @property
    def nbytes(self) -> int:
        return (self.bases.nbytes + self.slopes.nbytes + self.widths.nbytes
                + self.words.nbytes)


def _bit_width_u32(x: np.ndarray) -> int:
    m = int(x.max()) if len(x) else 0
    return int(m).bit_length()


def _pack_block(vals_u32: np.ndarray, w: int) -> np.ndarray:
    """Pack BLOCK u32 values of width w into ceil(BLOCK*w/32) u32 words."""
    nwords = -(-BLOCK * w // 32) if w else 0
    out = np.zeros(WORDS_PER_BLOCK_MAX, np.uint32)
    if w == 0:
        return out
    acc = 0
    accbits = 0
    wi = 0
    mask = (1 << w) - 1
    for v in vals_u32:
        acc |= (int(v) & mask) << accbits
        accbits += w
        while accbits >= 32:
            out[wi] = acc & 0xFFFFFFFF
            acc >>= 32
            accbits -= 32
            wi += 1
    if accbits:
        out[wi] = acc & 0xFFFFFFFF
    return out


def encode_ts_page(ts: np.ndarray) -> DevicePage:
    """Delta-delta encode timestamps into device blocks."""
    ts = np.ascontiguousarray(ts, np.int64)
    n = len(ts)
    nb = max(-(-n // BLOCK), 1)
    bases = np.zeros(nb, np.int64)
    slopes = np.zeros(nb, np.int32)
    widths = np.zeros(nb, np.int32)
    words = np.zeros((nb, WORDS_PER_BLOCK_MAX), np.uint32)
    for b in range(nb):
        seg = ts[b * BLOCK : (b + 1) * BLOCK]
        if len(seg) == 0:
            continue
        base = int(seg[0])
        slope = int((int(seg[-1]) - base) // max(len(seg) - 1, 1))
        resid = seg - (base + slope * np.arange(len(seg), dtype=np.int64))
        zz = ((resid << 1) ^ (resid >> 63)).astype(np.uint64)
        assert zz.max(initial=0) < 2**32, "residual too large for ts page"
        zz32 = zz.astype(np.uint32)
        pad = np.zeros(BLOCK, np.uint32)
        pad[: len(seg)] = zz32
        w = _bit_width_u32(zz32)
        bases[b], slopes[b], widths[b] = base, slope, w
        words[b] = _pack_block(pad, w)
    return DevicePage(n, "ts", bases, slopes, widths, words)


def encode_f32_page(vals: np.ndarray) -> DevicePage:
    """XOR-vs-block-first encode float32 values into device blocks."""
    v = np.ascontiguousarray(vals, np.float32)
    n = len(v)
    nb = max(-(-n // BLOCK), 1)
    bases = np.zeros(nb, np.uint32)
    slopes = np.zeros(nb, np.int32)
    widths = np.zeros(nb, np.int32)
    words = np.zeros((nb, WORDS_PER_BLOCK_MAX), np.uint32)
    for b in range(nb):
        seg = v[b * BLOCK : (b + 1) * BLOCK]
        if len(seg) == 0:
            continue
        bits = seg.view(np.uint32)
        first = bits[0]
        xored = bits ^ first
        # drop common trailing zero bits across the block
        nz = xored[xored != 0]
        tz = 32
        for x in nz:
            xi = int(x)
            t = (xi & -xi).bit_length() - 1
            tz = min(tz, t)
            if tz == 0:
                break
        if len(nz) == 0:
            tz = 32
        shifted = (xored >> np.uint32(tz % 32)) if tz < 32 else \
            np.zeros_like(xored)
        w = _bit_width_u32(shifted)
        pad = np.zeros(BLOCK, np.uint32)
        pad[: len(seg)] = shifted
        bases[b] = first
        slopes[b] = tz  # reuse the slope slot for the shift amount
        widths[b] = w
        words[b] = _pack_block(pad, w)
    return DevicePage(n, "f32", bases, slopes, widths, words)


# ---------------------------------------------------------------------------
# pure-jax decode (used everywhere; XLA fuses into downstream kernels)

def _unpack_block_jax(words, w):
    """words u32 [nwords]; returns u32 [BLOCK] of width-w fields.
    No data-dependent shapes: lane i reads bits [i*w, i*w+w)."""
    i = jnp.arange(BLOCK, dtype=jnp.uint32)
    bit0 = i * w.astype(jnp.uint32)
    word_idx = (bit0 >> 5).astype(jnp.int32)
    bit_off = bit0 & 31
    lo = words[jnp.clip(word_idx, 0, words.shape[0] - 1)]
    hi = words[jnp.clip(word_idx + 1, 0, words.shape[0] - 1)]
    mask = jnp.where(w >= 32, jnp.uint32(0xFFFFFFFF),
                     (jnp.uint32(1) << w.astype(jnp.uint32)) - 1)
    lo_part = lo >> bit_off
    hi_part = jnp.where(bit_off > 0, hi << (32 - bit_off), 0).astype(
        jnp.uint32)
    out = (lo_part | hi_part) & mask
    return jnp.where(w == 0, 0, out).astype(jnp.uint32)


@jax.jit
def decode_ts_page_jax(bases, slopes, widths, words):
    """→ int64-equivalent timestamps as int32 relative... returns int64 when
    x64 enabled, else float64-safe int32 path is caller's concern. Here we
    produce int64 via two int32 halves when x64 is off is unnecessary —
    callers rebase to the batch base; we return (nb, BLOCK) int32 offsets
    from each block base plus the int64 bases."""
    def one(base, slope, w, wd):
        zz = _unpack_block_jax(wd, w)
        resid = (zz >> 1).astype(jnp.int32) ^ -(zz & 1).astype(jnp.int32)
        pred = slope * jnp.arange(BLOCK, dtype=jnp.int32)
        return pred + resid  # offsets from block base

    return jax.vmap(one)(bases, slopes, widths, words)


@jax.jit
def decode_f32_page_jax(bases, shifts, widths, words):
    def one(first, tz, w, wd):
        x = _unpack_block_jax(wd, w)
        xored = jnp.where(tz >= 32, jnp.uint32(0),
                          x << tz.astype(jnp.uint32))
        bits = xored ^ first
        return jax.lax.bitcast_convert_type(bits, jnp.float32)

    return jax.vmap(one)(bases, shifts, widths, words)


# ---------------------------------------------------------------------------
# pallas decode kernels
#
# Mosaic (real-TPU) lowering constraints shape the design (both kernels are
# compiled for a described v5e by tests/test_chip_compile.py):
#   - rank-1 blocks and (1, N) tiles don't lower → grid steps cover ROWS=8
#     blocks at a time with (8, 128)-tiled VMEM blocks (native sublane×lane
#     tile for 32-bit types);
#   - SMEM only serves scalar reads → per-block width/slope/first scalars
#     ride as scalar-prefetch operands, read with an unrolled 8-scalar loop;
#   - lane-dim gather (`take_along_axis`) and per-lane variable shifts DO
#     lower, so the bit-unpack stays a gather + shift/mask program.

ROWS = 8  # blocks decoded per grid step


def _unpack_tile(w_col, words, out_dtype=jnp.uint32):
    """Shared (ROWS, BLOCK) bit-unpack: width-w_col fields from words."""
    col = jax.lax.broadcasted_iota(jnp.uint32, (ROWS, BLOCK), 1)
    bit0 = col * w_col
    word_idx = (bit0 >> 5).astype(jnp.int32)
    bit_off = bit0 & 31
    lo = jnp.take_along_axis(words, word_idx, axis=1)
    hi = jnp.take_along_axis(
        words, jnp.minimum(word_idx + 1, WORDS_PER_BLOCK_MAX - 1), axis=1)
    mask = jnp.where(w_col >= 32, jnp.uint32(0xFFFFFFFF),
                     (jnp.uint32(1) << w_col) - jnp.uint32(1))
    val = ((lo >> bit_off)
           | jnp.where(bit_off > 0, hi << (32 - bit_off), 0).astype(
               jnp.uint32)) & mask
    return jnp.where(w_col == 0, jnp.uint32(0), val)


def _smem_col(ref, base, dtype=None):
    """Read ROWS consecutive SMEM scalars into an (ROWS, 1) vector."""
    vals = [ref[base + r] for r in range(ROWS)]
    v = jnp.stack(vals).reshape(ROWS, 1)
    return v if dtype is None else v.astype(dtype)


def _ts_kernel(slopes_ref, widths_ref, words_ref, out_ref):
    from jax.experimental import pallas as pl

    g = pl.program_id(0)
    w_col = _smem_col(widths_ref, g * ROWS, jnp.uint32)
    slope_col = _smem_col(slopes_ref, g * ROWS)
    zz = _unpack_tile(w_col, words_ref[...])
    resid = (zz >> 1).astype(jnp.int32) ^ -(zz & 1).astype(jnp.int32)
    pred = slope_col * jax.lax.broadcasted_iota(jnp.int32, (ROWS, BLOCK), 1)
    out_ref[...] = pred + resid


def _pad_blocks(arrs, nb):
    """Pad leading (block) dim of each array to a multiple of ROWS."""
    nb_pad = -(-nb // ROWS) * ROWS
    if nb_pad == nb:
        return arrs, nb_pad
    return [jnp.pad(a, [(0, nb_pad - nb)] + [(0, 0)] * (a.ndim - 1))
            for a in arrs], nb_pad


def decode_ts_page_pallas(slopes, widths, words, interpret: bool = False):
    """Pallas grid over 8-block tiles: per-block offsets from the block base
    (reference hot-path decode `DeltaDeltaDataReader` semantics, on device)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nb = slopes.shape[0]
    (slopes, widths, words), nb_pad = _pad_blocks(
        [slopes, widths, words], nb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb_pad // ROWS,),
        in_specs=[pl.BlockSpec((ROWS, WORDS_PER_BLOCK_MAX),
                               lambda g, *_: (g, 0))],
        out_specs=pl.BlockSpec((ROWS, BLOCK), lambda g, *_: (g, 0)),
    )
    out = pl.pallas_call(
        _ts_kernel,
        out_shape=jax.ShapeDtypeStruct((nb_pad, BLOCK), jnp.int32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(slopes, widths, words)
    return out[:nb]


def page_to_arrays(page: DevicePage):
    """Device arrays for the decode kernels."""
    return (jnp.asarray(page.bases), jnp.asarray(page.slopes),
            jnp.asarray(page.widths), jnp.asarray(page.words))


def _f32_kernel(firsts_ref, shifts_ref, widths_ref, words_ref, out_ref):
    from jax.experimental import pallas as pl

    g = pl.program_id(0)
    w_col = _smem_col(widths_ref, g * ROWS, jnp.uint32)
    tz_col = _smem_col(shifts_ref, g * ROWS, jnp.uint32)
    first_col = jax.lax.bitcast_convert_type(
        _smem_col(firsts_ref, g * ROWS), jnp.uint32)
    x = _unpack_tile(w_col, words_ref[...])
    xored = jnp.where(tz_col >= 32, jnp.uint32(0), x << tz_col)
    bits = xored ^ first_col
    out_ref[...] = jax.lax.bitcast_convert_type(bits, jnp.float32)


def decode_f32_page_pallas(firsts, shifts, widths, words,
                           interpret: bool = False):
    """Pallas grid over 8-block tiles: XOR-vs-first float decode on device."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nb = firsts.shape[0]
    # SMEM carries i32 scalars; ship the u32 bit patterns bitcast to i32.
    firsts_i32 = jax.lax.bitcast_convert_type(
        jnp.asarray(firsts), jnp.int32)
    (firsts_i32, shifts, widths, words), nb_pad = _pad_blocks(
        [firsts_i32, shifts, widths, words], nb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nb_pad // ROWS,),
        in_specs=[pl.BlockSpec((ROWS, WORDS_PER_BLOCK_MAX),
                               lambda g, *_: (g, 0))],
        out_specs=pl.BlockSpec((ROWS, BLOCK), lambda g, *_: (g, 0)),
    )
    out = pl.pallas_call(
        _f32_kernel,
        out_shape=jax.ShapeDtypeStruct((nb_pad, BLOCK), jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(firsts_i32, shifts, widths, words)
    return out[:nb]
