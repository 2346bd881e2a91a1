"""Jitted range-function kernels.

Counterpart of the reference's range-function library
(``query/src/main/scala/filodb/query/exec/rangefn/RangeFunction.scala:1-568``,
``AggrOverTimeFunctions.scala:1-970``, ``RateFunctions.scala:1-303``) — but
formulated as dense batched tensor programs instead of per-sample iterators:

- window boundaries: vectorized binary search over padded ts arrays
- windowed sums/averages/stddev/changes/resets: exclusive prefix sums, O(1)
  per step
- min/max over time: sparse-table range-min/max query, O(1) per step
- rate/increase/delta: first/last gathers + a prefix sum of counter-reset
  corrections, with Prometheus extrapolation semantics (reference
  ``RateFunctions.scala`` mirrors promql ``extrapolatedRate``)
- quantile_over_time / holt_winters: masked per-window evaluation, blocked
  over output steps to bound memory

All kernels take ``ts`` as int32 millis relative to the batch base (padding
= INT32_MAX) and are shape-polymorphic only through jit's compile cache —
batch builders bucket shapes to powers of two to keep cache hits high.

Output convention: [P, K] float matrix; NaN = "no result at this step" (maps
to a gap in the Prom JSON output).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# ---------------------------------------------------------------------------
# helpers

def fdtype():
    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


def _valid_mask(ts, counts):
    """Prefix validity mask [P, S], materialized: left fusible, the TPU
    compiler folds the iota-vs-counts compare into every scan that reads it
    (cumsum, cummax, cummin) and takes ~25 s for each one at [8192, 2048]
    on a v5e, against 2-4 s for the same scan over an opaque mask."""
    S = ts.shape[1]
    return lax.optimization_barrier(jnp.arange(S)[None, :] < counts[:, None])


def _eprefix(x):
    """Exclusive prefix sum along the last axis: [..., S] -> [..., S+1]."""
    return jnp.concatenate(
        [jnp.zeros(x.shape[:-1] + (1,), x.dtype), jnp.cumsum(x, -1)], -1)


def window_bounds(ts, steps, window):
    """[lo, hi) sample index bounds of window (t-w, t] per series per step.

    ts: int32 [P, S] sorted, padded with INT32_MAX; steps: int32 [K];
    window: int32 scalar. Returns lo, hi int32 [P, K].
    """
    def one(tsp):
        hi = jnp.searchsorted(tsp, steps, side="right")
        lo = jnp.searchsorted(tsp, steps - window, side="right")
        return lo, hi

    lo, hi = jax.vmap(one)(ts)
    return lo.astype(jnp.int32), hi.astype(jnp.int32)


def _gather(x, idx):
    """x [P, S(+1)], idx [P, K] -> [P, K]."""
    return jnp.take_along_axis(x, idx, axis=1)


def _prev_valid_value(v, valid, pv):
    """(prev_valid_value, prev_exists) per position — comparisons against the
    previous VALID sample, skipping interior gaps."""
    pv_prev = jnp.concatenate(
        [jnp.full_like(pv[:, :1], -1), pv[:, :-1]], axis=1)
    prev_val = jnp.take_along_axis(v, jnp.maximum(pv_prev, 0), axis=1)
    return prev_val, pv_prev >= 0


def _counter_corrected(v, valid, pv=None):
    """Values plus cumulative reset correction (Prometheus counter semantics:
    on a drop, the previous value is added to all subsequent samples).
    Comparisons skip gap positions via the prev-valid index map."""
    if pv is None:
        S = v.shape[1]
        sidx = jnp.arange(S, dtype=jnp.int32)[None, :]
        pv = lax.cummax(jnp.where(valid, sidx, -1), axis=1)
    prev, prev_ok = _prev_valid_value(v, valid, pv)
    dropped = (v < prev) & valid & prev_ok
    correction = jnp.cumsum(jnp.where(dropped, prev, 0.0), axis=1)
    return v + correction


# ---------------------------------------------------------------------------
# sparse table (range min/max query)

def _build_sparse(v, op, identity, levels):
    P, S = v.shape
    tabs = [v]
    cur = v
    for j in range(1, levels):
        half = 1 << (j - 1)
        shifted = jnp.concatenate(
            [cur[:, half:], jnp.full((P, half), identity, v.dtype)], axis=1)
        cur = op(cur, shifted)
        tabs.append(cur)
    return jnp.stack(tabs)  # [L, P, S]


def _rmq(table, lo, hi, op, identity):
    """Range query over [lo, hi) using the sparse table. lo/hi [P, K]."""
    P = table.shape[1]
    w = hi - lo
    j = jnp.maximum(31 - lax.clz(jnp.maximum(w, 1)), 0)
    pw = jnp.left_shift(1, j)
    p_idx = jnp.arange(P)[:, None]
    a = table[j, p_idx, jnp.minimum(lo, table.shape[2] - 1)]
    b = table[j, p_idx, jnp.clip(hi - pw, 0, table.shape[2] - 1)]
    out = op(a, b)
    return jnp.where(w > 0, out, jnp.nan)


# ---------------------------------------------------------------------------
# the main range-function kernel family

SIMPLE_FNS = (
    "sum_over_time", "avg_over_time", "count_over_time", "min_over_time",
    "max_over_time", "stddev_over_time", "stdvar_over_time", "last_over_time",
    "present_over_time", "changes", "resets", "deriv", "irate", "idelta",
    "rate", "increase", "delta", "last_sample", "timestamp", "zscore",
    "absent_over_time",
)


@partial(jax.jit, static_argnames=("fn", "counter", "pre_corrected"))
def range_eval(fn: str, ts, vals, counts, steps, window, extra=0.0,
               counter: bool = False, pre_corrected: bool = False,
               raw=None):
    """Evaluate one range function at each step for each series.

    ts: int32 [P,S] relative ms; vals: float [P,S]; counts: int32 [P];
    steps: int32 [K]; window: int32 scalar ms; extra: scalar parameter
    (predict_linear horizon etc.). Returns float [P,K].

    ``pre_corrected``: values were counter-reset-corrected AND per-series
    rebased host-side in f64 (``SeriesBatch.delta_host``) — the in-kernel
    correction is skipped. ``raw`` [P,S] is the UNcorrected value tensor,
    consulted only where Prometheus' extrapolate-to-zero heuristic needs
    each window's raw first sample (precision there is moot, so the f32
    copy suffices). This is what keeps f32 device math exact at real
    counter magnitudes (a counter ≥2^24 otherwise loses every per-window
    delta to the f32 cast).
    """
    return _range_impl(fn, ts, vals, _valid_mask(ts, counts), steps, window,
                       extra, counter, pre_corrected, raw, counts=counts)


@partial(jax.jit, static_argnames=("fn", "counter", "pre_corrected"))
def range_eval_masked(fn: str, ts, vals, valid, steps, window, extra=0.0,
                      counter: bool = False, pre_corrected: bool = False,
                      raw=None):
    """Mask-aware variant: ``valid`` [P,S] may have interior gaps (device-
    decoded block-aligned pages). Gap positions must carry a timestamp ≤ the
    next valid sample's (monotone non-decreasing ts overall)."""
    return _range_impl(fn, ts, vals, valid, steps, window, extra, counter,
                       pre_corrected, raw)


def _range_impl(fn: str, ts, vals, valid, steps, window, extra, counter,
                pre_corrected: bool = False, raw=None, counts=None):
    """``counts`` [P] is given when ``valid`` is the prefix mask
    ``arange(S) < counts`` (no interior gaps): the prev/next-valid index
    maps then have closed forms and need no scan."""
    dt = fdtype()
    vals = vals.astype(dt)
    v = jnp.where(valid, vals, 0.0)
    S = ts.shape[1]
    lo, hi = window_bounds(ts, steps, window)
    vcount = _eprefix(valid.astype(dt))
    n = _gather(vcount, hi) - _gather(vcount, lo)
    has1 = n >= 1
    has2 = n >= 2
    nan = jnp.array(jnp.nan, dt)
    # valid-sample machinery (positions may be gaps, not just tail padding):
    # prev/next-valid index maps — only built for functions that gather
    # first/last samples (fn is static, so this prunes the compiled graph)
    pv = nv = first_idx = last_idx = None
    if fn in ("stddev_over_time", "stdvar_over_time", "zscore",
              "last_over_time", "last_sample", "timestamp", "changes",
              "resets", "irate", "idelta", "rate", "increase", "delta"):
        sidx = jnp.arange(S, dtype=jnp.int32)[None, :]
        if counts is None:
            pv = lax.cummax(jnp.where(valid, sidx, -1), axis=1)
            nv = lax.cummin(jnp.where(valid, sidx, S), axis=1, reverse=True)
        else:
            cnt = counts.astype(jnp.int32)[:, None]
            pv = jnp.minimum(sidx, cnt - 1)
            nv = jnp.where(sidx < cnt, sidx, S)
        # first/last VALID sample index within [lo, hi)
        first_idx = jnp.clip(_gather(nv, jnp.minimum(lo, S - 1)), 0, S - 1)
        last_idx = jnp.clip(_gather(pv, jnp.maximum(hi - 1, 0)), 0, S - 1)

    if fn == "count_over_time":
        return jnp.where(has1, n, nan)
    if fn == "present_over_time":
        return jnp.where(has1, 1.0, nan).astype(dt)
    if fn == "absent_over_time":
        # per-series presence; the absent transformer combines across series
        return jnp.where(has1, nan, 1.0).astype(dt)

    if fn in ("sum_over_time", "avg_over_time"):
        csum = _eprefix(v)
        s = _gather(csum, hi) - _gather(csum, lo)
        if fn == "avg_over_time":
            return jnp.where(has1, s / jnp.maximum(n, 1.0), nan)
        return jnp.where(has1, s, nan)

    if fn in ("stddev_over_time", "stdvar_over_time", "zscore"):
        csum = _eprefix(v)
        csum2 = _eprefix(v * v)
        s = _gather(csum, hi) - _gather(csum, lo)
        s2 = _gather(csum2, hi) - _gather(csum2, lo)
        mean = s / jnp.maximum(n, 1.0)
        var = jnp.maximum(s2 / jnp.maximum(n, 1.0) - mean * mean, 0.0)
        if fn == "stdvar_over_time":
            return jnp.where(has1, var, nan)
        sd = jnp.sqrt(var)
        if fn == "stddev_over_time":
            return jnp.where(has1, sd, nan)
        last = _gather(v, last_idx)
        return jnp.where(has1, (last - mean) / sd, nan)

    if fn in ("min_over_time", "max_over_time"):
        S = ts.shape[1]
        levels = max(S.bit_length(), 1)
        if fn == "min_over_time":
            masked = jnp.where(valid, vals, jnp.inf)
            table = _build_sparse(masked, jnp.minimum, jnp.inf, levels)
            out = _rmq(table, lo, hi, jnp.minimum, jnp.inf)
        else:
            masked = jnp.where(valid, vals, -jnp.inf)
            table = _build_sparse(masked, jnp.maximum, -jnp.inf, levels)
            out = _rmq(table, lo, hi, jnp.maximum, -jnp.inf)
        return jnp.where(has1, out, nan)

    if fn in ("last_over_time", "last_sample", "timestamp"):
        if fn == "timestamp":
            t_last = _gather(ts, last_idx).astype(dt)
            return jnp.where(has1, t_last / 1000.0, nan)
        return jnp.where(has1, _gather(v, last_idx), nan)

    if fn in ("changes", "resets"):
        prev_val, prev_ok = _prev_valid_value(v, valid, pv)
        if fn == "changes":
            ind = (v != prev_val) & valid & prev_ok
        else:
            ind = (v < prev_val) & valid & prev_ok
        cind = _eprefix(ind.astype(dt))
        # count indicators whose predecessor is also in the window:
        # positions (first_idx, hi)
        start = jnp.minimum(first_idx + 1, hi)
        cnt = _gather(cind, hi) - _gather(cind, start)
        return jnp.where(has1, cnt, nan)

    if fn in ("irate", "idelta"):
        i1 = last_idx
        i0 = jnp.clip(_gather(pv, jnp.maximum(i1 - 1, 0)), 0, S - 1)
        v1, v0 = _gather(v, i1), _gather(v, i0)
        t1, t0 = _gather(ts, i1).astype(dt), _gather(ts, i0).astype(dt)
        dv = v1 - v0
        if fn == "irate":
            dv = jnp.where(v1 < v0, v1, dv)  # counter reset: instant rate from 0
            out = dv / jnp.maximum((t1 - t0) / 1000.0, 1e-10)
        else:
            out = dv
        return jnp.where(has2, out, nan)

    if fn == "deriv":
        return _linreg(ts, v, valid, lo, hi, steps, slope_only=True)

    if fn == "predict_linear":
        return _linreg(ts, v, valid, lo, hi, steps, slope_only=False,
                       horizon_s=extra)

    if fn in ("rate", "increase", "delta"):
        if pre_corrected or not (counter or fn in ("rate", "increase")):
            cv = v  # host pre-corrected values are already monotone
        else:
            cv = _counter_corrected(jnp.where(valid, vals, 0.0), valid, pv)
            cv = jnp.where(valid, cv, 0.0)
        v_first = _gather(cv, first_idx)
        v_last = _gather(cv, last_idx)
        if pre_corrected and raw is not None:
            # the extrapolate-to-zero heuristic needs each window's RAW
            # first sample — the rebased lane lost that magnitude, so
            # gather it from the raw reference tensor instead
            raw_first = _gather(
                jnp.where(valid, raw.astype(dt), 0.0), first_idx)
        else:
            raw_first = _gather(v, first_idx)
        t_first = _gather(ts, first_idx).astype(dt) / 1000.0
        t_last = _gather(ts, last_idx).astype(dt) / 1000.0
        result = v_last - v_first
        # Prometheus extrapolatedRate semantics
        range_start = (steps[None, :] - window).astype(dt) / 1000.0
        range_end = steps[None, :].astype(dt) / 1000.0
        sampled = t_last - t_first
        avg_dur = sampled / jnp.maximum(n - 1.0, 1.0)
        dur_start = t_first - range_start
        dur_end = range_end - t_last
        if fn in ("rate", "increase"):
            dur_to_zero = jnp.where(result > 0,
                                    sampled * raw_first / jnp.maximum(result, 1e-30),
                                    jnp.inf)
            dur_start = jnp.minimum(dur_start, dur_to_zero)
        threshold = avg_dur * 1.1
        extend = sampled
        extend = extend + jnp.where(dur_start < threshold, dur_start, avg_dur / 2.0)
        extend = extend + jnp.where(dur_end < threshold, dur_end, avg_dur / 2.0)
        factor = extend / jnp.maximum(sampled, 1e-10)
        result = result * factor
        if fn == "rate":
            result = result / (window.astype(dt) / 1000.0)
        return jnp.where(has2, result, nan)

    raise ValueError(f"unknown range function {fn}")


def _linreg(ts, v, valid, lo, hi, steps, slope_only: bool, horizon_s=0.0):
    """Least-squares slope/prediction over each window (deriv/predict_linear).

    Time is centered at the step timestamp to keep the normal equations
    well-conditioned in float32.
    """
    dt = fdtype()
    t_s = jnp.where(valid, ts, 0).astype(dt) / 1000.0
    c_n = _eprefix(valid.astype(dt))
    c_t = _eprefix(jnp.where(valid, t_s, 0.0))
    c_v = _eprefix(v)
    c_tt = _eprefix(jnp.where(valid, t_s * t_s, 0.0))
    c_tv = _eprefix(jnp.where(valid, t_s * v, 0.0))
    n = _gather(c_n, hi) - _gather(c_n, lo)
    St = _gather(c_t, hi) - _gather(c_t, lo)
    Sv = _gather(c_v, hi) - _gather(c_v, lo)
    Stt = _gather(c_tt, hi) - _gather(c_tt, lo)
    Stv = _gather(c_tv, hi) - _gather(c_tv, lo)
    c = steps[None, :].astype(dt) / 1000.0  # center at step time
    St_c = St - n * c
    Stt_c = Stt - 2.0 * c * St + n * c * c
    Stv_c = Stv - c * Sv
    denom = n * Stt_c - St_c * St_c
    slope = (n * Stv_c - St_c * Sv) / jnp.where(denom == 0, 1.0, denom)
    has2 = n >= 2  # n counts VALID samples (mask-aware)
    if slope_only:
        return jnp.where(has2 & (denom != 0), slope, jnp.nan)
    intercept = (Sv - slope * St_c) / jnp.maximum(n, 1.0)
    return jnp.where(has2 & (denom != 0),
                     intercept + slope * horizon_s, jnp.nan)


# ---------------------------------------------------------------------------
# blocked masked kernels (quantile_over_time, holt_winters / double exp)

@partial(jax.jit, static_argnames=("block",))
def quantile_over_time(q, ts, vals, counts, steps, window, block: int = 16):
    """phi-quantile over each window. Masked sort per window, blocked over
    steps to bound the [P, block, S] working set."""
    return _quantile_impl(q, ts, vals, _valid_mask(ts, counts), steps,
                          window, block)


@partial(jax.jit, static_argnames=("block",))
def quantile_over_time_masked(q, ts, vals, valid, steps, window,
                              block: int = 16):
    return _quantile_impl(q, ts, vals, valid, steps, window, block)


def _quantile_impl(q, ts, vals, valid, steps, window, block: int):
    dt = fdtype()
    vals = vals.astype(dt)
    lo, hi = window_bounds(ts, steps, window)
    vcount = _eprefix(valid.astype(dt))
    K = steps.shape[0]
    S = ts.shape[1]
    pad_k = (-K) % block
    lo_p = jnp.pad(lo, ((0, 0), (0, pad_k)))
    hi_p = jnp.pad(hi, ((0, 0), (0, pad_k)))
    nblocks = (K + pad_k) // block
    s_idx = jnp.arange(S)[None, None, :]

    def do_block(b):
        lo_b = lax.dynamic_slice_in_dim(lo_p, b * block, block, axis=1)
        hi_b = lax.dynamic_slice_in_dim(hi_p, b * block, block, axis=1)
        in_win = (s_idx >= lo_b[:, :, None]) & (s_idx < hi_b[:, :, None])
        masked = jnp.where(in_win & valid[:, None, :], vals[:, None, :], jnp.inf)
        srt = jnp.sort(masked, axis=-1)
        n = (jnp.take_along_axis(vcount, hi_b, axis=1)
             - jnp.take_along_axis(vcount, lo_b, axis=1)).astype(dt)
        pos = q * jnp.maximum(n - 1.0, 0.0)
        i0 = jnp.floor(pos).astype(jnp.int32)
        frac = pos - i0
        a = jnp.take_along_axis(srt, i0[:, :, None], axis=-1)[:, :, 0]
        bv = jnp.take_along_axis(
            srt, jnp.minimum(i0 + 1, S - 1)[:, :, None], axis=-1)[:, :, 0]
        out = a + (bv - a) * frac
        return jnp.where(n > 0, out, jnp.nan)

    blocks = lax.map(do_block, jnp.arange(nblocks))  # [nb, P, block]
    out = jnp.moveaxis(blocks, 0, 1).reshape(ts.shape[0], -1)
    return out[:, :K]


@jax.jit
def holt_winters(sf, tf, ts, vals, counts, steps, window):
    """Holt's double exponential smoothing per window (promql holt_winters).

    Sequential by nature: a scan over samples carrying (level, trend) per
    (series, step) window. O(S) scan with [P, K] state.
    """
    return _holt_impl(sf, tf, ts, vals, _valid_mask(ts, counts), steps,
                      window)


@jax.jit
def holt_winters_masked(sf, tf, ts, vals, valid, steps, window):
    return _holt_impl(sf, tf, ts, vals, valid, steps, window)


def _holt_impl(sf, tf, ts, vals, valid, steps, window):
    dt = fdtype()
    vals = vals.astype(dt)
    lo, hi = window_bounds(ts, steps, window)
    S = ts.shape[1]
    P, K = lo.shape

    def step_fn(carry, i):
        level, trend, cnt = carry
        in_win = (i >= lo) & (i < hi) & valid[:, i][:, None]
        x = vals[:, i][:, None]
        new_level1 = x  # first sample initializes level
        new_trend1 = jnp.zeros_like(x)
        new_trend2 = x - level  # second sample initializes trend
        new_level2 = x
        sm_level = sf * x + (1 - sf) * (level + trend)
        sm_trend = tf * (sm_level - level) + (1 - tf) * trend
        nl = jnp.where(cnt == 0, new_level1,
                       jnp.where(cnt == 1, new_level2, sm_level))
        nt = jnp.where(cnt == 0, new_trend1,
                       jnp.where(cnt == 1, new_trend2, sm_trend))
        level = jnp.where(in_win, nl, level)
        trend = jnp.where(in_win, nt, trend)
        cnt = jnp.where(in_win, cnt + 1, cnt)
        return (level, trend, cnt), None

    init = (jnp.zeros((P, K), dt), jnp.zeros((P, K), dt),
            jnp.zeros((P, K), jnp.int32))
    (level, trend, cnt), _ = lax.scan(step_fn, init, jnp.arange(S))
    return jnp.where(cnt >= 2, level, jnp.nan)
