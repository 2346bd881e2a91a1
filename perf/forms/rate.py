"""``rate``: Prometheus's ``extrapolatedRate`` as published, a window at a
time in f64 (a copy of ``chip_smoke.py``'s, PR 21, the scrape interval an
argument).

``extrapolatedRate`` compares a duration with 1.1 average intervals. With
integer counters on a regular scrape that comparison is often an exact tie,
which f64, f32 on a CPU and f32 on a TPU each round their own way; the
extension it decides is worth ~10% of that one series' rate. ``bounds``
therefore evaluates both outcomes of any comparison within ``TIE_BAND`` of
its threshold, and an answer between the two is accepted."""

from __future__ import annotations

import numpy as np

TIE_BAND = 1e-4


def rate(ref, ts, vals, steps_ms, window_ms, interval_ms, nudge=0.0):
    """Counter resets added back, extrapolated to the window's edges unless
    the first or last sample is further than 1.1 average intervals from the
    edge, and never below a zero crossing. ts int64 ms [N, S], vals f64
    [N, S] → f64 [N, K], NaN where a window holds fewer than two samples.
    ``nudge`` moves the 1.1-interval threshold by that relative amount."""
    out = np.full((ts.shape[0], len(steps_ms)), np.nan)
    rows = np.arange(ts.shape[0])
    for k, t, cols, m in ref.windows(ts, steps_ms, window_ms, interval_ms):
        if cols.stop - cols.start < 2:
            continue
        tsb, vb = ts[:, cols], vals[:, cols]
        n = m.sum(1)
        i0 = m.argmax(1)
        i1 = m.shape[1] - 1 - m[:, ::-1].argmax(1)
        pair = m[:, 1:] & m[:, :-1]
        drop = pair & (vb[:, 1:] < vb[:, :-1])
        # sums keep the samples' type: f64, or the control's lower one
        inc = vb[rows, i1] - vb[rows, i0] + np.where(
            drop, vb[:, :-1], vb.dtype.type(0)).sum(1)
        t_first = tsb[rows, i0] / 1000.0
        t_last = tsb[rows, i1] / 1000.0
        sampled = t_last - t_first
        with np.errstate(divide="ignore", invalid="ignore"):
            avg = sampled / (n - 1)
            d_start = t_first - (t - window_ms) / 1000.0
            d_end = t / 1000.0 - t_last
            to_zero = np.where(inc > 0, sampled * vb[rows, i0] / inc, np.inf)
            d_start = np.minimum(d_start, to_zero)
            limit = avg * 1.1 * (1.0 + nudge)
            extend = sampled + np.where(d_start < limit, d_start, avg / 2) \
                + np.where(d_end < limit, d_end, avg / 2)
            r = inc * (extend / sampled) / (window_ms / 1000.0)
        out[:, k] = np.where(n >= 2, r, np.nan)
    return out


def bounds(ref, ts, vals, steps_ms, window_ms, interval_ms):
    """(low, high) per series: the rate with every near-tie at the
    extrapolation threshold decided one way, and the other."""
    a = rate(ref, ts, vals, steps_ms, window_ms, interval_ms, -TIE_BAND)
    b = rate(ref, ts, vals, steps_ms, window_ms, interval_ms, +TIE_BAND)
    return np.minimum(a, b), np.maximum(a, b)
