"""``histogram_quantile``, the ``post`` step ``{"fn": "histogram_quantile",
"q": phi}``: Prometheus's definition on cumulative buckets, in f64, and the
band an answer is held to."""

from __future__ import annotations

import numpy as np


def bucket_quantile(rank: float, cum, les) -> tuple:
    """Prometheus's ``bucketQuantile`` with the rank given apart from the
    counts: the first bucket whose cumulative count reaches the rank, linear
    interpolation inside it from its lower bound (0 under the first), the
    highest finite ``le`` where the rank falls in ``+Inf``. ``cum`` f64 [B]
    cumulative, ``les`` f64 [B] ending in +Inf → (value, bucket)."""
    last = len(les) - 1
    b = next((i for i in range(last) if cum[i] >= rank), last)
    if b == last:
        return float(les[last - 1]), last
    if b == 0 and les[0] <= 0:
        return float(les[0]), 0
    start, below = (float(les[b - 1]), cum[b - 1]) if b else (0.0, 0.0)
    return start + (float(les[b]) - start) * (rank - below) \
        / (cum[b] - below), b


def quantile(phi: float, cum, les):
    """``histogram_quantile(phi, ·)`` of cumulative buckets f64 [..., B] →
    f64 [...]: rank phi·total; NaN where the total is 0 or absent."""
    out = np.full(cum.shape[:-1], np.nan)
    for at in np.ndindex(*out.shape):
        total = cum[at][-1]
        if total > 0:
            out[at] = bucket_quantile(phi * total, cum[at], les)[0]
    return out


def quantile_band(phi: float, lo, hi, les, rtol: float) -> tuple:
    """(low, high, width), each f64 [...]: where ``histogram_quantile`` may
    lie when every cumulative bucket rate is only known to lie within
    ``rtol`` of [lo, hi] (f64 [..., B]: the tie band of ``rate``'s
    ``bounds``, summed over a group), and the width of the bucket the rank
    falls in.

    Why a band and no tolerance on the value: the quantile is
    ``start + width·(phi·T − C[b-1]) / (C[b] − C[b-1])``, and a relative
    error e on the counts C and the total T moves the fraction by up to
    ``e·(phi·T + C[b-1]) / (C[b] − C[b-1])``: thousands of times e where the
    bucket holds a thousandth of the total, and a whole bucket where the
    rank sits on a bucket's edge. But ``bucketQuantile(rank, C)`` never
    falls when the rank rises and never rises when any C[i] rises (more
    mass below means a lower quantile). So over every C within the error,
    its least value is at the lowest rank with the highest counts, and its
    greatest at the highest rank with the lowest counts: two evaluations of
    the plain function, exact at the discontinuities too. The interpolation
    itself is then rounded in the program's precision, an error of a few
    ulps of a bucket's bound: the caller widens the band by ``rtol`` of
    ``width`` for it, as the bucket rates are given ``rtol`` of themselves.
    An answer a bucket off lies a width outside this, unless the bucket
    holds less than ~4·``rtol`` of the total, where the band says so."""
    shape = lo.shape[:-1]
    low, high, width = (np.full(shape, np.nan) for _ in range(3))
    last = len(les) - 1
    for at in np.ndindex(*shape):
        t_lo, t_hi = lo[at][-1], hi[at][-1]
        if not t_hi > 0:          # no samples (NaN) or no increase: NaN
            continue
        low[at], _ = bucket_quantile(
            phi * t_lo * (1 - rtol), hi[at] * (1 + rtol), les)
        high[at], _ = bucket_quantile(
            phi * t_hi * (1 + rtol), lo[at] * (1 - rtol), les)
        b = min(bucket_quantile(phi * t_lo, lo[at], les)[1], last - 1)
        width[at] = les[b] - (les[b - 1] if b else 0.0)
    return low, high, width


def answer(ref, check, rows, les):
    """What the step makes of one group's bucket rows f64 [B, K]: f64 [K]."""
    return quantile(float(check["post"]["q"]), rows.T, les)


def compare(ref, check, body, steps_ms, lo, hi, groups, what):
    """Over the groups' bucket rates lo, hi [G, B, K]: every answered cell
    inside ``quantile_band``, widened by ``rtol`` of the bucket's width; NaN
    where the total is 0 or absent. The error compared is the distance
    outside the band in bucket widths; ``center_error_widths`` is the
    distance from the f64 quantile of the band's middle rates, for the
    record."""
    chosen, n_all, les = groups
    ref.require(len(chosen) == n_all, f"{what}: sample_groups under a quantile")
    rtol, phi = float(check["rtol"]), float(check["post"]["q"])
    low, high, width = quantile_band(
        phi, lo.transpose(0, 2, 1), hi.transpose(0, 2, 1), les, rtol)
    names, got = ref.matrix(body, steps_ms, check.get("by"))
    shown = ~np.isnan(low).all(1)
    ref.require(len(names) == int(shown.sum()), f"{what}: {len(names)} "
                f"groups answered, {int(shown.sum())} exist")
    rows = [names.get(str(c), -1) for c in chosen[shown]]
    ref.require(min(rows, default=0) >= 0, f"{what}: a group is missing")
    got = got[rows]
    ref.require((np.isnan(got) == np.isnan(low[shown])).all(),
                f"{what}: gaps differ")
    with np.errstate(invalid="ignore"):
        off = np.nan_to_num(np.maximum(np.maximum(
            low[shown] - got, got - high[shown]), 0.0) / width[shown])
        bad = off > rtol
    if bad.any():
        at = tuple(np.argwhere(bad)[0])
        raise ref.Mismatch(
            f"{what}: {int(bad.sum())} of {bad.size} quantiles outside the "
            f"reference's band, worst {off.max():.3g} bucket widths, first "
            f"at {at}: got {got[at]!r}, want [{low[shown][at]!r}, "
            f"{high[shown][at]!r}] in a bucket {width[shown][at]!r} wide")
    center = quantile(phi, (lo + hi).transpose(0, 2, 1) / 2, les)
    return {"groups_answered": len(names), "cells_checked": int(
                (~np.isnan(got)).sum()),
            "worst_rel_error": float(off.max()) if off.size else 0.0,
            "center_error_widths": float(np.nanmax(
                np.abs(got - center[shown]) / width[shown], initial=0)),
            "band_widths": float(np.nanmax((high - low) / width, initial=0))}
