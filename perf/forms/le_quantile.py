"""``le_quantile``, the ``post`` step ``{"fn": "le_quantile", "q": phi}``:
``histogram_quantile(phi, sum(rate(x_bucket[w])) by (le))`` over classic
Prometheus bucket series, each bucket a counter series with an ``le``
label. The groups of ``by: "le"`` are the buckets, each group's name its
bound; the answer is ONE series without ``le``. Prometheus's definition in
f64, and the band an answer is held to. ``bucket_quantile``, ``quantile``
and ``quantile_band`` are copies of ``forms/histogram_quantile.py``'s: a
form loads nothing beside it."""

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
    ``rtol`` of [lo, hi] (f64 [..., B]), and the width of the bucket the
    rank falls in. The quantile never falls when the rank rises and never
    rises when a count rises, so its least value is at the lowest rank with
    the highest counts and its greatest at the highest rank with the lowest
    counts: two evaluations (``forms/histogram_quantile.py`` says why at
    length)."""
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


def monotonic(rows):
    """Bucket rows f64 [B, ...] in bound order made cumulative-monotonic as
    Prometheus's ``ensureMonotonic`` makes them: a running max along the
    bounds, a missing bucket read as 0. NaN stays where no bucket has a
    sample. A running max keeps order, so the tie band's lo and hi stay
    its bounds."""
    gap = np.isnan(rows).all(0)
    out = np.maximum.accumulate(np.nan_to_num(rows, nan=0.0), axis=0)
    out[..., gap] = np.nan
    return out


def buckets(ref, names, lo, hi) -> tuple:
    """(les f64 [B], lo, hi f64 [B, K]): the ``le`` groups read by their
    names as bounds, in bound order, each made monotonic."""
    les = np.array([float(n) for n in names])     # "+Inf" reads as inf
    order = np.argsort(les, kind="stable")
    les = les[order]
    ref.require(len(les) > 1 and np.isposinf(les[-1]),
                f"no +Inf bucket among {len(les)} le groups")
    ref.require((np.diff(les) > 0).all(), "two le groups name one bound")
    return les, monotonic(lo[order]), monotonic(hi[order])


def answer(ref, check, rows, les):
    """What the step makes of one histogram's bucket rows f64 [B, K] in
    bound order: f64 [K]. The ``le`` groups are the buckets, so the rows
    are every group's, ordered by ``buckets`` — not one group's row, which
    is all ``reference.answer_body`` hands a form."""
    if les is None or np.ndim(rows) != 2:
        raise ValueError("le_quantile answers from all the le groups' rows "
                         "in bound order and their bounds (buckets())")
    return quantile(float(check["post"]["q"]), monotonic(rows).T, les)


def compare(ref, check, body, steps_ms, lo, hi, groups, what):
    """Over the ``le`` groups' rates lo, hi [B, K] (in the order of their
    names): the one answered series, which carries no ``le``, inside
    ``quantile_band`` at every step, widened by ``rtol`` of the bucket's
    width; NaN where the total is 0 or absent. The error compared is the
    distance outside the band in bucket widths; ``center_error_widths`` is
    the distance from the f64 quantile of the band's middle rates, for the
    record."""
    chosen, n_all, _ = groups
    ref.require(check.get("by") == "le", f"{what}: le_quantile groups by le")
    ref.require(len(chosen) == n_all, f"{what}: sample_groups under a quantile")
    les, lo, hi = buckets(ref, chosen, lo, hi)
    rtol, phi = float(check["rtol"]), float(check["post"]["q"])
    low, high, width = (a[0] for a in quantile_band(
        phi, lo.T[None], hi.T[None], les, rtol))
    names, got = ref.matrix(body, steps_ms, None)
    shown = not np.isnan(low).all()
    ref.require(len(names) == int(shown), f"{what}: {len(names)} series "
                f"answered, {int(shown)} exist")
    ref.require(all("le" not in r["metric"] for r in body["data"]["result"]),
                f"{what}: an answered series keeps its le label")
    got = got[0] if shown else np.full_like(low, np.nan)
    ref.require((np.isnan(got) == np.isnan(low)).all(), f"{what}: gaps differ")
    with np.errstate(invalid="ignore"):
        off = np.nan_to_num(np.maximum(np.maximum(
            low - got, got - high), 0.0) / width)
        bad = off > rtol
    if bad.any():
        (at,) = np.argwhere(bad)[0]
        raise ref.Mismatch(
            f"{what}: {int(bad.sum())} of {bad.size} quantiles outside the "
            f"reference's band, worst {off.max():.3g} bucket widths, first "
            f"at step {at}: got {got[at]!r}, want [{low[at]!r}, "
            f"{high[at]!r}] in a bucket {width[at]!r} wide")
    center = quantile(phi, ((lo + hi) / 2).T, les)
    return {"buckets_read": len(les), "cells_checked": int(
                (~np.isnan(got)).sum()),
            "worst_rel_error": float(off.max()) if off.size else 0.0,
            "center_error_widths": float(np.nanmax(
                np.abs(got - center) / width, initial=0)),
            "band_widths": float(np.nanmax((high - low) / width, initial=0))}
