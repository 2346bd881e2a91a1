"""The plain reference: float64, one window at a time, every sample of the
window looked at (no prefix sums, no binary search, no batching across
steps). Imports nothing of ``filodb_tpu``: the program may change, the
yardstick may not.

``ref_rate``, ``ref_max_over_time``, ``ref_group``, the ``TIE_BAND`` rule and
``assert_between`` are copies of ``chip_smoke.py``'s (PR 21), with the scrape
interval an argument and ``avg`` added. ``check_panel`` holds one Prom JSON
answer to the reference, driven by the panel's ``check`` block in its cell
file.
"""

from __future__ import annotations

import numpy as np

class Mismatch(AssertionError):
    """An answer that is not the reference's. Raised, never ``assert``ed:
    the verdict must not depend on ``python -O``."""


def require(ok, *what) -> None:
    if not ok:
        raise Mismatch(" ".join(str(w) for w in what))


# ``extrapolatedRate`` compares a duration with 1.1 average intervals. With
# integer counters on a regular scrape that comparison is often an exact tie,
# which f64, f32 on a CPU and f32 on a TPU each round their own way; the
# extension it decides is worth ~10% of that one series' rate. The reference
# therefore evaluates both outcomes of any comparison within this relative
# band of its threshold and accepts an answer between the two.
TIE_BAND = 1e-4


def _window_columns(ts, t, window_ms, interval_ms):
    """Column slice that holds the window (t-w, t] of every series, given
    that sample j of each series lies in [first + j*interval, +interval)."""
    first = int(ts[:, 0].min())
    c0 = max((t - window_ms - first) // interval_ms - 1, 0)
    c1 = min((t - first) // interval_ms + 2, ts.shape[1])
    return int(c0), int(max(c1, c0))


def ref_rate(ts, vals, steps_ms, window_ms, interval_ms, nudge=0.0):
    """Prometheus ``rate`` as published (``extrapolatedRate``): counter
    resets added back, extrapolated to the window's edges unless the first
    or last sample is further than 1.1 average intervals from the edge, and
    never below a zero crossing. ts int64 ms [N, S], vals f64 [N, S] →
    f64 [N, K], NaN where a window holds fewer than two samples. ``nudge``
    moves the 1.1-interval threshold by that relative amount."""
    n_series = ts.shape[0]
    out = np.full((n_series, len(steps_ms)), np.nan)
    rows = np.arange(n_series)
    for k, t in enumerate(steps_ms):
        c0, c1 = _window_columns(ts, int(t), window_ms, interval_ms)
        if c1 - c0 < 2:
            continue
        tsb, vb = ts[:, c0:c1], vals[:, c0:c1]
        m = (tsb > t - window_ms) & (tsb <= t)
        n = m.sum(1)
        i0 = m.argmax(1)
        i1 = m.shape[1] - 1 - m[:, ::-1].argmax(1)
        pair = m[:, 1:] & m[:, :-1]
        drop = pair & (vb[:, 1:] < vb[:, :-1])
        inc = vb[rows, i1] - vb[rows, i0] + np.where(drop, vb[:, :-1],
                                                     0.0).sum(1)
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


def _ref_window(ts, vals, steps_ms, window_ms, interval_ms, how):
    out = np.full((ts.shape[0], len(steps_ms)), np.nan)
    for k, t in enumerate(steps_ms):
        c0, c1 = _window_columns(ts, int(t), window_ms, interval_ms)
        if c1 <= c0:
            continue
        tsb = ts[:, c0:c1]
        m = (tsb > t - window_ms) & (tsb <= t)
        if how == "max":
            v = np.where(m, vals[:, c0:c1], -np.inf).max(1)
        else:
            with np.errstate(invalid="ignore", divide="ignore"):
                v = np.where(m, vals[:, c0:c1], 0.0).sum(1) / m.sum(1)
        out[:, k] = np.where(m.any(1), v, np.nan)
    return out


def ref_max_over_time(ts, vals, steps_ms, window_ms, interval_ms):
    return _ref_window(ts, vals, steps_ms, window_ms, interval_ms, "max")


def ref_avg_over_time(ts, vals, steps_ms, window_ms, interval_ms):
    return _ref_window(ts, vals, steps_ms, window_ms, interval_ms, "avg")


def ref_rate_bounds(ts, vals, steps_ms, window_ms, interval_ms):
    """(low, high) per series: the rate with every near-tie at the
    extrapolation threshold decided one way, and the other."""
    a = ref_rate(ts, vals, steps_ms, window_ms, interval_ms, -TIE_BAND)
    b = ref_rate(ts, vals, steps_ms, window_ms, interval_ms, +TIE_BAND)
    return np.minimum(a, b), np.maximum(a, b)


def ref_group(per_series, gids, n_groups, how):
    """sum/max/avg by group, ignoring absent (NaN) series; NaN for a group
    with no series present at that step."""
    out = np.full((n_groups, per_series.shape[1]), np.nan)
    for g in range(n_groups):
        rows = per_series[gids == g]
        if not len(rows):
            continue
        present = ~np.isnan(rows)
        if how == "max":
            agg = np.where(present, rows, -np.inf).max(0)
        else:
            agg = np.where(present, rows, 0.0).sum(0)
            if how == "avg":
                with np.errstate(invalid="ignore", divide="ignore"):
                    agg = agg / present.sum(0)
        out[g] = np.where(present.any(0), agg, np.nan)
    return out


def assert_between(got, lo, hi, rtol, what="") -> float:
    """Every cell of ``got`` within ``rtol`` of the interval [lo, hi], gaps
    where the reference has gaps. Returns the worst relative distance from
    the interval (0 inside it): how close the device came."""
    require(got.shape == lo.shape, f"{what}: shape {got.shape} != {lo.shape}")
    require((np.isnan(got) == np.isnan(lo)).all(), f"{what}: gaps differ")
    with np.errstate(invalid="ignore", divide="ignore"):
        off = np.maximum(np.maximum(lo - got, got - hi), 0.0) \
            / np.maximum(np.abs(lo), np.abs(hi))
    off = np.nan_to_num(off, nan=0.0)
    bad = off > rtol
    if bad.any():
        raise Mismatch(
            f"{what}: {int(bad.sum())} of {bad.size} cells outside the "
            f"reference, worst {off.max():.3g} relative, first at "
            f"{tuple(np.argwhere(bad)[0])}: got {got[bad][0]!r}, want "
            f"[{lo[bad][0]!r}, {hi[bad][0]!r}]")
    return float(off.max()) if off.size else 0.0


# ---------------------------------------------------------------------------
# one Prom JSON answer against the reference

_WINDOW_FNS = {"max_over_time": ref_max_over_time,
               "avg_over_time": ref_avg_over_time}


def _matrix(body: dict, steps_ms, by: str | None):
    """Prom matrix JSON → ({group label value: row index}, f64 [R, K]) with
    NaN where a row shows no sample at a step."""
    require(body.get("status") == "success", body.get("error", body))
    require(not body.get("partial"), "partial answer")
    rows = body["data"]["result"]
    col = {int(t): k for k, t in enumerate(steps_ms)}
    got = np.full((len(rows), len(steps_ms)), np.nan)
    names = {}
    for i, row in enumerate(rows):
        name = row["metric"].get(by, "") if by else ""
        require(name not in names, f"group {name!r} answered twice")
        names[name] = i
        for t, v in row["values"]:
            k = col.get(int(round(float(t) * 1000)))
            require(k is not None, f"a sample at {t}, which is no step")
            got[i, k] = float(v)
    return names, got


def check_panel(check: dict, metrics: dict, interval_ms: int, key,
                start_s: int, end_s: int, step_s: int, body: dict,
                rng) -> dict:
    """Hold one ``query_range`` answer to the reference. ``check`` is the
    panel's block in its cell file: ``metric``, ``select`` (label → value
    template with ``{key}``), ``fn`` and ``window_s``, ``agg`` with ``by``
    (a label, or null for one group), optionally ``topk`` and
    ``sample_groups`` (hold that many seeded groups to the reference, and
    only count the rest). Raises ``Mismatch`` on any difference."""
    m = metrics[check["metric"]]
    keep = np.ones(len(m["ts"]), bool)
    for label, template in check.get("select", {}).items():
        keep &= m["labels"][label] == template.format(key=key)
    by = check.get("by")
    group_of = m["labels"][by][keep] if by else np.zeros(keep.sum(), "U1")
    names_all = np.unique(group_of)
    steps_ms = np.arange(start_s, end_s + 1, step_s, dtype=np.int64) * 1000
    names, got = _matrix(body, steps_ms, by)
    n_sample = check.get("sample_groups")
    if n_sample and n_sample < len(names_all):
        chosen = rng.choice(names_all, n_sample, replace=False)
    else:
        chosen = names_all
    pick = np.isin(group_of, chosen)
    ts, vals = m["ts"][keep][pick], m["vals"][keep][pick]
    chosen = np.unique(group_of[pick])
    gids = np.searchsorted(chosen, group_of[pick])
    window_ms = int(check["window_s"]) * 1000
    if check["fn"] == "rate":
        lo, hi = ref_rate_bounds(ts, vals, steps_ms, window_ms, interval_ms)
    else:
        lo = hi = _WINDOW_FNS[check["fn"]](ts, vals, steps_ms, window_ms,
                                           interval_ms)
    same = hi is lo
    lo = ref_group(lo, gids, len(chosen), check["agg"])
    hi = lo if same else ref_group(hi, gids, len(chosen), check["agg"])
    rtol = float(check["rtol"])
    what = f"{check['fn']} key={key} end={end_s}"
    k = check.get("topk")
    if not k:
        shown = ~np.isnan(lo).all(1)  # Prom drops a row with no sample
        want = int(shown.sum()) if len(chosen) == len(names_all) \
            else len(names_all)
        require(len(names) == want,
                f"{what}: {len(names)} groups answered, {want} exist")
        rows = [names.get(str(c), -1) for c in chosen[shown]]
        require(min(rows, default=0) >= 0, f"{what}: a group is missing")
        worst = assert_between(got[rows], lo[shown], hi[shown], rtol, what)
        return {"groups_answered": len(names), "groups_checked": len(rows),
                "worst_rel_error": worst}
    # top k of the groups at each step: a row is shown only at its steps
    order = {str(c): g for g, c in enumerate(chosen)}
    require(set(names) <= set(order), f"{what}: unknown groups in the answer")
    ref_row = np.array([order[n] for n in names], np.int64)
    cells = 0
    for j in range(len(steps_ms)):
        ranked = np.sort(lo[~np.isnan(lo[:, j]), j])[::-1]
        shown = ref_row[~np.isnan(got[:, j])]
        require(len(shown) == min(k, len(ranked)), what, "step", j, "shows",
                len(shown))
        for g in shown:
            # inside the top k, up to a near-tie that f32 cannot order
            require(hi[g, j] >= ranked[len(shown) - 1] * (1 - rtol), what,
                    "step", j, "shows group", int(g), hi[g, j], "of",
                    ranked[:k + 1])
        cells += len(shown)
    cell = ~np.isnan(got)
    worst = assert_between(np.where(cell, got, 0.0),
                           np.where(cell, lo[ref_row], 0.0),
                           np.where(cell, hi[ref_row], 0.0), rtol, what)
    return {"rows_shown": len(names), "cells_checked": cells,
            "worst_rel_error": worst}
