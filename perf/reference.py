"""The plain reference: float64, one window at a time, every sample of the
window looked at (no prefix sums, no binary search, no batching across
steps). Imports nothing of ``filodb_tpu``: the program may change, the
yardstick may not.

``check_panel`` holds one Prom JSON answer to the reference, driven by the
panel's ``check`` block in its cell file. The block's ``fn`` (a window
function) and ``post.fn`` (a step over the aggregate) each name a file
under ``perf/forms/``, found by that name as a generator or a per-layer
reader is: a panel with another function brings its form as a new file and
edits nothing here. ``agg`` (``ref_group``), ``topk`` and the bucket matrix
of a histogram are this module's. ``ref_group``, ``assert_between`` and,
in ``forms/rate.py``, the tie band are copies of ``chip_smoke.py``'s (PR
21), with ``avg`` added. A metric's ``vals`` is f64 ``[N, S]``, or a mapping
of columns of which one is a histogram ``{"les": f64 [B], "counts": int64
[N, S, B]}`` (PR 36).
"""

from __future__ import annotations

import importlib.util
import os
import types

import numpy as np

FORMS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "forms")
_forms: dict = {}


def load_form(name: str):
    """``perf/forms/<name>.py``. A window function's form has
    ``bounds(ref, ts, vals, steps_ms, window_ms, interval_ms)`` → (low,
    high) f64 [N, K] per series, the same array twice where nothing is
    decided two ways. A ``post`` step's form has ``compare(ref, check,
    body, steps_ms, lo, hi, groups, what)`` → the numbers compared, raising
    ``Mismatch``, and ``answer(ref, check, rows, les)`` → f64 [K] of one
    group's rows. ``ref`` is ``LENT``, what this module lends a form:
    handed over and not imported, since ``perf/`` is loaded by path under
    more than one name and a second copy would raise another ``Mismatch``
    than the caller catches."""
    if name not in _forms:
        path = os.path.join(FORMS, f"{name}.py")
        if not os.path.isfile(path):
            raise KeyError(f"no reference form {name!r}: a check block's "
                           f"`fn` or `post.fn` names a file under {FORMS}")
        spec = importlib.util.spec_from_file_location(
            f"perf_forms_{name}", path)
        _forms[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(_forms[name])
    return _forms[name]


class Mismatch(AssertionError):
    """An answer that is not the reference's. Raised, never ``assert``ed:
    the verdict must not depend on ``python -O``."""


def require(ok, *what) -> None:
    if not ok:
        raise Mismatch(" ".join(str(w) for w in what))


def _window_columns(ts, t, window_ms, interval_ms):
    """Column slice that holds the window (t-w, t] of every series, given
    that sample j of each series lies in [first + j*interval, +interval)."""
    first = int(ts[:, 0].min())
    c0 = max((t - window_ms - first) // interval_ms - 1, 0)
    c1 = min((t - first) // interval_ms + 2, ts.shape[1])
    return int(c0), int(max(c1, c0))


def windows(ts, steps_ms, window_ms, interval_ms):
    """(k, t, column slice, mask [N, columns]) of each step t whose slice
    is not empty: the mask says which of the slice's samples lie in the
    window (t-w, t]. What a window function's form walks."""
    for k, t in enumerate(steps_ms):
        c0, c1 = _window_columns(ts, int(t), window_ms, interval_ms)
        if c1 > c0:
            tsb = ts[:, c0:c1]
            yield k, int(t), slice(c0, c1), (tsb > t - window_ms) & (tsb <= t)


def ref_group(per_series, gids, n_groups, how):
    """sum/max/avg by group, ignoring absent (NaN) series; NaN for a group
    with no series present at that step."""
    out = np.full((n_groups, per_series.shape[1]), np.nan)
    for g in range(n_groups):
        rows = per_series[gids == g]
        if not len(rows):
            continue
        present = ~np.isnan(rows)
        kind = rows.dtype.type
        if how == "max":
            agg = np.where(present, rows, kind(-np.inf)).max(0)
        else:
            agg = np.where(present, rows, kind(0)).sum(0)
            if how == "avg":
                with np.errstate(invalid="ignore", divide="ignore"):
                    agg = agg / present.sum(0).astype(kind)
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

def fmt_le(le: float) -> str:
    """A bucket's bound as the program's Prom JSON labels its row
    (``http/promjson.py:_fmt``): ``repr`` of the f64, ``+Inf``."""
    return "+Inf" if np.isposinf(le) else repr(float(le))


def matrix(body: dict, steps_ms, by: str | None):
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


# what a form may use of this module (``load_form``)
LENT = types.SimpleNamespace(
    Mismatch=Mismatch, require=require, windows=windows, matrix=matrix,
    assert_between=assert_between, fmt_le=fmt_le, ref_group=ref_group)


def _compare_groups(check, body, steps_ms, lo, hi, groups, what):
    """One row a group, every cell within ``rtol`` of [lo, hi]. ``groups``
    is ``evaluate``'s."""
    chosen, n_all, _ = groups
    names, got = matrix(body, steps_ms, check.get("by"))
    shown = ~np.isnan(lo).all(1)  # Prom drops a row with no sample
    want = int(shown.sum()) if len(chosen) == n_all else n_all
    require(len(names) == want,
            f"{what}: {len(names)} groups answered, {want} exist")
    rows = [names.get(str(c), -1) for c in chosen[shown]]
    require(min(rows, default=0) >= 0, f"{what}: a group is missing")
    worst = assert_between(got[rows], lo[shown], hi[shown],
                           float(check["rtol"]), what)
    return {"groups_answered": len(names), "groups_checked": len(rows),
            "worst_rel_error": worst}


def _compare_topk(check, body, steps_ms, lo, hi, groups, what):
    """Top k of the groups at each step: a row is shown only at its
    steps."""
    chosen = groups[0]
    k, rtol = check["topk"], float(check["rtol"])
    names, got = matrix(body, steps_ms, check.get("by"))
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


def _compare_buckets(check, body, steps_ms, lo, hi, groups, what):
    """A histogram answer with no ``post``: the bucket matrix of the one
    group, a row a bucket, read by its ``le`` label. lo, hi [1, B, K]."""
    require(lo.shape[0] == 1 and not check.get("by"),
            f"{what}: a bucket matrix is compared for one group only")
    les = groups[2]
    names, got = matrix(body, steps_ms, "le")
    want = [fmt_le(le) for le in les]
    missing = [n for n in want if n not in names]
    require(not missing and len(names) == len(want),
            f"{what}: {len(names)} le rows answered of {len(want)}; "
            f"missing {missing[:4]}")
    worst = assert_between(got[[names[n] for n in want]], lo[0], hi[0],
                           float(check["rtol"]), what)
    return {"buckets_answered": len(names), "worst_rel_error": worst}


def _comparison(check: dict, histogram: bool):
    if check.get("post"):
        form = load_form(check["post"]["fn"])
        return lambda *args: form.compare(LENT, *args)
    if check.get("topk"):
        return _compare_topk
    return _compare_buckets if histogram else _compare_groups


def _value_column(vals):
    """(f64 [N, S], None), or of a mapping its histogram (int64 [N, S, B],
    les)."""
    if isinstance(vals, dict):
        h = next(c for c in vals.values() if isinstance(c, dict))
        return h["counts"], np.asarray(h["les"], np.float64)
    return vals, None


def evaluate(check: dict, metrics: dict, interval_ms: int, key,
             start_s: int, end_s: int, step_s: int, rng, cast=None) -> tuple:
    """The reference's own answer to a panel: (steps_ms, lo, hi, groups).
    lo and hi are f64 [G, K] a group — [G, B, K] for a histogram column —
    the same array where nothing is decided two ways; ``groups`` is (the
    groups evaluated, how many exist, a histogram's les or None). ``cast``
    is applied to the samples first: the control evaluates in a lower
    precision."""
    m = metrics[check["metric"]]
    keep = np.ones(len(m["ts"]), bool)
    for label, template in check.get("select", {}).items():
        keep &= m["labels"][label] == template.format(key=key)
    by = check.get("by")
    group_of = m["labels"][by][keep] if by else np.zeros(keep.sum(), "U1")
    names_all = np.unique(group_of)
    steps_ms = np.arange(start_s, end_s + 1, step_s, dtype=np.int64) * 1000
    n_sample = check.get("sample_groups")
    if n_sample and n_sample < len(names_all):
        chosen = rng.choice(names_all, n_sample, replace=False)
    else:
        chosen = names_all
    pick = np.isin(group_of, chosen)
    vals, les = _value_column(m["vals"])
    ts, vals = m["ts"][keep][pick], vals[keep][pick]
    chosen = np.unique(group_of[pick])
    gids = np.searchsorted(chosen, group_of[pick])
    buckets = 1 if les is None else len(les)
    if les is not None:
        # a row a (series, bucket); group ids g·B + b
        vals = vals.transpose(0, 2, 1).reshape(-1, vals.shape[1]) \
            .astype(np.float64)
        ts = np.repeat(ts, buckets, axis=0)
        gids = (gids[:, None] * buckets + np.arange(buckets)).ravel()
    if cast is not None:
        vals = cast(vals)
    lo, hi = load_form(check["fn"]).bounds(
        LENT, ts, vals, steps_ms, int(check["window_s"]) * 1000, interval_ms)
    same = hi is lo
    lo = ref_group(lo, gids, len(chosen) * buckets, check["agg"])
    hi = lo if same else ref_group(hi, gids, len(chosen) * buckets,
                                   check["agg"])
    if les is not None:
        lo = lo.reshape(len(chosen), buckets, -1)
        hi = hi.reshape(len(chosen), buckets, -1)
    return steps_ms, lo, hi, (chosen, len(names_all), les)


def answer_body(check: dict, steps_ms, rows, groups) -> dict:
    """Prom matrix JSON of ``rows`` (a ``lo`` or ``hi`` of ``evaluate``) as
    the program would answer the panel — the reference put in the program's
    place: what a control, and a test of a comparison, starts from."""
    by = check.get("by")
    labelled = [({by: str(c)} if by else {}, r)
                for c, r in zip(groups[0], rows)]
    if check.get("post"):
        les = groups[2]
        answer = load_form(check["post"]["fn"]).answer
        labelled = [(lab, answer(LENT, check, r, les)) for lab, r in labelled]
    elif groups[2] is not None:
        labelled = [({**lab, "le": fmt_le(le)}, r[b])
                    for lab, r in labelled for b, le in enumerate(groups[2])]
    elif check.get("topk"):
        # exactly k a step: a tie at the k-th place goes to the earlier row
        table = np.stack([r for _, r in labelled])
        rank = np.argsort(np.argsort(-np.nan_to_num(table, nan=-np.inf),
                                     axis=0, kind="stable"), axis=0)
        labelled = [(lab, np.where(rank[g] < check["topk"], r, np.nan))
                    for g, (lab, r) in enumerate(labelled)]
    return {"status": "success", "data": {"resultType": "matrix", "result": [
        {"metric": lab, "values": [[t / 1000.0, repr(float(v))]
                                   for t, v in zip(steps_ms, r)
                                   if not np.isnan(v)]}
        for lab, r in labelled if not np.isnan(r).all()]}}


def check_panel(check: dict, metrics: dict, interval_ms: int, key,
                start_s: int, end_s: int, step_s: int, body: dict,
                rng) -> dict:
    """Hold one ``query_range`` answer to the reference. ``check`` is the
    panel's block in its cell file: ``metric``, ``select`` (label → value
    template with ``{key}``), ``fn`` (a form) and ``window_s``, ``agg``
    (``sum``, ``max``, ``avg``) with ``by`` (a label, or null for one
    group), ``rtol``, and optionally ``topk``, ``post`` (its ``fn`` a form)
    and ``sample_groups`` (hold that many seeded groups to the reference,
    and only count the rest). A histogram column is evaluated a bucket: the
    ``[N·B, S]`` view of its cumulative counts under the same ``fn`` (for
    ``rate``: counter semantics and the extrapolation a bucket), ``agg`` a
    bucket over the group; the answer is the bucket matrix, rows read by
    ``le``, or what ``post`` makes of it. Raises ``Mismatch`` on any
    difference."""
    steps_ms, lo, hi, groups = evaluate(
        check, metrics, interval_ms, key, start_s, end_s, step_s, rng)
    what = f"{check['fn']} key={key} end={end_s}"
    return _comparison(check, groups[2] is not None)(
        check, body, steps_ms, lo, hi, groups, what)
