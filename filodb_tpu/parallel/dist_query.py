"""Sharded query execution: sum(rate(...)) by (...) over a (shard, time) mesh.

The flagship distributed kernel: evaluates a counter-corrected, extrapolated
Prometheus ``rate`` over series sharded across the ``shard`` mesh axis AND
samples sharded across the ``time`` mesh axis, then reduces label groups with
``segment_sum`` + ``psum``.

Why this shape: the reference scales queries by (a) scattering per-shard
subtrees to nodes and gathering partial aggregates (``ExecPlan``/
``ActorPlanDispatcher``) and (b) splitting long time ranges into sequential
sub-plans (``SingleClusterPlanner.materializeTimeSplitPlan``,
``StitchRvsExec``). On a TPU mesh both axes become dimensions of one SPMD
program: shard-axis reduction is a ``psum`` over ICI, and the time axis is
handled like sequence parallelism — each device computes window partials for
its time block, then per-step summaries (count, first/last sample, internal
counter-corrected increase) are all-gathered over the time axis (tiny
[dt, P, K, 6] tensors) and combined associatively, including counter resets
that straddle block boundaries.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from filodb_tpu.query.engine.kernels import fdtype

# Stable ``jax.named_scope`` names on the bodies of the device programs, so
# that a profile names an operation by what it does and not by the
# compiler's ``while.13``: ``prepare/correct``, ``prepare/prefix``,
# ``bounds/count`` or ``bounds/search`` (the form that was traced),
# ``eval/<fn>``, ``reduce/<agg>``. Metadata only. The masked-scan program
# of window min/max finds its bounds inside ``eval/<fn>``, so there the
# names nest (``eval/max_over_time/bounds/count``).


def bounds_form(mesh: Mesh) -> str:
    """The form ``_window_bounds`` takes in the programs of ``mesh``,
    ``"count"`` or ``"search"``: the ONE place that decides, at trace time,
    from the platform of the mesh's devices. On a TPU a data-dependent
    gather costs ~18 ns an element and a compare a lane-cycle, so the count
    wins at every shape measured on a v5e (``make_mesh_bounds``, ms, search
    → count; PERF.md §6, PR 32): (P, S, K) = (16384, 1024, 32) 215.6 → 1.7;
    (16384, 1024, 256) 947.9 → 7.0; (1024, 1024, 256) 60.5 → 1.6;
    (131072, 1024, 32) 1,753.9 → 7.2; (16384, 8192, 256) 2,072.9 → 49.0 —
    the count grows as K·S and the search as K·log S, and at 42× apart at
    S 8,192 they meet beyond any S a batch is built at, so the shape has
    no say. On the CPU the gather is cheap and the search is ~10× the
    faster (sandbox, CPU only): the tier-1 meshes keep it; so does any
    platform nobody has measured."""
    return "count" if mesh.devices.flat[0].platform == "tpu" else "search"


def _bounds_by_search(ts, steps, window):
    """(lo, hi) by two binary searches a row: ⌈log2(S_l+1)⌉ dependent
    gathers an element."""
    def bounds(tsp):
        hi = jnp.searchsorted(tsp, steps, side="right")
        lo = jnp.searchsorted(tsp, steps - window, side="right")
        return lo, hi

    return jax.vmap(bounds)(ts)


# Window edges counted in one pass over ``ts``. On the chip the compare
# fuses into the row reduce whatever the chunk (0 bytes of temporaries in
# HBM); 8 is as fast as a whole grid in one fusion at S 1,024 and 3.7× the
# faster at S 8,192, and 1 is 4–7× the slower (v5e; PERF.md §6, PR 32). A
# compiler that fuses nothing (the CPU's) holds [8, P_l, S_l] at most.
_COUNT_CHUNK = 8


def _bounds_by_count(ts, steps, window):
    """(lo, hi) by compare-and-count. A row ascends and its padding
    (``TS_PAD``, int32 max) comes last, in every time block, so
    ``#{ts_row <= q}`` IS the ``side="right"`` insertion point of ``q``:
    lane-parallel compares and adds, no gather, no dependent loop. Both
    edges of every window are counted in the same passes over ``ts``,
    ``_COUNT_CHUNK`` of them a pass, so the [P_l, K, S_l] compare of a
    whole grid never exists."""
    k = steps.shape[0]
    q = jnp.concatenate([steps - window, steps])
    c = min(_COUNT_CHUNK, 2 * k)
    q = jnp.pad(q, (0, -(2 * k) % c))

    def count(qc):  # [c] edges -> [c, P_l]: the sample axis reduces away
        return jnp.sum(ts[None, :, :] <= qc[:, None, None], axis=-1,
                       dtype=jnp.int32)

    n = lax.map(count, q.reshape(-1, c)).reshape(-1, ts.shape[0]).T
    return n[:, :k], n[:, k:2 * k]


def _window_bounds(ts, steps, window, mesh: Mesh):
    """int32 [P_l, K] pair (lo, hi): samples ``lo[p, k] .. hi[p, k] - 1`` of
    row ``p`` lie in ``(steps[k] - window, steps[k]]``. Both forms return
    the same integers; ``bounds_form`` picks one for the mesh."""
    form = bounds_form(mesh)
    with jax.named_scope(f"bounds/{form}"):
        return (_bounds_by_count if form == "count"
                else _bounds_by_search)(ts, steps, window)


@jax.named_scope("prepare/correct")
def _counter_correct(v, valid):
    """Block-local counter-reset correction (monotonized values): the
    cumulative sum of every dropped previous value is added back, exactly
    like ``kernels.range_eval`` / ``SeriesBatch.delta_host``. ``v`` must
    already be masked (invalid positions zeroed)."""
    prev = jnp.concatenate([v[:, :1], v[:, :-1]], axis=1)
    both = valid & jnp.concatenate(
        [jnp.zeros_like(valid[:, :1]), valid[:, :-1]], axis=1)
    dropped = (v < prev) & both
    corr = jnp.cumsum(jnp.where(dropped, prev, 0.0), axis=1)
    return v + corr


def _rate_partials_from_bounds(ts, vals, counts_mask, lo, hi, cv=None,
                               raw=None):
    """[P_l, K, 7] rate partials given precomputed window bounds.

    ``cv`` is the (optionally counter-corrected) value tensor; when None
    the masked values are used directly (delta / non-counter semantics).

    ``raw`` [P_l, S_l] is the uncorrected value tensor when ``vals`` are
    the host's pre-corrected/rebased f64 pass (``SeriesBatch.delta_host``,
    placed when the device dtype cannot correct the batch itself); it feeds
    ONLY the ``v_first_raw`` field, whose sole consumer is Prometheus'
    extrapolate-to-zero heuristic. The boundary combine keeps using the
    rebased first/last (a large base would not cancel exactly in f32
    there).
    """
    dt = fdtype()
    valid = counts_mask
    v = jnp.where(valid, vals, 0.0).astype(dt)
    if cv is None:
        cv = v
    n = (hi - lo).astype(jnp.int32)
    has = hi > lo

    def g(x, idx):
        return jnp.take_along_axis(x, idx, axis=1)

    i_first = jnp.minimum(lo, ts.shape[1] - 1)
    i_last = jnp.maximum(hi - 1, 0)
    t_first = jnp.where(has, g(ts, i_first), jnp.int32(2**31 - 1)).astype(dt)
    t_last = jnp.where(has, g(ts, i_last), jnp.int32(-(2**31 - 1))).astype(dt)
    v_first = jnp.where(has, g(v, i_first), 0.0)
    v_last = jnp.where(has, g(v, i_last), 0.0)
    inc = jnp.where(has, g(cv, i_last) - g(cv, i_first), 0.0)
    if raw is None:
        v_first_raw = v_first
    else:
        rawm = jnp.where(valid, raw, 0.0).astype(dt)
        v_first_raw = jnp.where(has, g(rawm, i_first), 0.0)
    return jnp.stack([n.astype(dt), t_first, v_first, t_last, v_last, inc,
                      v_first_raw], axis=-1)


def _combine_time_partials(parts, steps, window, mode: str = "rate",
                           counter: bool = True):
    """Combine all-gathered time-block partials [dt, P, K, 7] → [P, K].

    Sequential associative combine over the (static, small) time axis,
    handling counter resets across block boundaries, then Prometheus
    extrapolation using the global first/last samples. ``mode``: "rate",
    "increase" (extrapolated, not divided by window) or "delta"
    (non-counter increase, extrapolated).
    """
    dtt = fdtype()
    dt_blocks = parts.shape[0]
    n_tot = jnp.sum(parts[..., 0], axis=0)
    t_first_g = jnp.min(parts[..., 1], axis=0)
    t_last_g = jnp.max(parts[..., 3], axis=0)

    total_inc = jnp.zeros_like(parts[0, ..., 5])
    has_prev = jnp.zeros(parts.shape[1:3], bool)
    v_prev = jnp.zeros_like(total_inc)
    v_first_g = jnp.zeros_like(total_inc)
    for d in range(dt_blocks):  # static unroll; dt is the mesh time size
        nd = parts[d, ..., 0] > 0
        vf, vl, inc = parts[d, ..., 2], parts[d, ..., 4], parts[d, ..., 5]
        if counter:
            boundary = jnp.where(
                nd & has_prev,
                jnp.where(vf < v_prev, vf, vf - v_prev), 0.0)
        else:
            boundary = jnp.where(nd & has_prev, vf - v_prev, 0.0)
        total_inc = total_inc + inc + boundary
        # the global first's RAW value (field 6), for extrapolate-to-zero
        v_first_g = jnp.where(nd & ~has_prev, parts[d, ..., 6], v_first_g)
        v_prev = jnp.where(nd, vl, v_prev)
        has_prev = has_prev | nd

    # Prometheus extrapolatedRate (see kernels.range_eval)
    t_first_s = t_first_g / 1000.0
    t_last_s = t_last_g / 1000.0
    range_start = (steps[None, :] - window).astype(dtt) / 1000.0
    range_end = steps[None, :].astype(dtt) / 1000.0
    sampled = t_last_s - t_first_s
    avg_dur = sampled / jnp.maximum(n_tot - 1.0, 1.0)
    dur_start = t_first_s - range_start
    dur_end = range_end - t_last_s
    if counter and mode != "delta":
        # Prometheus applies the extrapolate-to-zero heuristic only to
        # rate/increase — delta on a counter schema gets the reset
        # correction but never the clamp (kernels.range_eval agrees)
        dur_to_zero = jnp.where(
            total_inc > 0,
            sampled * v_first_g / jnp.maximum(total_inc, 1e-30), jnp.inf)
        dur_start = jnp.minimum(dur_start, dur_to_zero)
    threshold = avg_dur * 1.1
    extend = sampled
    extend = extend + jnp.where(dur_start < threshold, dur_start, avg_dur / 2)
    extend = extend + jnp.where(dur_end < threshold, dur_end, avg_dur / 2)
    ext = total_inc * extend / jnp.maximum(sampled, 1e-10)
    if mode == "rate":
        out = ext / (window.astype(dtt) / 1000.0)
    else:  # increase / delta
        out = ext
    return jnp.where(n_tot >= 2, out, jnp.nan)


@jax.named_scope("prepare/prefix")
def _simple_prefixes(vals, counts_mask):
    """Exclusive prefix sums (value, count, value²) [P_l, S_l+1] — the
    per-batch state that makes every window sum an O(1) pair of gathers."""
    dt = fdtype()
    valid = counts_mask
    v = jnp.where(valid, vals, 0.0).astype(dt)

    def eprefix(x):
        return jnp.concatenate(
            [jnp.zeros(x.shape[:-1] + (1,), x.dtype), jnp.cumsum(x, -1)], -1)

    return eprefix(v), eprefix(valid.astype(dt)), eprefix(v * v)


def _simple_partials_from_bounds(ts, vals, counts_mask, csum, cnt, csum2,
                                 lo, hi, with_minmax: bool = True):
    """[P_l, K, 7] simple-fn partials given precomputed prefixes + bounds:
    sum, count, min, max, last, t_last, sumsq. ``with_minmax=False`` fills
    the min/max fields with sentinels — window min/max have no prefix form
    (only the masked-scan program, ``make_distributed_range_agg``, asks
    for them)."""
    dt = fdtype()
    valid = counts_mask
    v = jnp.where(valid, vals, 0.0).astype(dt)

    def g(x, idx):
        return jnp.take_along_axis(x, idx, axis=1)

    s = g(csum, hi) - g(csum, lo)
    s2 = g(csum2, hi) - g(csum2, lo)
    n = g(cnt, hi) - g(cnt, lo)
    if with_minmax:
        # blocked masked min/max (local S is small per device)
        S = ts.shape[1]
        sidx = jnp.arange(S)[None, None, :]
        in_win = (sidx >= lo[:, :, None]) & (sidx < hi[:, :, None]) \
            & valid[:, None, :]
        mn = jnp.min(jnp.where(in_win, vals[:, None, :], jnp.inf), axis=2)
        mx = jnp.max(jnp.where(in_win, vals[:, None, :], -jnp.inf), axis=2)
    else:
        mn = jnp.full_like(s, jnp.inf)
        mx = jnp.full_like(s, -jnp.inf)
    has = n > 0
    last = jnp.where(has, g(v, jnp.maximum(hi - 1, 0)), 0.0)
    t_last = jnp.where(has, g(ts, jnp.maximum(hi - 1, 0)),
                       jnp.int32(-(2**31 - 1))).astype(dt)
    return jnp.stack([s, n, mn, mx, last, t_last, s2], axis=-1)


def _sc_var(p):
    n = p[..., 1].sum(0)
    s = p[..., 0].sum(0)
    s2 = p[..., 6].sum(0)
    mean = s / jnp.maximum(n, 1.0)
    return n, jnp.maximum(s2 / jnp.maximum(n, 1.0) - mean * mean, 0.0)


_SIMPLE_COMBINE = {
    "sum_over_time": lambda p: jnp.where(p[..., 1].sum(0) > 0,
                                         p[..., 0].sum(0), jnp.nan),
    "count_over_time": lambda p: jnp.where(p[..., 1].sum(0) > 0,
                                           p[..., 1].sum(0), jnp.nan),
    "avg_over_time": lambda p: jnp.where(
        p[..., 1].sum(0) > 0,
        p[..., 0].sum(0) / jnp.maximum(p[..., 1].sum(0), 1.0), jnp.nan),
    "min_over_time": lambda p: jnp.where(p[..., 1].sum(0) > 0,
                                         p[..., 2].min(0), jnp.nan),
    "max_over_time": lambda p: jnp.where(p[..., 1].sum(0) > 0,
                                         p[..., 3].max(0), jnp.nan),
    "last_over_time": lambda p: jnp.where(
        p[..., 1].sum(0) > 0,
        jnp.take_along_axis(p[..., 4], jnp.argmax(p[..., 5], axis=0)[None],
                            axis=0)[0], jnp.nan),
    "present_over_time": lambda p: jnp.where(p[..., 1].sum(0) > 0, 1.0,
                                             jnp.nan),
    "stdvar_over_time": lambda p: jnp.where(
        _sc_var(p)[0] > 0, _sc_var(p)[1], jnp.nan),
    "stddev_over_time": lambda p: jnp.where(
        _sc_var(p)[0] > 0, jnp.sqrt(_sc_var(p)[1]), jnp.nan),
}


def _group_reduce(res, gid_l, num_groups, agg):
    """[P_l, K] per-series results → [G, K] grouped aggregate (psum/pmin/
    pmax over the shard axis). NaN = series absent at that step."""
    present = ~jnp.isnan(res)
    contrib = jnp.where(present, res, 0.0)
    if agg in ("min", "max"):
        sentinel = jnp.inf if agg == "min" else -jnp.inf
        marked = jnp.where(present, res, sentinel)
        seg = (jax.ops.segment_min if agg == "min"
               else jax.ops.segment_max)(marked, gid_l, num_groups)
        seg = (lax.pmin if agg == "min" else lax.pmax)(seg, "shard")
        gcnt = lax.psum(jax.ops.segment_sum(
            present.astype(contrib.dtype), gid_l, num_groups), "shard")
        return jnp.where(gcnt > 0, seg, jnp.nan)
    gsum = lax.psum(jax.ops.segment_sum(contrib, gid_l, num_groups), "shard")
    gcnt = lax.psum(jax.ops.segment_sum(
        present.astype(contrib.dtype), gid_l, num_groups), "shard")
    if agg in ("stddev", "stdvar"):
        gsum2 = lax.psum(jax.ops.segment_sum(contrib * contrib, gid_l,
                                             num_groups), "shard")
        mean = gsum / jnp.maximum(gcnt, 1.0)
        var = jnp.maximum(gsum2 / jnp.maximum(gcnt, 1.0) - mean * mean, 0.0)
        out = var if agg == "stdvar" else jnp.sqrt(var)
        return jnp.where(gcnt > 0, out, jnp.nan)
    if agg == "avg":
        return jnp.where(gcnt > 0, gsum / jnp.maximum(gcnt, 1.0), jnp.nan)
    if agg == "count":
        return jnp.where(gcnt > 0, gcnt, jnp.nan)
    if agg == "group":
        return jnp.where(gcnt > 0, 1.0, jnp.nan)
    return jnp.where(gcnt > 0, gsum, jnp.nan)


COUNTER_FNS = {"rate": ("rate", True), "increase": ("increase", True),
               "delta": ("delta", False)}

# aggs with associative mesh reductions
MESH_AGG_OPS = ("sum", "avg", "count", "min", "max", "stddev", "stdvar",
                "group")


# ---- split pipeline: prepare / bounds / eval / reduce -----------------------
#
# A query's device work is cut by what each pass depends on, and each pass
# is its own jitted program whose output stays on the device, cached by the
# mesh engine:
#
#   prepare  (batch version)                    counter-correction cumsum
#                                               over [P, S], or the three
#                                               exclusive prefix sums
#   bounds   (batch version, grid, window)      (lo, hi) of every window:
#                                               a compare-and-count on the
#                                               chip, the vmapped double
#                                               searchsorted on a CPU mesh
#   eval     (batch version, grid, window, fn)  boundary gathers, the
#                                               all_gather of [dt, P_l, K, 7]
#                                               partials over ``time``, the
#                                               combine: [P, K] a series
#   reduce   (every query)                      segment reduce + psum over
#                                               ``shard``: [G, K]
#
# so a query that repeats a grid over unchanged data runs only the reduce,
# and sum() and avg() over one rate() share one eval. All four are
# shard_map programs over the same (shard, time) mesh: an output is only
# ever read by a program with the same sharding, and no global layout is
# materialised between them.
#
# The functions below have that form because a window of theirs is a
# difference of two prefix values or a pair of boundary samples. Window
# min/max are neither: see ``make_distributed_range_agg``.
SPLIT_FNS = ("rate", "increase", "delta", "sum_over_time",
             "count_over_time", "avg_over_time", "last_over_time",
             "present_over_time", "stddev_over_time", "stdvar_over_time")
_SIMPLE_SPLIT_FNS = tuple(f for f in SPLIT_FNS if f not in COUNTER_FNS)


def make_mesh_prepare(mesh: Mesh, kind: str):
    """Per-batch-version prepare program, sharded like the batch itself.

    ``kind="counter"``: (vals, valid) → counter-corrected values [P, S]
    (block-local cumsum — resets across time blocks are handled by the
    combine's boundary terms). This is the device-side replacement for
    the host ``SeriesBatch.delta_host`` pre-pass when the value magnitudes
    make direct f32 arithmetic safe (see mesh_engine._device_correction_ok).

    ``kind="prefix"``: (vals, valid) → (csum, cnt, csum2) exclusive
    prefixes, globally [P, S + dt] sharded (shard, time) — each time block
    holds its local [P_l, S_l+1] prefix.
    """

    def prep(vals, valid):
        def kernel(vals_l, valid_l):
            dt = fdtype()
            if kind == "counter":
                v = jnp.where(valid_l, vals_l, 0.0).astype(dt)
                return _counter_correct(v, valid_l)
            return _simple_prefixes(vals_l, valid_l)

        out_specs = P("shard", "time") if kind == "counter" \
            else (P("shard", "time"),) * 3
        return jax.shard_map(
            kernel, mesh=mesh,
            in_specs=(P("shard", "time"), P("shard", "time")),
            out_specs=out_specs, check_vma=False,
        )(vals, valid)

    return jax.jit(prep)


def make_mesh_bounds(mesh: Mesh):
    """Window-bounds program: (ts, steps, window) → (lo, hi) int32, each
    time block's bounds local to its own [P_l, S_l] slice. Globally
    [P, dt·K] sharded (shard, time); only ever consumed by step programs
    with the same sharding, so the global layout is never materialized.
    As a binary search this was three quarters of the device's time in
    the listed cell (215.6 ms at P=16384, S=1024, K=32 on a v5e; ~200 ms
    at P=8192, S=2048, K=256 on one CPU device); as a count it is 1.7 ms
    there (``bounds_form``). Its output is still cached per (batch
    version, grid, window): a repeated grid runs no bounds at all."""

    def bounds(ts, steps, window):
        def kernel(ts_l, steps_r, window_r):
            lo, hi = _window_bounds(ts_l, steps_r, window_r, mesh)
            return lo.astype(jnp.int32), hi.astype(jnp.int32)

        return jax.shard_map(
            kernel, mesh=mesh,
            in_specs=(P("shard", "time"), P(None), P()),
            out_specs=(P("shard", "time"), P("shard", "time")),
            check_vma=False,
        )(ts, steps, window)

    return jax.jit(bounds)


def make_mesh_eval_delta(mesh: Mesh, fn: str, counter: bool | None = None):
    """Per-(batch version, grid, window) series evaluation for rate/
    increase/delta given cached correction + bounds: gathers →
    [P_l, K, 7] partials → all_gather over ``time`` → associative combine
    with Prometheus extrapolation. Output [P, K] per-series values,
    sharded on ``shard`` and replicated over ``time``.

    The boundary gathers are the dominant device cost now that bounds
    are a count (on a v5e ``jit_ev`` is ~60 of the 66 ms a request of the
    listed cell keeps the device busy, ``avg_over_time`` at P=16384, K=32:
    PERF.md §5, PR 32; a CPU figure: ~280 ms for the 7 gathers at P=8192,
    K=256, XLA's gather being per-element there) and depend only on (data
    version, step grid, window) — never on the query's grouping — so the
    engine caches THIS stage's output and re-runs only the group reduce
    per query. ``cv``
    (counter-corrected values) rides along for counter fns; ``raw``
    accompanies the host's pre-corrected values (see
    ``_rate_partials_from_bounds``).

    ``counter`` overrides the per-fn default: delta on a COUNTER schema
    is reset-corrected like rate/increase (mirroring the exec
    transformers), while delta on a gauge keeps raw differences."""
    mode, default_counter = COUNTER_FNS[fn]
    counter = default_counter if counter is None else counter

    def ev(ts, vals, valid, lo, hi, steps, window, cv=None, raw=None):
        def kernel(ts_l, vals_l, valid_l, lo_l, hi_l, steps_r,
                   window_r, *rest):
            cv_l = rest[0] if cv is not None else None
            raw_l = rest[-1] if raw is not None else None
            with jax.named_scope(f"eval/{fn}"):
                parts = _rate_partials_from_bounds(ts_l, vals_l, valid_l,
                                                   lo_l, hi_l, cv=cv_l,
                                                   raw=raw_l)
                gathered = lax.all_gather(parts, "time")  # [dt, P_l, K, 7]
                return _combine_time_partials(gathered, steps_r, window_r,
                                              mode=mode, counter=counter)

        in_specs = (P("shard", "time"),) * 5 + (P(None), P())
        args = (ts, vals, valid, lo, hi, steps, window)
        for extra in (cv, raw):
            if extra is not None:
                in_specs += (P("shard", "time"),)
                args += (extra,)
        return jax.shard_map(
            kernel, mesh=mesh, in_specs=in_specs,
            out_specs=P("shard", None), check_vma=False,
        )(*args)

    return jax.jit(ev)


def make_mesh_eval_simple(mesh: Mesh, fn: str):
    """Per-(batch version, grid, window) series evaluation for the
    prefix-summable over-time fns given cached prefixes + bounds (window
    min/max have no prefix form: ``make_distributed_range_agg``). Output
    [P, K] sharded on ``shard``, replicated over ``time`` — cached by the
    engine like the delta-family eval."""
    if fn not in _SIMPLE_SPLIT_FNS:
        raise ValueError(f"{fn} has no split (prefix) form")
    combine = _SIMPLE_COMBINE[fn]

    def ev(ts, vals, valid, csum, cnt, csum2, lo, hi, steps, window):
        def kernel(ts_l, vals_l, valid_l, cs_l, cn_l, cs2_l, lo_l, hi_l,
                   steps_r, window_r):
            with jax.named_scope(f"eval/{fn}"):
                parts = _simple_partials_from_bounds(
                    ts_l, vals_l, valid_l, cs_l, cn_l, cs2_l, lo_l, hi_l,
                    with_minmax=False)
                gathered = lax.all_gather(parts, "time")  # [dt, P_l, K, 7]
                return combine(gathered)

        in_specs = (P("shard", "time"),) * 8 + (P(None), P())
        return jax.shard_map(
            kernel, mesh=mesh, in_specs=in_specs,
            out_specs=P("shard", None), check_vma=False,
        )(ts, vals, valid, csum, cnt, csum2, lo, hi, steps, window)

    return jax.jit(ev)


def make_mesh_group_reduce(mesh: Mesh, num_groups: int, agg: str):
    """The per-query step of the split pipeline: cached per-series values
    [P, K] → [G, K] grouped aggregate — one segment reduce plus one psum
    over ``shard``, orders of magnitude less work than re-evaluating the
    windows. This is ALL a warm repeat query runs on device."""

    def step(series_vals, group_ids):
        def kernel(res_l, gid_l):
            with jax.named_scope(f"reduce/{agg}"):
                return _group_reduce(res_l, gid_l, num_groups, agg)

        return jax.shard_map(
            kernel, mesh=mesh,
            in_specs=(P("shard", None), P("shard")),
            out_specs=P(None, None), check_vma=False,
        )(series_vals, group_ids)

    return jax.jit(step)


# ---- window min/max: the masked scan ----------------------------------------

def make_distributed_range_agg(mesh: Mesh, fn: str, num_groups: int,
                               agg: str | None = "sum"):
    """The program of the range functions that have no prefix form,
    ``min_over_time`` and ``max_over_time``: distributed
    ``agg(fn(x[w])) by (g)`` over the (shard, time) mesh in ONE program,
    run whole by every query. Each device searches its block's window
    bounds and scans every window under a mask ([P_l, K, S_l] compares);
    the time-block partials are all-gathered over ``time`` and combined,
    and label groups are reduced via segment ops + collectives over
    ``shard``. ``agg=None`` returns the per-series [P, K] matrix, sharded
    over the shard axis.

    Inputs (global shapes): ts [P, S] int32 relative ms, vals [P, S],
    valid [P, S] bool, group_ids [P] int32, steps [K] int32, window int32
    scalar. A ``fn`` of ``SPLIT_FNS`` is refused: its programs are the
    four above."""
    if fn not in ("min_over_time", "max_over_time"):
        raise ValueError(f"{fn} is not a masked-scan range function")
    combine = _SIMPLE_COMBINE[fn]

    def step(ts, vals, valid, group_ids, steps, window):
        def kernel(ts_l, vals_l, valid_l, gid_l, steps_r, window_r):
            with jax.named_scope(f"eval/{fn}"):
                lo, hi = _window_bounds(ts_l, steps_r, window_r, mesh)
                csum, cnt, csum2 = _simple_prefixes(vals_l, valid_l)
                parts = _simple_partials_from_bounds(
                    ts_l, vals_l, valid_l, csum, cnt, csum2, lo, hi)
                gathered = lax.all_gather(parts, "time")  # [dt, P_l, K, 7]
                res = combine(gathered)
            if agg is None:
                return res
            with jax.named_scope(f"reduce/{agg}"):
                return _group_reduce(res, gid_l, num_groups, agg)

        return jax.shard_map(
            kernel, mesh=mesh,
            in_specs=(P("shard", "time"), P("shard", "time"),
                      P("shard", "time"), P("shard"), P(None), P()),
            out_specs=P("shard", None) if agg is None else P(None, None),
            check_vma=False,
        )(ts, vals, valid, group_ids, steps, window)

    return jax.jit(step)


def shard_batch_arrays(mesh: Mesh, ts, vals, valid, group_ids, raw=None):
    """Place host arrays with (shard, time) shardings. ``raw`` [P, S]
    (optional — the uncorrected values accompanying the host's
    pre-corrected pass) shards like ``vals``.

    The placed arrays never alias the caller's memory: it overwrites its
    staging buffers once they are ready. An accelerator's put is a copy. A
    CPU client may KEEP an aligned numpy buffer as the device's own — under
    ``may_alias=False`` too, which jax 0.9 honours for device arrays alone
    (``pxla._shard_np_array`` drops it) — so on a CPU mesh the put is handed
    a copy it may keep."""
    s2 = NamedSharding(mesh, P("shard", "time"))
    s1 = NamedSharding(mesh, P("shard"))
    keeps = mesh.devices.flat[0].platform == "cpu"
    return tuple(jax.device_put(a.copy() if keeps else a, s)
                 for a, s in ((ts, s2), (vals, s2), (valid, s2),
                              (group_ids, s1), (raw, s2))
                 if a is not None)
