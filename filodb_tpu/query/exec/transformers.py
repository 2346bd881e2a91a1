"""RangeVectorTransformers: per-plan post-processing stages.

Counterpart of reference ``RangeVectorTransformer.scala:1-489`` +
``PeriodicSamplesMapper.scala`` + ``HistogramQuantileMapper.scala`` — but
operating on whole StepMatrix batches; each transformer is host orchestration
around jitted kernels.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

import jax.numpy as jnp
import numpy as np

from filodb_tpu.core.partkey import METRIC_LABEL
from filodb_tpu.query.engine import kernels
from filodb_tpu.query.engine.aggregations import (
    aggregate as agg_kernel,
    histogram_quantile,
    quantile_across,
    topk_mask,
)
from filodb_tpu.query.engine.batch import TS_PAD, SeriesBatch
from filodb_tpu.query.engine.instantfns import apply_binary_op, apply_instant_fn
from filodb_tpu.query.model import RangeVectorKey, ScalarResult, StepMatrix


_GID_CACHE: dict = {}


class RangeVectorTransformer:
    def apply(self, data: StepMatrix) -> StepMatrix:  # pragma: no cover
        raise NotImplementedError


def steps_array(start: int, step: int, end: int) -> np.ndarray:
    """Step timestamps [start, end] inclusive (epoch ms)."""
    if step <= 0:
        return np.array([end], dtype=np.int64)
    return np.arange(start, end + 1, step, dtype=np.int64)


@dataclass
class PeriodicSamplesMapper(RangeVectorTransformer):
    """THE hot windowing operator (reference ``PeriodicSamplesMapper.scala``):
    evaluates a range function (or instant-vector last-sample materialization)
    at each step. Operates on a SeriesBatch via the kernel library — O(P·(S+K))
    instead of per-sample sliding windows."""

    start: int
    step: int
    end: int
    window: int = 0
    function: str | None = None  # None => instant last-sample semantics
    params: tuple = ()
    offset: int = 0
    at_ms: "int | None" = None  # @ modifier: pin evaluation time
    is_counter: bool = False
    keep_metric: bool = False

    def eval_batch(self, batch: SeriesBatch,
                   keys: list[RangeVectorKey]) -> StepMatrix:
        steps = steps_array(self.start, self.step, self.end)
        if self.at_ms is not None:
            eval_steps = np.full(len(steps), self.at_ms - self.offset,
                                 np.int64)
        else:
            eval_steps = steps - self.offset
        rel_steps = (eval_steps - batch.base_ts).astype(np.int32)
        fn = self.function or "last_sample"
        window = self.window if self.function else 300_000  # staleness lookback
        steps_j = jnp.asarray(rel_steps)
        win_j = jnp.asarray(np.int32(window))

        if getattr(batch, "masked", False):
            # device-decoded masked batch (engine/device_batch.py)
            ts_j, vals_j, valid_j = batch.device_arrays()
            if batch.is_histogram:
                import jax

                def per_bucket_m(vb):
                    return kernels.range_eval_masked(
                        fn, ts_j, vb, valid_j, steps_j, win_j,
                        counter=self.is_counter)

                out = jax.vmap(per_bucket_m, in_axes=2, out_axes=2)(vals_j)
                out = np.asarray(out)[: batch.num_series]
                return StepMatrix(self._out_keys(keys), out, steps,
                                  batch.les)
            if fn == "quantile_over_time":
                out = kernels.quantile_over_time_masked(
                    self.params[0], ts_j, vals_j, valid_j, steps_j, win_j)
            elif fn == "holt_winters":
                out = kernels.holt_winters_masked(
                    self.params[0], self.params[1], ts_j, vals_j, valid_j,
                    steps_j, win_j)
            elif fn == "predict_linear":
                out = kernels.range_eval_masked(
                    fn, ts_j, vals_j, valid_j, steps_j, win_j,
                    extra=float(self.params[0]))
            else:
                out = kernels.range_eval_masked(
                    fn, ts_j, vals_j, valid_j, steps_j, win_j,
                    counter=self.is_counter)
            out = out[: batch.num_series]  # stays on device (lazy transfer)
            if fn == "timestamp":
                out = out + batch.base_ts / 1000.0
            return StepMatrix(self._out_keys(keys), out, steps)

        # delta-family fns run on f64-host-corrected, per-series-rebased
        # values (SeriesBatch.delta_host): the f32 device cast then only
        # sees window-scale magnitudes, keeping rate() exact for counters
        # beyond 2^24 (reference RateFunctions.scala runs
        # in double throughout). Which fns get the reset CORRECTION
        # mirrors the kernels exactly: rate/increase always, delta only on
        # counter schemas, irate's reset handling is arithmetically
        # equivalent under correction; idelta/deriv are defined on raw
        # values (idelta must keep its negative delta across a reset), so
        # they take the rebase-only lane.
        delta_fns = ("rate", "increase", "delta", "irate", "idelta", "deriv")
        pre_corrected = fn in delta_fns and not batch.is_histogram
        if pre_corrected:
            corrected = fn in ("rate", "increase", "irate") \
                or (fn == "delta" and self.is_counter)
            ts_j, vals_j, counts_j, raw_j = batch.delta_arrays(
                counter=corrected)
            if fn not in ("rate", "increase"):
                raw_j = None  # only the extrapolation clamp consumes it
        else:
            ts_j, vals_j, counts_j = batch.device_arrays()
            raw_j = None

        if batch.is_histogram:
            # apply the range function per bucket: vmap over B
            import jax

            def per_bucket(vb):
                return kernels.range_eval(fn, ts_j, vb, counts_j, steps_j,
                                          win_j, counter=self.is_counter)

            out = jax.vmap(per_bucket, in_axes=2, out_axes=2)(vals_j)
            out = np.asarray(out)[: batch.num_series]
            m = StepMatrix(self._out_keys(keys), out, steps, batch.les)
            return m

        if fn == "quantile_over_time":
            out = kernels.quantile_over_time(self.params[0], ts_j, vals_j,
                                             counts_j, steps_j, win_j)
        elif fn == "holt_winters":
            sf, tf = self.params
            out = kernels.holt_winters(sf, tf, ts_j, vals_j, counts_j,
                                       steps_j, win_j)
        elif fn == "predict_linear":
            out = kernels.range_eval("predict_linear", ts_j, vals_j, counts_j,
                                     steps_j, win_j,
                                     extra=float(self.params[0]))
        else:
            out = kernels.range_eval(fn, ts_j, vals_j, counts_j, steps_j,
                                     win_j, counter=self.is_counter,
                                     pre_corrected=pre_corrected,
                                     raw=raw_j)
        # keep the result on device: downstream aggregation consumes it
        # without a host round trip; the query service materializes the
        # final result once (StepMatrix tolerates device values)
        out = out[: batch.num_series]
        if fn == "timestamp":
            # kernel returned relative seconds; rebase to epoch
            out = out + batch.base_ts / 1000.0
        return StepMatrix(self._out_keys(keys), out, steps)

    def _out_keys(self, keys):
        if self.function and not self.keep_metric:
            return [k.drop_metric() for k in keys]
        return list(keys)

    # matrix-in/matrix-out path (subqueries)
    def apply(self, data: StepMatrix) -> StepMatrix:
        """Apply the range function over an already-evaluated inner matrix
        (subquery): inner steps act as samples."""
        steps = steps_array(self.start, self.step, self.end)
        P = data.num_series
        if P == 0:
            return StepMatrix([], np.zeros((0, len(steps))), steps)
        data.materialize()
        # compact per-series NaN samples into padded ts/vals arrays
        inner_ts = data.steps_ms  # [S]
        S = len(inner_ts)
        base = int(inner_ts[0]) if S else 0
        ts_arr = np.full((P, max(S, 1)), TS_PAD, np.int32)
        vals_arr = np.zeros((P, max(S, 1)), np.float64)
        counts = np.zeros(P, np.int32)
        for i in range(P):
            valid = ~np.isnan(data.values[i])
            n = int(valid.sum())
            counts[i] = n
            ts_arr[i, :n] = (inner_ts[valid] - base).astype(np.int32)
            vals_arr[i, :n] = data.values[i][valid]
        batch = SeriesBatch(base, ts_arr, vals_arr, counts,
                            list(range(P)), data.les)
        return self.eval_batch(batch, data.keys)


@dataclass
class AggregateMapReduce(RangeVectorTransformer):
    """Label-grouped aggregation (reference ``AggregateMapReduce`` +
    RowAggregators), lowered to segment reductions."""

    op: str
    params: tuple = ()
    by: tuple[str, ...] = ()
    without: tuple[str, ...] = ()

    def bind(self, ctx) -> None:
        # exec-context hook (ExecPlan.execute / leaf chains call bind before
        # apply): gives the aggregation access to the query's cost budget
        self._ctx = ctx

    def group_keys(self, keys: list[RangeVectorKey]) -> list[RangeVectorKey]:
        if self.by:
            return [k.only(self.by) for k in keys]
        if self.without:
            return [k.without(self.without).drop_metric() for k in keys]
        return [RangeVectorKey(()) for _ in keys]

    def _group_ids(self, keys):
        # group-id computations repeat across queries over cached batches
        # (the keys list object is stable); memoize on list identity. Entries
        # hold the keys list itself so the id can't be recycled while cached.
        ck = (id(keys), self.by, self.without)
        hit = _GID_CACHE.get(ck)
        if hit is not None and hit[0] is keys:
            return hit[1], hit[2]
        gkeys = self.group_keys(keys)
        uniq: dict[RangeVectorKey, int] = {}
        gids = np.empty(len(gkeys), np.int32)
        for i, gk in enumerate(gkeys):
            gids[i] = uniq.setdefault(gk, len(uniq))
        out_keys = list(uniq.keys())
        if len(_GID_CACHE) >= 128:
            _GID_CACHE.pop(next(iter(_GID_CACHE)))
        _GID_CACHE[ck] = (keys, gids, out_keys)
        return gids, out_keys

    def apply(self, data: StepMatrix) -> StepMatrix:
        if data.num_series == 0:
            return data
        gids, out_keys = self._group_ids(data.keys)
        G = len(out_keys)
        # scan-time group-cardinality budget: checked BEFORE the aggregation
        # kernel runs, so a runaway group-by fails (or truncates) without
        # paying for the full reduction
        ctx = getattr(self, "_ctx", None)
        budget = getattr(ctx, "budget", None) if ctx is not None else None
        if budget is not None and budget.check_cardinality(ctx, G):
            limit = int(budget.max_group_cardinality)
            idx = np.nonzero(gids < limit)[0]
            data = StepMatrix([data.keys[i] for i in idx],
                              np.asarray(data.values)[idx],
                              data.steps_ms, data.les)
            gids = gids[idx]
            out_keys = out_keys[:limit]
            G = limit
        v = jnp.asarray(data.values)
        g = jnp.asarray(gids)

        if self.op in ("sum", "avg", "count", "min", "max", "stddev",
                       "stdvar", "group"):
            # results stay device-resident (lazy): the exec tree may layer
            # further device transforms, and the service boundary
            # materializes exactly once — no per-node fetches
            if data.is_histogram:  # hist sum aggregates per bucket
                import jax
                out = jax.vmap(
                    lambda vb: agg_kernel(self.op, vb, g, G),
                    in_axes=2, out_axes=2)(v)
                return StepMatrix(out_keys, out, data.steps_ms, data.les)
            out = agg_kernel(self.op, v, g, G)
            return StepMatrix(out_keys, out, data.steps_ms)

        if self.op in ("topk", "bottomk"):
            k = int(self.params[0])
            mask = np.asarray(topk_mask(v, g, G, k, self.op == "bottomk"))
            vals = np.where(mask, data.values, np.nan)
            return StepMatrix(list(data.keys), vals, data.steps_ms).compact()

        if self.op == "quantile":
            out = quantile_across(float(self.params[0]), v, g, G)
            return StepMatrix(out_keys, out, data.steps_ms)

        if self.op == "count_values":
            label = str(self.params[0])
            # host-side: distinct values become output series. One
            # vectorized np.unique over (group, value, step) triples —
            # the former Python triple loop was O(groups × steps × uniques)
            vals = np.asarray(data.values)
            K = data.num_steps
            mask = ~np.isnan(vals)
            g = np.broadcast_to(gids[:, None], vals.shape)[mask]
            s = np.broadcast_to(np.arange(K)[None, :], vals.shape)[mask]
            v = vals[mask]
            triples = np.stack([g.astype(np.float64), v,
                                s.astype(np.float64)], axis=1)
            uniq, counts = np.unique(triples, axis=0, return_counts=True)
            # distinct (group, value) pairs become the output rows
            pairs, row_of = np.unique(uniq[:, :2], axis=0,
                                      return_inverse=True)
            values = np.full((len(pairs), K), np.nan)
            values[row_of, uniq[:, 2].astype(np.int64)] = counts
            keys = [RangeVectorKey(tuple(sorted(
                list(out_keys[int(gi)].labels) + [(label, _fmt_value(val))])))
                for gi, val in pairs]
            return StepMatrix(keys, values, data.steps_ms)

        raise ValueError(f"unknown aggregation {self.op}")


def _fmt_value(v: float) -> str:
    if v == int(v):
        return str(int(v))
    return repr(v)


# ---------------------------------------------------------------------------
# two-phase aggregation pushdown (map stage on children, reduce at the root)

# reserved label carrying a partial component name ("sum" / "sumsq" /
# "count") from the map stage to the root reduce; never a real series label
AGG_PART_LABEL = "__agg_part__"

# ops whose partials re-reduce with op-correct semantics at the root.
# quantile and count_values need every raw series at once — they stay on
# the declared bypass list (full-gather path).
AGG_PUSHDOWN_OPS = frozenset((
    "sum", "min", "max", "count", "avg", "group", "stddev", "stdvar",
    "topk", "bottomk"))
AGG_PUSHDOWN_BYPASS = frozenset(("quantile", "count_values"))


def _grouped(op: str, v, g, num_groups: int, is_hist: bool):
    """agg_kernel, vmapped over the bucket axis for histogram matrices."""
    if is_hist:
        import jax
        return jax.vmap(lambda vb: agg_kernel(op, vb, g, num_groups),
                        in_axes=2, out_axes=2)(v)
    return agg_kernel(op, v, g, num_groups)


def _part_key(gk: RangeVectorKey, comp: str) -> RangeVectorKey:
    return RangeVectorKey(tuple(sorted(gk.labels
                                       + ((AGG_PART_LABEL, comp),))))


@dataclass
class AggregatePartialMapper(RangeVectorTransformer):
    """Map stage of two-phase aggregation pushdown (the reference runs
    ``AggregateMapReduce`` on each leaf node): emits per-group PARTIAL rows
    so remote children ship one row per group instead of one per series.

    sum/min/max/count/group emit the local aggregate directly (count
    re-reduces via sum at the root); avg ships (sum, count) and
    stddev/stdvar ship (sum, sum-of-squares, count) as component rows
    tagged with ``AGG_PART_LABEL``; topk/bottomk emit the shard's k
    candidate series per group — exact after the root re-rank, because each
    step's global top-k is a subset of the union of per-shard top-k's."""

    op: str
    params: tuple = ()
    by: tuple[str, ...] = ()
    without: tuple[str, ...] = ()

    def apply(self, data: StepMatrix) -> StepMatrix:
        if data.num_series == 0:
            return data
        amr = AggregateMapReduce(self.op, self.params, self.by, self.without)
        if self.op in ("sum", "min", "max", "count", "group", "topk",
                       "bottomk"):
            return amr.apply(data)
        if self.op == "avg":
            comps = ("sum", "count")
        elif self.op in ("stddev", "stdvar"):
            comps = ("sum", "sumsq", "count")
        else:
            raise ValueError(f"aggregation {self.op!r} is not "
                             f"pushdown-capable")
        gids, out_keys = amr._group_ids(data.keys)
        G = len(out_keys)
        v = jnp.asarray(data.values)
        g = jnp.asarray(gids)
        hist = data.is_histogram
        keys: list[RangeVectorKey] = []
        parts = []
        for comp in comps:
            if comp == "sumsq":
                part = _grouped("sum", v * v, g, G, hist)
            else:
                part = _grouped(comp, v, g, G, hist)
            parts.append(part)
            keys.extend(_part_key(gk, comp) for gk in out_keys)
        return StepMatrix(keys, jnp.concatenate(parts, axis=0),
                          data.steps_ms, data.les)


def _reduce_by_key(m: StepMatrix, op: str) -> StepMatrix:
    """Merge rows with identical keys using ``op`` (root combine of
    pushdown partials: group labels are already reduced on partial rows,
    so grouping is plain full-key identity)."""
    uniq: dict[RangeVectorKey, int] = {}
    gids = np.empty(m.num_series, np.int32)
    for i, k in enumerate(m.keys):
        gids[i] = uniq.setdefault(k, len(uniq))
    G = len(uniq)
    if G == m.num_series:
        return m  # all keys distinct: nothing to merge
    out = _grouped(op, jnp.asarray(m.values), jnp.asarray(gids), G,
                   m.is_histogram)
    return StepMatrix(list(uniq), np.asarray(out), m.steps_ms, m.les)


def _split_components(m: StepMatrix, comps: tuple[str, ...]):
    """Partial rows → (base keys, one aligned [G, K] array per component)."""
    rows: dict[str, dict[RangeVectorKey, np.ndarray]] = {c: {} for c in comps}
    for i, k in enumerate(m.keys):
        lm = dict(k.labels)
        comp = lm.pop(AGG_PART_LABEL, None)
        if comp not in rows:
            raise ValueError(f"partial aggregate row lacks a valid "
                             f"{AGG_PART_LABEL} component: {k}")
        rows[comp][RangeVectorKey(tuple(sorted(lm.items())))] = m.values[i]
    keys = list(rows[comps[0]])
    arrs = []
    for c in comps:
        if set(rows[c]) != set(keys):
            raise ValueError("misaligned partial aggregate components")
        arrs.append(np.stack([rows[c][k] for k in keys]) if keys
                    else m.values[:0])
    return keys, arrs


class PartialAggregateFolder:
    """Root reduce stage of two-phase pushdown: folds per-child partial
    matrices AS THEY ARRIVE — the accumulator stays at O(groups) rows, so
    peak root memory no longer scales with fan-out × cardinality — then
    finalizes multi-component ops (avg, stddev/stdvar)."""

    # how partial rows combine across children, per original op
    _COMBINE = {"sum": "sum", "count": "sum", "min": "min", "max": "max",
                "group": "group", "avg": "sum", "stddev": "sum",
                "stdvar": "sum"}

    def __init__(self, op: str, params=(), by=(), without=()):
        self.op = op
        self.params = params
        self.by = by
        self.without = without
        self._acc: StepMatrix | None = None

    def fold(self, m: StepMatrix) -> None:
        if m is None or m.num_series == 0:
            return
        m.materialize()  # partial rows are tiny; fold on host
        if self._acc is None or self._acc.num_series == 0:
            self._acc = m
            return
        both = StepMatrix.concat([self._acc, m])
        if self.op in ("topk", "bottomk"):
            # re-rank the accumulated candidate union after every fold so
            # the accumulator stays at ≤ groups × k live rows
            self._acc = AggregateMapReduce(
                self.op, self.params, self.by, self.without).apply(both)
        else:
            self._acc = _reduce_by_key(both, self._COMBINE[self.op])

    def finalize(self) -> StepMatrix:
        acc = self._acc
        if acc is None:
            return StepMatrix.empty()
        acc.materialize()
        if self.op == "avg":
            keys, (s, cnt) = _split_components(acc, ("sum", "count"))
            with np.errstate(invalid="ignore", divide="ignore"):
                out = np.where(np.nan_to_num(cnt) > 0, s / cnt, np.nan)
            return StepMatrix(keys, out, acc.steps_ms, acc.les)
        if self.op in ("stddev", "stdvar"):
            keys, (s, s2, cnt) = _split_components(
                acc, ("sum", "sumsq", "count"))
            # the sum-of-squares difference cancels catastrophically in
            # low precision; do the root math in float64 (the kernel-dtype
            # partials still bound equivalence to ~kernel tolerance)
            s, s2, cnt = (x.astype(np.float64) for x in (s, s2, cnt))
            with np.errstate(invalid="ignore", divide="ignore"):
                mean = s / cnt
                var = np.maximum(s2 / cnt - mean * mean, 0.0)
                out = np.where(np.nan_to_num(cnt) > 0,
                               var if self.op == "stdvar" else np.sqrt(var),
                               np.nan)
            return StepMatrix(keys, out, acc.steps_ms, acc.les)
        return acc


@dataclass
class InstantVectorFunctionMapper(RangeVectorTransformer):
    function: str
    args: tuple = ()

    def apply(self, data: StepMatrix) -> StepMatrix:
        if self.function == "hist_to_prom_vectors":
            # first-class histogram → le-labelled bucket series (reference
            # HistToPromSeriesMapper)
            if not data.is_histogram:
                return data
            from filodb_tpu.http.promjson import _flatten_histograms
            return _flatten_histograms(data)
        if self.function in ("histogram_quantile", "histogram_max_quantile"):
            q = float(self.args[0])
            if data.is_histogram:
                out = histogram_quantile(
                    q, jnp.asarray(data.values), jnp.asarray(data.les))
                keys = [k.drop_metric() for k in data.keys]
                return data.derive(keys, out)
            return self._bucket_quantile(q, data)
        vals = jnp.asarray(data.values)
        if self.function in ("hour", "minute", "month", "year", "day_of_month",
                             "day_of_week", "day_of_year", "days_in_month"):
            out = apply_instant_fn(self.function, vals)
        else:
            params = tuple(float(a) for a in self.args)
            out = apply_instant_fn(self.function, vals, params=params)
        keys = [k.drop_metric() for k in data.keys]
        return data.derive(keys, out, data.les)

    def _bucket_quantile(self, q: float, data: StepMatrix) -> StepMatrix:
        """histogram_quantile over prom-style `le`-labelled bucket series
        (reference ``HistogramQuantileMapper.scala:1-149``)."""
        data.materialize()  # host loop over bucket groups below
        groups: dict[RangeVectorKey, list[tuple[float, int]]] = {}
        for i, k in enumerate(data.keys):
            lm = k.label_map
            le = lm.get("le")
            if le is None:
                continue
            gk = k.without(("le", METRIC_LABEL))
            groups.setdefault(gk, []).append((float(le), i))
        if not groups:
            return StepMatrix([], np.zeros((0, data.num_steps)),
                              data.steps_ms)
        # groups sharing one bucket scheme evaluate as ONE batched
        # [G, K, B] quantile call (per-group device calls previously cost
        # ~90% of flat-histogram query time at fleet scale)
        by_les: dict[tuple, list] = {}
        for gk, buckets in groups.items():
            buckets.sort()
            by_les.setdefault(tuple(b[0] for b in buckets),
                              []).append((gk, [b[1] for b in buckets]))
        out_keys = []
        outs = []
        for les_t, members in by_les.items():
            les = np.array(les_t)
            h = data.values[np.array([idx for _, idx in members])]  # [G,B,K]
            # make cumulative counts monotonic across buckets (prom tolerates
            # slight non-monotonicity from scrapes)
            h = np.maximum.accumulate(np.nan_to_num(h, nan=0.0), axis=1)
            res = np.asarray(histogram_quantile(
                q, jnp.asarray(h.transpose(0, 2, 1)),
                jnp.asarray(les)))  # [G, K]
            out_keys.extend(gk for gk, _ in members)
            outs.extend(res)
        return StepMatrix(out_keys, np.stack(outs), data.steps_ms)


@dataclass
class ScalarOperationMapper(RangeVectorTransformer):
    """vector-scalar binary op (reference ``ScalarOperationMapper``)."""

    op: str
    scalar: "ScalarResult | float"
    scalar_is_lhs: bool = True
    bool_mode: bool = False

    _COMPARISONS = ("==", "!=", ">", "<", ">=", "<=")

    def apply(self, data: StepMatrix) -> StepMatrix:
        v = jnp.asarray(data.values)
        if v.size == 0:
            # no series: comparing/combining an empty vector with a
            # scalar is the empty vector (broadcast_to would reject
            # shaping a stepped scalar to the (0, 0) values array)
            return data.derive([k.drop_metric() for k in data.keys], v)
        if isinstance(self.scalar, ScalarResult):
            sc = jnp.asarray(self.scalar.values)[None, :]
        else:
            sc = jnp.asarray(float(self.scalar))
        sc = jnp.broadcast_to(sc, v.shape)
        lhs, rhs = (sc, v) if self.scalar_is_lhs else (v, sc)
        if self.op in self._COMPARISONS and not self.bool_mode:
            # comparison filtering keeps the *vector* sample values
            cond = ~jnp.isnan(apply_binary_op(self.op, lhs, rhs,
                                              bool_mode=True)) \
                & (apply_binary_op(self.op, lhs, rhs, bool_mode=True) == 1.0)
            out = jnp.where(cond, v, jnp.nan)
        else:
            out = apply_binary_op(self.op, lhs, rhs, self.bool_mode)
        keys = [k.drop_metric() for k in data.keys]
        return data.derive(keys, out)


@dataclass
class MiscellaneousFunctionMapper(RangeVectorTransformer):
    function: str
    args: tuple = ()

    def apply(self, data: StepMatrix) -> StepMatrix:
        if self.function == "label_replace":
            dst, repl, src, regex = self.args[:4]
            pat = re.compile(f"^(?:{regex})$")
            keys = []
            for k in data.keys:
                lm = k.label_map
                m = pat.match(lm.get(src, ""))
                if m:
                    val = m.expand(_dollar_to_backslash(repl))
                    if val:
                        lm[dst] = val
                    else:
                        lm.pop(dst, None)
                keys.append(RangeVectorKey.of(lm))
            return data.derive(keys, data.values, data.les)
        if self.function == "label_join":
            dst, sep, *srcs = self.args
            keys = []
            for k in data.keys:
                lm = k.label_map
                lm[dst] = sep.join(lm.get(s, "") for s in srcs)
                keys.append(RangeVectorKey.of(lm))
            return data.derive(keys, data.values, data.les)
        raise ValueError(f"unknown misc function {self.function}")


def _dollar_to_backslash(repl: str) -> str:
    # promql uses $1; python re.expand uses \1
    return re.sub(r"\$(\d+|\{\w+\})", lambda m: "\\" +
                  m.group(1).strip("{}"), repl)


@dataclass
class SortFunctionMapper(RangeVectorTransformer):
    descending: bool = False

    def apply(self, data: StepMatrix) -> StepMatrix:
        if data.num_series == 0:
            return data
        # sort by value at the last step with any data (prom: instant sort)
        v = np.nan_to_num(data.values[:, -1], nan=-np.inf if not
                          self.descending else np.inf)
        order = np.argsort(-v if self.descending else v, kind="stable")
        return data.derive([data.keys[i] for i in order],
                           data.values[order], data.les)


@dataclass
class AbsentFunctionMapper(RangeVectorTransformer):
    filters: tuple = ()
    start: int = 0
    step: int = 1000
    end: int = 0

    def apply(self, data: StepMatrix) -> StepMatrix:
        steps = steps_array(self.start, self.step, self.end)
        if data.num_series == 0:
            present = np.zeros(len(steps), bool)
        else:
            present = ~np.all(np.isnan(data.values), axis=0)
        out = np.where(present, np.nan, 1.0)[None, :]
        labels = {}
        from filodb_tpu.core.filters import Equals
        for f in self.filters:
            if isinstance(f.filter, Equals) and f.column != METRIC_LABEL:
                labels[f.column] = f.filter.value
        if not np.isnan(out).all():
            return StepMatrix([RangeVectorKey.of(labels)], out, steps)
        return StepMatrix([], np.zeros((0, len(steps))), steps)


@dataclass
class LimitFunctionMapper(RangeVectorTransformer):
    limit: int = 1000

    def apply(self, data: StepMatrix) -> StepMatrix:
        if data.num_series <= self.limit:
            return data
        return data.derive(data.keys[: self.limit],
                           data.values[: self.limit], data.les)
