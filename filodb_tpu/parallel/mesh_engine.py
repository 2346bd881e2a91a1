"""Device-mesh query engine: PromQL plans lowered onto SPMD mesh kernels.

The reference distributes queries by shipping exec-plan subtrees to
shard-owning nodes and gathering partial aggregates over the network
(``query/src/main/scala/filodb/query/exec/ExecPlan.scala:41``,
``PlanDispatcher.scala:31``). On a TPU pod the same computation is ONE SPMD
program over a ``(shard, time)`` ``jax.sharding.Mesh``: series are
data-parallel over the ``shard`` axis, samples sequence-parallel over the
``time`` axis, label-group reduction is a ``segment_sum`` + ``psum`` over
ICI (see ``parallel/dist_query.py`` for the kernels).

This module is the bridge from the query engine. ``MeshQueryEngine`` lowers
the plan family

    [instant-fn | scalar-op | topk]* agg?(range_fn(selector[w] offset o))
                                      by/without (labels)

— range functions with associative time combines, all aggregate ops with
associative series combines, raw/un-aggregated selectors (per-series [P, K]
output sharded over the mesh), instant-selector staleness semantics, offsets,
and instant-function / scalar-op post-transforms applied to the (tiny) mesh
output. ``execute_many`` additionally batches several lowered queries that
share a plan signature into ONE device program by concatenating their step
grids — the serving-side analog of inference micro-batching (the reference's
``QueryInMemoryBenchmark`` drives 100 concurrent queries of 4 shapes).

``QueryService(engine="mesh")`` tries this engine first and falls back to the
scatter-gather exec tree for every other plan shape.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field

import numpy as np

from filodb_tpu.parallel.dist_query import (
    MESH_AGG_OPS,
    SPLIT_FNS,
    bounds_form,
    make_mesh_bounds,
    make_mesh_eval_delta,
    make_mesh_eval_simple,
    make_mesh_group_reduce,
    make_mesh_prepare,
)
from filodb_tpu.parallel.staging import StagingPool
from filodb_tpu.query import logical as lp
from filodb_tpu.query.model import QueryStats, RangeVectorKey, StepMatrix
from filodb_tpu.utils.metrics import GaugeFn, get_counter
from filodb_tpu.utils.tracing import span, tag

log = logging.getLogger(__name__)

# mesh-engine observability: plan recognition, dispatch form and cache
# behavior (tests/test_metrics_scrape.py pins these families). Registered
# eagerly so a scrape sees the families even before the first mesh query.
_M_SUPPORTED = get_counter(
    "filodb_mesh_supported", help="plans recognized for mesh execution")
_M_UNSUPPORTED = get_counter(
    "filodb_mesh_unsupported", help="plans that fell back to the exec path "
    "at recognition time")
_M_DISPATCH = {f: get_counter("filodb_mesh_dispatch", {"form": f},
                              help="mesh batch dispatches by program form "
                              "(split pipeline; fused masked scan of "
                              "window min/max)")
               for f in ("split", "fused")}
_M_SAMPLES = get_counter(
    "filodb_mesh_samples_scanned", help="samples of the placed batch a mesh "
    "dispatch scans, once a dispatch: a batch-cache hit scans its batch "
    "again, and a batch's members share one scan")
_M_BUCKET_SAMPLES = get_counter(
    "filodb_mesh_bucket_samples_scanned", help="scalar rows of the placed "
    "batch a device program reads: a histogram sample counts once a bucket "
    "(samples x buckets), a scalar sample once; moved once a program that "
    "evaluates the placed rows (an eval-cache miss, a fused dispatch), so "
    "a dispatch the eval cache answers, which runs the group reduce alone, "
    "moves filodb_mesh_samples_scanned_total and not this")
_M_COMPILE = {e: get_counter("filodb_mesh_compile_cache", {"event": e},
                             help="compiled mesh program cache hits/misses")
              for e in ("hit", "miss")}
_M_BATCH = {e: get_counter("filodb_mesh_batch_cache", {"event": e},
                           help="decoded+placed batch cache hits/misses")
            for e in ("hit", "miss")}
_BOUNDS_HELP = ("cached window-bounds hits/misses on the split pipeline; "
                "a miss runs the bounds program, in the form ``method``")
_M_BOUNDS = {"hit": get_counter("filodb_mesh_bounds_cache", {"event": "hit"},
                                help=_BOUNDS_HELP),
             **{m: get_counter("filodb_mesh_bounds_cache",
                               {"event": "miss", "method": m},
                               help=_BOUNDS_HELP)
                for m in ("count", "search")}}
_M_EVAL = {e: get_counter("filodb_mesh_eval_cache", {"event": e},
                          help="cached per-series window evaluation "
                          "hits/misses on the split pipeline")
           for e in ("hit", "miss")}
_M_FALLBACK = {r: get_counter("filodb_mesh_fallback", {"reason": r},
                              help="mesh dispatches that fell back to the "
                              "exec path after recognition")
               for r in ("declined", "error", "shards")}
GaugeFn("filodb_mesh_hit_rate",
        lambda: _M_SUPPORTED.value / t
        if (t := _M_SUPPORTED.value + _M_UNSUPPORTED.value) else 0.0,
        help="fraction of inspected plans the mesh engine recognized")

# f32 device arithmetic keeps ≥4 fractional bits of absolute precision for
# values below 2^20 (ulp ≤ 2^-4 = 0.0625); above that, counter deltas and
# gauge cancellation degrade and the f64 host pre-correction lane
# (SeriesBatch.delta_host) takes over. Well under the 2^24 integer-exact
# limit, so integral counters are bit-exact either way.
F32_SAFE_MAX = float(1 << 20)


def _device_correction_ok(vals: np.ndarray) -> bool:
    """May counter-reset correction / delta cancellation run directly on
    the device value dtype? Always under x64; under f32, only when every
    finite value is small enough that window-scale differences keep
    absolute precision (see ``F32_SAFE_MAX``). One host pass per decoded
    batch — amortized across every query the cached batch serves."""
    import jax.numpy as jnp

    from filodb_tpu.query.engine.kernels import fdtype

    if fdtype() == jnp.float64:
        return True
    # the largest and the smallest value, NaN padding skipped, with no
    # array made on the way (empty or all NaN: the initial values stand)
    hi = np.fmax.reduce(vals, axis=None, initial=-np.inf)
    lo = np.fmin.reduce(vals, axis=None, initial=np.inf)
    if np.isfinite(hi) and np.isfinite(lo):
        return bool(max(abs(hi), abs(lo)) < F32_SAFE_MAX)
    # an infinity (or nothing finite at all) hides the largest finite
    # magnitude from the two reductions: mask, as before
    finite = vals[np.isfinite(vals)]
    return finite.size == 0 or float(np.abs(finite).max()) < F32_SAFE_MAX


def _placed_values(vals: np.ndarray, take, marks: np.ndarray) -> np.ndarray:
    """The array that is placed for a host value array ``[P, S]``, or
    ``[P, S, B]`` with the buckets flattened into the series axis
    (``[P·B, S]``, row ``p·B + b``): ONE buffer from ``take`` in the device's
    float dtype, written in one pass that transposes and rounds (numpy's
    cast rounds as ``device_put`` would), then the NaN that marks padding
    set to 0 — the kernels mask by validity, not by NaN. ``marks`` is a
    bool buffer of the placed shape to find the NaN in. Always a copy:
    ``vals`` may be ``delta_host``'s cached array and is only read; every
    element of both buffers is overwritten, whatever they held."""
    from filodb_tpu.query.engine.batch import device_float

    src = vals if vals.ndim == 2 else vals.transpose(0, 2, 1)
    out = take(marks.shape, device_float())
    np.copyto(out.reshape(src.shape), src, casting="same_kind")
    np.putmask(out, np.isnan(out, out=marks), 0)
    return out


# range functions with associative mesh combines (dist_query kernels)
MESH_FNS = ("rate", "increase", "delta", "sum_over_time", "count_over_time",
            "avg_over_time", "min_over_time", "max_over_time",
            "last_over_time", "present_over_time", "stddev_over_time",
            "stdvar_over_time")
MESH_AGGS = MESH_AGG_OPS

# value-wise instant functions safe to post-apply on the [G, K] mesh output
_POST_INSTANT_FNS = (
    "abs", "ceil", "floor", "exp", "ln", "log2", "log10", "sqrt", "round",
    "clamp", "clamp_min", "clamp_max", "sgn", "deg", "rad", "acos", "asin",
    "atan", "cos", "cosh", "sin", "sinh", "tan", "tanh",
)


def _replace(low: _Lowered, **kw) -> _Lowered:
    import dataclasses
    return dataclasses.replace(low, **kw)


def _replace_post(low: _Lowered, op: tuple) -> _Lowered:
    return _replace(low, post=low.post + (op,))


def make_query_mesh(n_devices: int | None = None, time_axis: int | None = None):
    """Build the default (shard × time) mesh over available devices.

    ``time_axis``: devices on the sample axis (sequence parallelism); default
    2 when the device count allows, else 1 — series parallelism usually
    dominates for TSDB workloads (P >> S blocks).
    """
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    n = len(devs)
    if time_axis is None:
        time_axis = 2 if n % 2 == 0 and n >= 2 else 1
    shard_axis = n // time_axis
    return Mesh(np.array(devs[: shard_axis * time_axis]).reshape(
        shard_axis, time_axis), ("shard", "time"))


@dataclass(frozen=True)
class _Lowered:
    """A plan recognized for mesh execution."""

    filters: tuple
    start: int
    step: int
    end: int
    window: int
    fn: str
    offset: int
    agg: str | None
    by: tuple
    without: tuple
    keep_metric: bool
    # post-transforms applied to the mesh output StepMatrix, innermost first:
    # ("instant", fn, args) | ("scalarop", op, scalar, lhs, bool)
    # | ("kagg", op, params, by, without)
    post: tuple = ()

    @property
    def signature(self):
        """Batching key: everything except the step grid and post ops."""
        return (self.filters, self.window, self.fn, self.offset, self.agg,
                self.by, self.without, self.keep_metric, self.step)


@dataclass
class MeshQueryEngine:
    """Compiles + caches distributed query steps per (fn, agg, G-bucket).

    Shapes bucket to powers of two (series count, sample count, step count,
    group count) so repeated queries reuse compiled programs — the mesh
    analog of the exec path's batch-shape bucketing.
    """

    mesh: object = None
    # prepare-stage sidecar delegation (engine/sidecar_lane.py): tick-shaped
    # grids (K ≤ 2 steps — rule ticks and alert probes evaluate at a single
    # instant) over eligible range functions are declined here so the exec
    # leaf folds them from chunk aggregate sidecars in O(chunks) —
    # per-evaluation device prep (decode + upload) never amortizes at K≈1.
    # Wider grids keep the device pipeline and its warm split caches. Off by
    # default so direct-constructed engines keep the pure device path;
    # QueryService turns it on for production-facing engines.
    sidecars: bool = False

    _fns: dict = field(default_factory=dict)
    # PLACED global batches are reused across queries over unchanged data
    # (the mesh analog of the exec path's per-shard batch cache). An entry
    # is (version, BatchHeader, keys, gids, out_keys, placed, is_counter):
    # the device arrays and what a hit reads beside them — never the host
    # ``ts``/``vals``, whose memory went back to ``_staging``
    _batch_cache: dict = field(default_factory=dict)
    _batch_cache_cap: int = 16
    # the [P, S] host arrays one placement needs (build_batch's ts/vals,
    # the validity mask, the split lane's converted copy, a histogram's
    # bucket rows), taken back once the placed arrays are ready
    _staging: StagingPool = field(default_factory=StagingPool)
    # step-grid device arrays keyed by their bytes: repeated queries
    # re-upload identical grids every batch otherwise (a host→device
    # transfer per chunk)
    _grid_cache: dict = field(default_factory=dict)
    _grid_cache_cap: int = 64
    # split-pipeline device caches: prepared per-batch-version arrays
    # (counter correction / prefix sums), window bounds per (batch
    # version, grid, window), and per-series evaluated windows per (batch
    # version, grid, window, fn) — the passes that otherwise dominate a
    # warm query's device time (see dist_query "split pipeline" section).
    # Caps are deliberately small: entries scale with the batch (bounds
    # ~2·P·K int32, eval ~P·K float), so a handful of distinct dashboards
    # already costs hundreds of MB at big-scan sizes.
    _prep_cache: dict = field(default_factory=dict)
    _prep_cache_cap: int = 4
    _bounds_cache: dict = field(default_factory=dict)
    _bounds_cache_cap: int = 16
    _eval_cache: dict = field(default_factory=dict)
    _eval_cache_cap: int = 32
    # mesh-hit accounting
    hits: int = 0
    misses: int = 0

    def ensure_mesh(self):
        """Build the default mesh over this process's devices, once.

        Not done at construction: an accelerator belongs to one process,
        and CLI tools, coordinators over remote members and the roots of
        worker pools all build a ``QueryService`` without ever running a
        device program. A server that WILL serve from the mesh calls this
        at start (``FiloServer.start``), so a device it cannot reach fails
        the boot instead of the first query."""
        if self.mesh is None:
            self.mesh = make_query_mesh()
        return self.mesh

    # ---- plan recognition ------------------------------------------------

    def supports(self, plan) -> bool:
        ok = self._lower(plan) is not None
        self._note(ok)
        return ok

    def _note(self, ok: bool) -> None:
        if ok:
            self.hits += 1
            _M_SUPPORTED.inc()
        else:
            self.misses += 1
            _M_UNSUPPORTED.inc()

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def _lower(self, plan) -> _Lowered | None:
        low = self._lower_plan(plan)
        if low is not None and self.sidecars \
                and (low.end - low.start) // max(low.step, 1) + 1 <= 2:
            from filodb_tpu.query.engine import sidecar_lane
            if sidecar_lane.covers_fn(low.fn):
                return None  # sidecar delegation (see ``sidecars`` field)
        return low

    def _lower_plan(self, plan) -> _Lowered | None:
        """Recognize a plan for mesh execution (None = exec-path fallback)."""
        # wrappers peel off into post-transforms (applied to the small
        # [G|P, K] mesh output, so any value-wise op is safe)
        if isinstance(plan, lp.ApplyInstantFunction) \
                and plan.function in _POST_INSTANT_FNS \
                and all(isinstance(a, (int, float)) for a in plan.args):
            inner = self._lower(plan.vector)
            if inner is None:
                return None
            return _replace_post(inner, ("instant", plan.function,
                                         tuple(plan.args)))
        if isinstance(plan, lp.ApplyInstantFunction) \
                and plan.function == "histogram_quantile":
            # histogram_quantile(φ, sum(rate(hist[5m])) by (...)) runs
            # fully on the mesh: bucket-rate partials are associative, so
            # buckets flatten into the series axis (see
            # execute_lowered_many) and the quantile is a tiny [G, K, B]
            # post-transform (reference first-class-histogram query path,
            # ``HistogramQuantileMapper`` + README.md:437 claim)
            args = [a.value if isinstance(a, lp.ScalarFixedDoublePlan)
                    else a for a in plan.args]
            if len(args) != 1 or not isinstance(args[0], (int, float)):
                return None
            inner = self._lower(plan.vector)
            if inner is None:
                return None
            return _replace_post(inner, ("instant", "histogram_quantile",
                                         (float(args[0]),)))
        if isinstance(plan, lp.ScalarVectorBinaryOperation):
            sc = plan.scalar
            if isinstance(sc, lp.ScalarFixedDoublePlan):
                sc = sc.value
            if isinstance(sc, (int, float)):
                inner = self._lower(plan.vector)
                if inner is None:
                    return None
                return _replace_post(inner, ("scalarop", plan.op, float(sc),
                                             plan.scalar_is_lhs,
                                             plan.bool_mode))
            return None
        if isinstance(plan, lp.Aggregate) and plan.op in ("topk", "bottomk") \
                and len(plan.params) == 1:
            inner = self._lower(plan.vector)
            if inner is None or inner.post:
                return None
            return _replace_post(inner, ("kagg", plan.op, plan.params,
                                         plan.by, plan.without))
        if isinstance(plan, lp.Aggregate):
            if plan.op not in MESH_AGGS or plan.params:
                return None
            core = self._lower_periodic(plan.vector)
            if core is None or core.agg is not None:
                return None
            return _replace(core, agg=plan.op, by=tuple(plan.by),
                            without=tuple(plan.without))
        return self._lower_periodic(plan)

    def _lower_periodic(self, plan) -> _Lowered | None:
        if isinstance(plan, lp.PeriodicSeriesWithWindowing):
            if plan.function not in MESH_FNS or plan.params \
                    or plan.at_ms is not None:
                return None
            raw = plan.raw
            if not isinstance(raw, lp.RawSeries) or raw.column is not None:
                return None
            # the parser records the selector offset on BOTH the periodic
            # node and the raw selector — one value, not additive
            return _Lowered(tuple(raw.filters), plan.start, plan.step,
                            plan.end, plan.window, plan.function,
                            plan.offset or raw.offset, None, (), (), False)
        if isinstance(plan, lp.PeriodicSeries):
            if plan.at_ms is not None:
                return None
            raw = plan.raw
            if not isinstance(raw, lp.RawSeries) or raw.column is not None:
                return None
            lookback = raw.lookback or 300_000
            return _Lowered(tuple(raw.filters), plan.start, plan.step,
                            plan.end, lookback, "last_sample",
                            plan.offset or raw.offset, None, (), (), True)
        return None

    # ---- execution -------------------------------------------------------

    def execute(self, memstore, dataset: str, plan,
                stats: QueryStats | None = None) -> StepMatrix | None:
        """Run a supported plan on the mesh; ``None`` = fall back to the
        exec path (histogram data or other shapes the kernels don't cover).
        """
        low = self._lower(plan)
        if low is None:
            return None
        out = self.execute_lowered_many([low], memstore, dataset, stats)
        return out[0]

    def execute_many(self, plans: list, memstore, dataset: str,
                     stats_list: list | None = None) -> list:
        """Evaluate many plans, batching those that share a signature into
        one device program (concatenated step grids). Returns a StepMatrix
        (or None = unsupported) per plan, in order."""
        lows = [self._lower(p) for p in plans]
        results: list = [None] * len(plans)
        groups: dict[tuple, list[int]] = {}
        for i, low in enumerate(lows):
            self._note(low is not None)
            if low is not None:
                groups.setdefault(low.signature, []).append(i)
        for idxs in groups.values():
            outs = self.execute_lowered_many(
                [lows[i] for i in idxs], memstore, dataset,
                [stats_list[i] for i in idxs] if stats_list else None)
            for i, out in zip(idxs, outs):
                results[i] = out
        return results

    def execute_lowered_many(self, lows: list[_Lowered], memstore,
                             dataset: str,
                             stats: "QueryStats | list | None" = None
                             ) -> list:
        """Evaluate lowered plans sharing a signature (same selector/fn/agg;
        step grids may differ) over ONE placed batch. Returns one StepMatrix
        (or None) per entry. ``stats`` is one QueryStats (single query) or a
        list aligned with ``lows`` — every query in the group scanned the
        whole shared batch, so each gets the full scan counts.

        Phase spans, in order, tiling the caller's ``mesh-execute``:
        ``mesh-lookup`` (posting lists, paging), ``decode`` (``build_batch``:
        every sample written once into arrays of the placed shape — and, on
        the ``raw`` lane, the placed dtype — that come from ``_staging``,
        tag ``reused_bytes`` on ``batch-stack``), ``mesh-group`` (keys and
        group ids at the placed length), ``mesh-pad`` (no padding any more:
        the lane choice, the ``split`` lane's conversion of the f64 batch
        or, where the device dtype cannot correct it, its host f64 pre-pass
        — tag ``copied_bytes``, 0 on the ``raw`` lane — the histogram
        flatten, in a child span ``hist-flatten`` of its own where the
        batch has buckets and converting in the same pass, the validity
        mask: every array of the placed size it writes is a staging
        buffer too, tag ``reused_bytes`` on both spans),
        ``mesh-place`` (the put: the batch's own ``ts``/``vals`` on the
        ``raw`` lane); a batch-cache hit opens none of these five and takes
        nothing from the pool. Then ``mesh-dispatch``, ``mesh-fetch`` — at
        whose end a miss waits for its placed arrays and gives the staging
        buffers back, the one point where they return — ``mesh-assemble``
        (tag ``buckets`` on a histogram batch; a ``histogram_quantile``
        post-transform runs under ``hist-quantile`` inside it)."""
        stats_objs = stats if isinstance(stats, list) \
            else ([stats] if stats is not None else [])
        from filodb_tpu.core.memstore.odp import page_partitions
        from filodb_tpu.parallel.dist_query import (
            make_distributed_range_agg,
            shard_batch_arrays,
        )
        from filodb_tpu.query.engine.batch import build_batch
        from filodb_tpu.query.engine.device_batch import _pow2
        from filodb_tpu.query.exec.transformers import steps_array

        low0 = lows[0]
        mesh = self.ensure_mesh()
        fn = "last_over_time" if low0.fn == "last_sample" else low0.fn
        # union data range across the batch (offset shifts evaluation back)
        chunk_start = min(lo.start for lo in lows) - low0.window - low0.offset
        chunk_end = max(lo.end for lo in lows) - low0.offset

        shards = memstore.shards_for(dataset)
        version = sum(s.data_version for s in shards)
        # split pipeline (prepare/bounds/eval/reduce, dist_query.py):
        # correction, prefixes, window bounds and evaluated windows are
        # cached on device across queries. Window min/max have no prefix
        # form: they run the masked-scan program, whole, every query.
        use_split = fn in SPLIT_FNS
        # what is placed, and so part of the cache key. ``raw``: the
        # values as stored. ``split`` (the delta family): the same raw
        # values when the batch's magnitudes let the device dtype correct
        # counter resets and cancel bases itself (_device_correction_ok);
        # otherwise the host's f64 pre-pass (SeriesBatch.delta_host:
        # reset-corrected for rate/increase and for delta on a COUNTER
        # schema, as the exec transformers do; rebased only for delta on
        # a gauge) with, for rate/increase, the raw values beside it.
        lane = "split" if fn in ("rate", "increase", "delta") else "raw"
        # the agg NAME is part of the key (not just agg-vs-none): a
        # histogram batch cached under sum must not satisfy a later
        # min/max/avg over the same selector — those fall back to the
        # exec path, and the cache-hit branch must re-make that decision
        ckey = (dataset, str(low0.filters), chunk_start, chunk_end,
                low0.by, low0.without, low0.agg, lane)
        # split-pipeline device caches (prepare/bounds/eval) consume only
        # the data tensors, never the grouping — keyed WITHOUT agg/by so
        # e.g. sum() and avg() over the same rate() share one evaluation
        dkey = (dataset, str(low0.filters), chunk_start, chunk_end, lane)
        cached = self._batch_cache.get(ckey)
        _M_BATCH["hit" if cached is not None and cached[0] == version
                 else "miss"].inc()
        lease = None
        if cached is not None and cached[0] == version:
            _, batch, keys, gids, out_keys, placed, is_counter = cached
            if batch is None:
                return [StepMatrix.empty(steps_array(lo.start, lo.step,
                                                     lo.end))
                        for lo in lows]
            if batch.is_histogram and low0.agg not in (None, "sum"):
                return [None] * len(lows)
            samples = int(batch.counts.sum())
            _M_SAMPLES.inc(samples)
            for st in stats_objs:
                st.series_scanned += len(keys)
                st.samples_scanned += samples
        else:
            placed = None
            # the host arrays of THIS placement; dropped, not given back,
            # by any return or exception before the end of mesh-fetch
            lease = self._staging.lease()
            parts = []
            extra_by_obj: dict[int, list] = {}
            with span("mesh-lookup", shards=len(shards)) as sp:
                for shard in shards:
                    sparts = []
                    for pid in shard.lookup_partitions(
                            list(low0.filters), chunk_start, chunk_end):
                        p = shard.partition(pid)
                        if p is not None:
                            sparts.append(p)
                    # on-demand paging: cold chunks (flushed + evicted-from-
                    # RAM, or pre-restart data recovered only to the column
                    # store) are merged exactly like the exec path does
                    # (plan.py) — keyed by object identity because part_ids
                    # repeat across shards
                    if sparts and shard.config.demand_paging_enabled:
                        extra = page_partitions(shard, sparts, chunk_start,
                                                chunk_end, shard.odp_cache)
                        if extra:
                            for p in sparts:
                                ec = extra.get(p.part_id)
                                if ec:
                                    extra_by_obj[id(p)] = ec
                    parts.extend(sparts)
                if sp is not None:
                    sp.tags.update(partitions=len(parts),
                                   paged=len(extra_by_obj))
            if not parts:
                self._cache_put(ckey, (version, None, [], None, [], None,
                                       False))
                return [StepMatrix.empty(steps_array(lo.start, lo.step,
                                                     lo.end))
                        for lo in lows]
            with span("decode", partitions=len(parts)) as sp:
                # the batch is built at the shape that is placed, and on
                # the raw lane (no host f64 pass follows) in the dtype too
                built = build_batch(
                    parts, chunk_start, chunk_end,
                    extra_by_obj=extra_by_obj or None,
                    mesh_multiples=(mesh.shape["shard"], mesh.shape["time"]),
                    host_f64=lane != "raw", alloc=lease.take)
                # what the cached entry keeps, and what a hit reads
                batch = built.header()
                samples = int(batch.counts.sum())
                if sp is not None:
                    sp.tags.update(samples=samples,
                                   shape=list(built.vals.shape))
            # counter-ness of the scanned value column (same source the
            # exec path reads): decides delta's reset-correction semantics
            sdata = parts[0].schema.data
            is_counter = bool(sdata.columns[sdata.value_column].is_counter)
            if batch.is_histogram and low0.agg not in (None, "sum"):
                # bucket-wise semantics only defined for sum (and raw)
                return [None] * len(lows)
            _M_SAMPLES.inc(samples)
            for st in stats_objs:
                st.series_scanned += len(parts)
                st.samples_scanned += samples
            # label grouping (first-occurrence order, like
            # AggregateMapReduce). The metric label is dropped first — the
            # exec path drops it in range-function output keys before
            # grouping, so `by (_metric_)` must group on nothing there too.
            with span("mesh-group") as sp:
                keys = [p.part_key.range_vector_key for p in parts]
                # one id a padded row: padding series join group 0 and
                # contribute nothing (no valid samples)
                gids = np.zeros(built.ts.shape[0], np.int32)
                if low0.agg is None:
                    out_keys = []
                else:
                    gkeys = [self._group_key(k, low0) for k in keys]
                    uniq: dict[RangeVectorKey, int] = {}
                    for i, gk in enumerate(gkeys):
                        gids[i] = uniq.setdefault(gk, len(uniq))
                    out_keys = list(uniq.keys())
                if sp is not None:
                    sp.tags["groups"] = len(out_keys)
        # histogram batches flatten buckets into the series axis: every
        # (series, bucket) pair becomes one scalar row, group ids become
        # g*B + b, and the same associative kernels/combines apply. The
        # output un-flattens to [rows, K, B].
        B = batch.buckets
        # delta mirrors the exec kernels: reset-corrected on counter
        # schemas, raw differences on gauges (rate/increase always correct)
        delta_counter = fn == "delta" and is_counter
        G = len(out_keys)
        Gp = _pow2(max(G * B, 1))

        # per-plan step grids, each padded to a power of two for compile
        # reuse (window evaluations are independent per step — batching
        # queries = concatenating steps)
        all_steps = []
        spans = []
        for lo in lows:
            steps_ms = steps_array(lo.start, lo.step, lo.end)
            K = len(steps_ms)
            Kp = _pow2(K)
            rel = np.empty(Kp, np.int32)
            rel[:K] = (steps_ms - lo.offset - batch.base_ts).astype(np.int32)
            rel[K:] = rel[K - 1]
            spans.append((Kp, K, steps_ms))
            all_steps.append(rel)

        if placed is None:
            with span("mesh-pad", lane=lane) as sp:
                ts_p, counts_p, gid_p = built.ts, built.counts, gids
                raw_vals = None
                if lane == "raw" or _device_correction_ok(built.vals):
                    # raw values go straight to the device; on the split
                    # lane the counter correction is the cached prepare
                    # program's (make_mesh_prepare), so no host pre-pass
                    # runs at all
                    host_vals = built.vals
                else:
                    counter = fn in ("rate", "increase") or delta_counter
                    host_vals = built.delta_host(counter=counter)
                    if fn in ("rate", "increase"):
                        # rate/increase also need the raw values for the
                        # extrapolate-to-zero clamp (heuristic-only reference;
                        # delta never clamps, even when reset-corrected)
                        raw_vals = built.vals
                Pp_, S_ = ts_p.shape
                # every [P·B, S] array made here is the lease's, to go back
                # with the builder's. The mask's buffer first holds the NaN
                # marks of each converted copy
                valid = lease.take((Pp_ * B, S_), np.bool_)

                def placed_values(vals):
                    return None if vals is None \
                        else _placed_values(vals, lease.take, valid)

                if B > 1:
                    with span("hist-flatten", rows=Pp_ * B,
                              buckets=B) as fsp:
                        vals_p = placed_values(host_vals)
                        raw_p = placed_values(raw_vals)
                        ts_p = lease.take(valid.shape, ts_p.dtype)
                        np.copyto(ts_p.reshape(Pp_, B, S_),
                                  built.ts[:, None, :])
                        counts_p = np.repeat(counts_p, B)
                        gid_p = (gid_p[:, None] * B + np.arange(
                            B, dtype=np.int32)[None, :]).reshape(-1)
                        if fsp is not None:
                            fsp.tags["bytes"] = sum(
                                a.nbytes for a in (vals_p, raw_p, ts_p,
                                                   counts_p, gid_p)
                                if a is not None)
                elif lane == "raw":
                    # a scalar batch of the raw lane was built in the
                    # placed dtype with 0 padding: it is placed as it is
                    vals_p, raw_p = host_vals, None
                else:
                    vals_p = placed_values(host_vals)
                    raw_p = placed_values(raw_vals)
                # counts do not shard along the time axis: a mask does
                # (an int32 range, as counts are: numpy would widen both
                # sides to int64 first, three times the compare's time)
                np.less(np.arange(S_, dtype=np.int32)[None, :],
                        counts_p[:, None], out=valid)
                if sp is not None:
                    # the [P,S] arrays this phase made beside the mask:
                    # what is placed and is not the builder's own array
                    sp.tags.update(
                        shape=list(vals_p.shape),
                        copied_bytes=sum(
                            a.nbytes for a in (ts_p, vals_p, raw_p)
                            if a is not None and a is not built.ts
                            and a is not built.vals))
            with span("mesh-place") as sp:
                placed = shard_batch_arrays(mesh, ts_p, vals_p, valid,
                                            gid_p, raw_p)
                if sp is not None:
                    sp.tags["bytes"] = sum(
                        a.nbytes for a in (ts_p, vals_p, valid, gid_p,
                                           raw_p) if a is not None)
            self._cache_put(ckey, (version, batch, keys, gids, out_keys,
                                   placed, is_counter))

        with span("mesh-dispatch",
                  form="split" if use_split else "fused") as sp:
            agg = low0.agg
            if use_split:
                # per-query work is ONLY the group reduce; window evaluation
                # is served from the eval cache (see the chunk loop below)
                step_fn = None if agg is None else self._get_fn(
                    ("split-reduce", agg, Gp),
                    lambda: make_mesh_group_reduce(mesh, Gp, agg))
            else:
                step_fn = self._get_fn(
                    (fn, agg, Gp if agg else None),
                    lambda: make_distributed_range_agg(mesh, fn, Gp, agg))
            _M_DISPATCH["split" if use_split else "fused"].inc()

            import jax
            import jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec

            # replicated small operands are PINNED to the mesh's devices:
            # the default backend may be a different platform (e.g. a
            # CPU mesh inside a TPU process), and a default-placed
            # operand would drag cross-backend transfers into every call
            repl = NamedSharding(mesh, PartitionSpec())
            win_d = jax.device_put(np.int32(low0.window), repl)
            ts_d, vals_d, valid_d, gid_d = placed[:4]
            raw_d = placed[4] if len(placed) > 4 else None

            # split pipeline: prepared per-version device arrays
            # (correction / prefixes), reused by every query over this
            # batch version
            split_cv = None
            split_prefix = None
            if use_split:
                if fn in ("rate", "increase") or delta_counter:
                    split_cv = self._prepared(dkey, version, "counter", mesh,
                                              vals_d, valid_d)
                elif fn != "delta":
                    split_prefix = self._prepared(dkey, version, "prefix",
                                                  mesh, vals_d, valid_d)

            # Fixed call shapes: compile storms would otherwise follow the
            # batch size (every distinct ΣKp is a fresh program). Queries
            # grouped by Kp run in chunks of exactly 1 or GROUP (grids
            # repeated to fill), so each (signature, Kp) compiles at most
            # twice ever.
            GROUP = 8
            by_kp: dict[int, list[int]] = {}
            for i, (Kp, _, _) in enumerate(spans):
                by_kp.setdefault(Kp, []).append(i)
            results: list = [None] * len(lows)
            nrows = (G if agg else len(keys)) * B
            # phase 1: dispatch every chunk's device program (async —
            # results stay lazy on device so compute overlaps across chunks)
            calls: list[tuple] = []
            for Kp, idxs in by_kp.items():
                pos = 0
                while pos < len(idxs):
                    chunk = idxs[pos : pos + GROUP]
                    pos += GROUP
                    size = 1 if len(chunk) == 1 else GROUP
                    grids = [all_steps[i] for i in chunk]
                    grids += [grids[-1]] * (size - len(chunk))
                    blob = np.concatenate(grids)
                    gkey = blob.tobytes()
                    grid_d = self._grid_cache.get(gkey)
                    if grid_d is None:
                        if len(self._grid_cache) >= self._grid_cache_cap:
                            self._grid_cache.pop(
                                next(iter(self._grid_cache)))
                        grid_d = self._grid_cache[gkey] = jax.device_put(
                            blob, repl)
                    if use_split:
                        ev_d = self._series_eval_cached(
                            dkey, version, low0.window, gkey, fn, mesh, ts_d,
                            vals_d, valid_d, grid_d, win_d, split_cv,
                            split_prefix, raw_d, delta_counter,
                            scanned=samples * B)
                        out = ev_d if step_fn is None \
                            else step_fn(ev_d, gid_d)
                    else:
                        _M_BUCKET_SAMPLES.inc(samples * B)
                        out = step_fn(ts_d, vals_d, valid_d, gid_d, grid_d,
                                      win_d)
                    calls.append((out, chunk, Kp))
            if sp is not None:
                sp.tags["programs"] = len(calls)
        with span("mesh-fetch") as sp:
            # phase 2: coalesced device→host fetch — one transfer per
            # distinct output shape (per-query slicing on device would cost
            # a dispatch + a blocking fetch each)
            by_shape: dict[tuple, list[int]] = {}
            for ci, (out, _, _) in enumerate(calls):
                by_shape.setdefault(out.shape, []).append(ci)
            fetched: dict[int, np.ndarray] = {}
            for cis in by_shape.values():
                if len(cis) == 1:
                    fetched[cis[0]] = np.asarray(calls[cis[0]][0])
                else:
                    stacked = np.asarray(jnp.stack(
                        [calls[ci][0] for ci in cis]))
                    for j, ci in enumerate(cis):
                        fetched[ci] = stacked[j]
            if sp is not None:
                sp.tags["bytes"] = sum(a.nbytes
                                       for a in fetched.values())
            if lease is not None:
                # the puts only enqueued, and a program may never have read
                # the new arrays (an eval-cache hit under the same dkey):
                # the host memory is free to overwrite once they are ready
                jax.block_until_ready(placed)
                lease.give_back()
        with span("mesh-assemble", rows=nrows,
                  **({"buckets": B} if B > 1 else {})):
            for ci, (_, chunk, Kp) in enumerate(calls):
                out_np = fetched[ci]
                for j, i in enumerate(chunk):
                    lo = lows[i]
                    _, K, steps_ms = spans[i]
                    vals = out_np[:nrows, j * Kp : j * Kp + K]
                    if B > 1:  # un-flatten buckets: [n*B, K] -> [n, K, B]
                        vals = np.ascontiguousarray(
                            vals.reshape(-1, B, K).transpose(0, 2, 1))
                    if agg is None:
                        rkeys = keys if lo.keep_metric \
                            else [k.drop_metric() for k in keys]
                    else:
                        rkeys = out_keys
                    m = StepMatrix(list(rkeys), vals, steps_ms,
                                   batch.les if B > 1 else None)
                    results[i] = self._apply_post(m, lo)
        return results

    def _cache_put(self, ckey, entry):
        if len(self._batch_cache) >= self._batch_cache_cap:
            self._batch_cache.pop(next(iter(self._batch_cache)))
        self._batch_cache[ckey] = entry

    def _get_fn(self, key, builder):
        """Compiled-program cache with hit/miss accounting."""
        fn = self._fns.get(key)
        if fn is None:
            _M_COMPILE["miss"].inc()
            fn = self._fns[key] = builder()
        else:
            _M_COMPILE["hit"].inc()
        return fn

    def _prepared(self, dkey, version, kind, mesh, vals_d, valid_d):
        """Device-resident prepared arrays for the split pipeline, one
        entry per (batch cache key, kind), invalidated by data version.
        ``kind="counter"``: corrected values; ``"prefix"``: (csum, cnt,
        csum2) exclusive prefixes."""
        key = (dkey, kind)
        hit = self._prep_cache.get(key)
        if hit is not None and hit[0] == version:
            return hit[1]
        prep_fn = self._get_fn(("prep", kind),
                               lambda: make_mesh_prepare(mesh, kind))
        out = prep_fn(vals_d, valid_d)
        if len(self._prep_cache) >= self._prep_cache_cap:
            self._prep_cache.pop(next(iter(self._prep_cache)))
        self._prep_cache[key] = (version, out)
        return out

    def _window_bounds_cached(self, dkey, version, window, grid_bytes,
                              mesh, ts_d, grid_d, win_d):
        """Cached (lo, hi) window bounds per (batch version, step grid,
        window): their inputs change only when data or the query grid do.
        A miss runs the bounds program and says in which form (the span's
        ``bounds`` tag, the counter's ``method``)."""
        bkey = (dkey, version, window, grid_bytes)
        hit = self._bounds_cache.get(bkey)
        if hit is not None:
            _M_BOUNDS["hit"].inc()
            return hit
        form = bounds_form(mesh)
        tag("bounds", form)
        _M_BOUNDS[form].inc()
        bounds_fn = self._get_fn(("bounds",), lambda: make_mesh_bounds(mesh))
        out = bounds_fn(ts_d, grid_d, win_d)
        if len(self._bounds_cache) >= self._bounds_cache_cap:
            self._bounds_cache.pop(next(iter(self._bounds_cache)))
        self._bounds_cache[bkey] = out
        return out

    def _series_eval_cached(self, dkey, version, window, grid_bytes, fn,
                            mesh, ts_d, vals_d, valid_d, grid_d, win_d,
                            split_cv, split_prefix, raw_d,
                            delta_counter=False, scanned=0):
        """Cached per-series evaluated windows [P, K] per (batch version,
        step grid, window, fn) — the boundary gathers + time combine that
        remain the dominant per-query device cost once bounds are cached.
        Nothing here depends on the query's grouping, so every agg over
        the same inner range function shares one entry and a warm query
        runs only the group reduce. A miss, which runs the programs over
        the placed rows, moves ``filodb_mesh_bucket_samples_scanned_total``
        by ``scanned``, the batch's samples x buckets; a hit reads none of
        them and moves nothing."""
        ekey = (dkey, version, window, grid_bytes, fn)
        hit = self._eval_cache.get(ekey)
        tag("eval_cache", "miss" if hit is None else "hit")
        if hit is not None:
            _M_EVAL["hit"].inc()
            return hit
        _M_EVAL["miss"].inc()
        _M_BUCKET_SAMPLES.inc(scanned)
        lo_d, hi_d = self._window_bounds_cached(dkey, version, window,
                                                grid_bytes, mesh, ts_d,
                                                grid_d, win_d)
        if fn in ("rate", "increase", "delta"):
            # delta-on-counter compiles its own corrected variant; the
            # dkey's filters pin the schema, so the eval cache key needs
            # no extra discriminator
            counter = fn in ("rate", "increase") or delta_counter
            ev_fn = self._get_fn(
                ("eval", fn, counter),
                lambda: make_mesh_eval_delta(mesh, fn, counter=counter))
            out = ev_fn(ts_d, vals_d, valid_d, lo_d, hi_d, grid_d, win_d,
                        cv=split_cv, raw=raw_d)
        else:
            ev_fn = self._get_fn(("eval", fn),
                                 lambda: make_mesh_eval_simple(mesh, fn))
            cs_d, cn_d, cs2_d = split_prefix
            out = ev_fn(ts_d, vals_d, valid_d, cs_d, cn_d, cs2_d, lo_d,
                        hi_d, grid_d, win_d)
        if len(self._eval_cache) >= self._eval_cache_cap:
            self._eval_cache.pop(next(iter(self._eval_cache)))
        self._eval_cache[ekey] = out
        return out

    @staticmethod
    def _group_key(k: RangeVectorKey, low: _Lowered) -> RangeVectorKey:
        base = k.drop_metric()
        if low.without:
            return base.without(low.without)
        return base.only(low.by)

    @staticmethod
    def _apply_post(m: StepMatrix, low: _Lowered) -> StepMatrix:
        if not low.post:
            return m.compact() if low.agg is not None else m
        from filodb_tpu.query.exec.transformers import (
            AggregateMapReduce,
            InstantVectorFunctionMapper,
            ScalarOperationMapper,
        )

        for op in low.post:
            if op[0] == "instant":
                mapper = InstantVectorFunctionMapper(op[1], op[2])
                if op[1] == "histogram_quantile":
                    with span("hist-quantile", groups=m.num_series,
                              steps=m.num_steps,
                              buckets=m.values.shape[2] if m.is_histogram
                              else 0):
                        m = mapper.apply(m)
                else:
                    m = mapper.apply(m)
            elif op[0] == "scalarop":
                m = ScalarOperationMapper(op=op[1], scalar=op[2],
                                          scalar_is_lhs=op[3],
                                          bool_mode=op[4]).apply(m)
            elif op[0] == "kagg":
                m = AggregateMapReduce(op=op[1], params=op[2], by=op[3],
                                       without=op[4]).apply(m)
        return m
