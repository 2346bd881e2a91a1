"""Step-aligned range-query splitting with an immutable-extent result cache.

The Cortex/Thanos query-frontend pattern, built into ``QueryService``: a
range query's step grid is split at step-aligned *extent* boundaries, each
extent evaluated as an independent sub-query, and per-extent result
matrices cached keyed on a canonical (time-blanked) logical-plan signature
plus the extent bounds. Because a sub-query's logical plan keeps its
``lookback``/``window``/``offset`` fields and the planner widens the chunk
scan by them at materialization (``SingleClusterPlanner._leaves``), range
functions (``rate``, ``increase``, ``*_over_time``) are exact at extent
seams — no samples are missing from any window that straddles a boundary.

Invalidation is the core trick: extents that end at or before the dataset's
**mutable horizon** (min over local shards of the max ingested timestamp,
minus a configurable out-of-order allowance) can never be changed by
further ingest, so they are cached with NO version stamp — ingest cannot
orphan them. Only the head extent past the horizon carries the dataset's
``data_version`` and is recomputed whenever ingest has advanced. This is
what makes the cache effective under live ingestion, where the exact-match
rendered-response cache (``filodb_tpu/http/server.py``) has ~0% hit rate
(its stamp bumps on every row).

Each extent is evaluated on its FULL aligned grid (``extent_steps`` steps),
cached once, and sliced to the requested sub-range at merge time. Partial
head/edge extents would otherwise produce a different step count every
dashboard refresh — a fresh XLA compile per refresh on the batched kernel
path — while full extents give every sub-query the same shape and let
queries with different (same-phase) starts share entries.

Splicing is *semantics-preserving*, not bit-identical: the windowed kernels
are prefix-sum based, so evaluating the same step over a different chunk
batch can differ in the last ulp. Absent-series fill is NaN, which matches
the aggregation kernels' ``cnt == 0 → NaN`` convention exactly.

Anything the splitter can't prove safe bypasses the cache wholesale:
instant queries (step 0), subqueries, ``absent()``/``absent_over_time``,
``sort``/``limit`` (cross-extent ordering), ``@`` modifiers and negative
offsets (extent immutability undecidable), metadata plans, and any result
that comes back partial or with warnings (PR 1 degraded scatter-gather) is
never stored.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import OrderedDict

import numpy as np

from filodb_tpu.query import logical as lp
from filodb_tpu.query.model import (
    QueryContext,
    QueryResult,
    QueryStats,
    StepMatrix,
)
from filodb_tpu.utils.metrics import Gauge, get_counter
from filodb_tpu.utils.tracing import span

cache_hits = get_counter("filodb_result_cache_hits")
cache_misses = get_counter("filodb_result_cache_misses")
cache_partial_hits = get_counter("filodb_result_cache_partial_hits")
cache_evictions = get_counter("filodb_result_cache_evictions")
cache_bytes = Gauge("filodb_result_cache_bytes")

# Predicted recompute wall time below which an extent admits at low
# priority (it's cheaper to recompute than the cache space it occupies).
_CHEAP_RECOMPUTE_S = 0.002


@dataclasses.dataclass
class ResultCacheConfig:
    """``result_cache`` config block (``filodb_tpu.config.DEFAULTS``)."""

    enabled: bool = True
    # extent length in steps; dashboards advancing one step per refresh
    # recompute only the head extent plus at most one partial edge extent
    extent_steps: int = 32
    # byte budget for cached matrices (LRU beyond it)
    max_bytes: int = 256 * 1024 * 1024
    # how far behind the max ingested timestamp a row may still arrive;
    # extents ending earlier than (max_ts - allowance) are immutable
    ooo_allowance_ms: int = 300_000

    @staticmethod
    def from_dict(d: dict) -> "ResultCacheConfig":
        known = {f.name for f in dataclasses.fields(ResultCacheConfig)}
        return ResultCacheConfig(**{k: v for k, v in d.items() if k in known})


# Plan node types that make a query unsplittable. Subqueries re-sample the
# inner plan on their own grid; absent() needs the whole range to decide
# emptiness; sort/limit order or truncate series by values across the whole
# range, which splicing would not preserve.
_BYPASS_NODES = (
    lp.SubqueryWithWindowing,
    lp.TopLevelSubquery,
    lp.ApplyAbsentFunction,
    lp.ApplySortFunction,
    lp.ApplyLimitFunction,
    lp.RawChunkMeta,
    lp.LabelValues,
    lp.LabelNames,
    lp.SeriesKeysByFilters,
)


def splittable_grid(plan: lp.LogicalPlan) -> tuple[int, int, int] | None:
    """The single (start, step, end) grid every periodic node of ``plan``
    evaluates on, or None when the plan must bypass the splitter."""
    grids: list[tuple[int, int, int]] = []
    ok = True

    def walk(p):
        nonlocal ok
        if not ok:
            return
        if isinstance(p, _BYPASS_NODES):
            ok = False
            return
        if isinstance(p, lp.RawSeries):
            # a bare selector (no periodic sampling) returns raw samples;
            # its output is not on a step grid
            ok = False
            return
        if isinstance(p, (lp.PeriodicSeries, lp.PeriodicSeriesWithWindowing)):
            if p.at_ms is not None or p.offset < 0 or p.raw.offset < 0 \
                    or p.step <= 0 or p.end < p.start:
                # @ fixes evaluation time (extent immutability is about the
                # evaluation window, which @ decouples from the grid);
                # negative offsets read the future relative to the extent
                ok = False
                return
            grids.append((p.start, p.step, p.end))
            return
        if dataclasses.is_dataclass(p):
            for f in dataclasses.fields(p):
                v = getattr(p, f.name)
                if isinstance(v, lp.LogicalPlan):
                    walk(v)
                elif isinstance(v, tuple):
                    for x in v:
                        if isinstance(x, lp.LogicalPlan):
                            walk(x)

    walk(plan)
    if not ok or not grids:
        return None
    g0 = grids[0]
    if any(g != g0 for g in grids):
        return None
    return g0


def retime_extent(plan: lp.LogicalPlan, start: int, end: int):
    """Rebind a splittable plan tree onto the [start, end] extent grid.

    Periodic nodes keep step/window/lookback/offset — only the evaluation
    range moves, so the planner re-widens the chunk scan per extent and
    window functions stay exact at seams. With ``start == end == 0`` this
    doubles as the canonical plan *signature*: two queries that differ only
    in evaluation range retime to equal (hashable, frozen) trees.
    """
    if isinstance(plan, (lp.PeriodicSeries, lp.PeriodicSeriesWithWindowing)):
        raw = dataclasses.replace(plan.raw, range_start=start, range_end=end)
        return dataclasses.replace(plan, raw=raw, start=start, end=end)
    if not dataclasses.is_dataclass(plan):
        return plan
    changes = {}
    for f in dataclasses.fields(plan):
        v = getattr(plan, f.name)
        if f.name == "start" and isinstance(v, int):
            changes[f.name] = start
        elif f.name == "end" and isinstance(v, int):
            changes[f.name] = end
        elif isinstance(v, lp.LogicalPlan):
            changes[f.name] = retime_extent(v, start, end)
        elif isinstance(v, tuple) and any(isinstance(x, lp.LogicalPlan)
                                          for x in v):
            changes[f.name] = tuple(
                retime_extent(x, start, end) if isinstance(x, lp.LogicalPlan)
                else x for x in v)
    return dataclasses.replace(plan, **changes) if changes else plan


def plan_signature(plan: lp.LogicalPlan):
    """Canonical, hashable signature: the plan with its evaluation range
    blanked. Selectors, functions, windows, offsets, steps all remain."""
    return retime_extent(plan, 0, 0)


def split_extents(start: int, step: int, end: int, extent_steps: int
                  ) -> list[tuple[int, int]]:
    """Split the inclusive step grid {start + k*step <= end} at absolute
    extent boundaries (multiples of ``extent_steps * step``), returning
    [(first_step, last_step)] per extent. Boundaries are absolute — NOT
    relative to ``start`` — so a dashboard window sliding one step per
    refresh keeps hitting the same interior extents."""
    extent_ms = extent_steps * step
    last = start + ((end - start) // step) * step
    out: list[tuple[int, int]] = []
    cur = start
    while cur <= last:
        bound = (cur // extent_ms + 1) * extent_ms  # exclusive
        k = (bound - 1 - cur) // step
        ext_last = min(cur + k * step, last)
        out.append((cur, ext_last))
        cur = ext_last + step
    return out


def _matrix_nbytes(m: StepMatrix) -> int:
    n = int(m.values.nbytes) + int(m.steps_ms.nbytes)
    if m.les is not None:
        n += int(np.asarray(m.les).nbytes)
    # label tuples are shared/interned; charge a flat overhead per key
    return n + 64 * len(m.keys) + 256


class ResultCache:
    """Byte-budgeted LRU of per-extent result matrices.

    Entries: (signature, full_extent_start, full_extent_end) →
    (stamp, StepMatrix), the full aligned extent grid regardless of how
    much of it the triggering query needed.
    ``stamp`` is None for immutable extents (never orphaned by ingest) and
    the dataset ``data_version`` for the mutable head (self-invalidates on
    any applied write). Stored matrices are host-resident and compacted;
    ``execute`` copies values out at merge time, so cached arrays are never
    aliased into mutable results.
    """

    def __init__(self, config: ResultCacheConfig | None = None):
        self.config = config or ResultCacheConfig()
        self._lru: "OrderedDict[tuple, tuple[int | None, StepMatrix]]" = \
            OrderedDict()
        self._bytes = 0
        self._lock = threading.Lock()
        # low-priority admissions: extents that are cheap to recompute
        # (pyramid-served, or predicted-cheap by the cost model) evict
        # before any payload-decoding entry under byte pressure
        self._cheap: set = set()

    @staticmethod
    def from_config(cfg) -> "ResultCache | None":
        """Build from a ``result_cache`` config dict (or passthrough an
        existing instance); None when disabled."""
        if cfg is None or cfg is False:
            return None
        if isinstance(cfg, ResultCache):
            return cfg
        if isinstance(cfg, ResultCacheConfig):
            conf = cfg
        elif isinstance(cfg, dict):
            conf = ResultCacheConfig.from_dict(cfg)
        elif cfg is True:
            conf = ResultCacheConfig()
        else:
            raise TypeError(f"bad result_cache config: {cfg!r}")
        return ResultCache(conf) if conf.enabled else None

    # ---- LRU ------------------------------------------------------------

    def __len__(self) -> int:
        with self._lock:
            return len(self._lru)

    @property
    def nbytes(self) -> int:
        return self._bytes

    def _get(self, key: tuple, stamp: int | None) -> StepMatrix | None:
        with self._lock:
            entry = self._lru.get(key)
            if entry is None or entry[0] != stamp:
                return None
            self._lru.move_to_end(key)
            return entry[1]

    def _put(self, key: tuple, stamp: int | None, m: StepMatrix,
             cheap: bool = False) -> None:
        nb = _matrix_nbytes(m)
        if nb > self.config.max_bytes:
            return  # larger than the whole budget: don't thrash
        with self._lock:
            old = self._lru.pop(key, None)
            if old is not None:
                self._bytes -= _matrix_nbytes(old[1])
            self._cheap.discard(key)
            self._lru[key] = (stamp, m)
            self._bytes += nb
            if cheap:
                self._cheap.add(key)
            while self._bytes > self.config.max_bytes and self._lru:
                # cheap-to-recompute entries go first (oldest cheap entry),
                # then plain LRU order — a payload-decoding extent outlives
                # every pyramid-served one under byte pressure
                ev_key = None
                if self._cheap:
                    ev_key = next((k for k in self._lru if k in self._cheap),
                                  None)
                if ev_key is None:
                    ev_key, (_, ev) = self._lru.popitem(last=False)
                else:
                    _, ev = self._lru.pop(ev_key)
                self._cheap.discard(ev_key)
                self._bytes -= _matrix_nbytes(ev)
                cache_evictions.inc()
            cache_bytes.set(self._bytes)

    def clear(self) -> None:
        with self._lock:
            self._lru.clear()
            self._cheap.clear()
            self._bytes = 0
            cache_bytes.set(0)

    # ---- split / execute / merge ----------------------------------------

    def execute(self, svc, plan: lp.LogicalPlan,
                qcontext: QueryContext | None = None) -> QueryResult | None:
        """Answer ``plan`` by extent splitting, or None to signal the
        caller to take the uncached path (bypass)."""
        qcontext = qcontext or QueryContext()
        pp = qcontext.planner_params
        if pp.shard_overrides or pp.spread is not None:
            return None  # per-query routing overrides change what's read
        grid = splittable_grid(plan)
        if grid is None:
            return None
        start, step, end = grid
        shards = svc.memstore.shards_for(svc.dataset)
        if len(shards) < getattr(svc, "num_shards", 1):
            # remote shards: local versions/horizons can't witness their
            # ingest (same rule as http.server.service_version)
            return None
        extents = split_extents(start, step, end, self.config.extent_steps)
        # Read version BEFORE evaluating the head extent: rows ingested
        # while we compute make the stored stamp stale, so the entry
        # self-invalidates instead of serving a pre-ingest result.
        version = sum(s.data_version for s in shards)
        max_ts = min((s.max_ingested_ts for s in shards), default=-1)
        horizon = max_ts - self.config.ooo_allowance_ms
        # standing-query hook (rules/manager.py): recording rules write
        # series AT timestamps at/below the ingest horizon, i.e. inside
        # the "immutable" region. Clamp immutability to what the rules
        # have verifiably written so an extent of a rule-output series is
        # never frozen before the rule's write lands; extents past the
        # clamp carry a version stamp and self-invalidate on the write.
        floor = getattr(svc, "rules_horizon_floor", None)
        if floor is not None:
            horizon = min(horizon, floor() if callable(floor) else floor)
        sig = plan_signature(plan)
        # tiered federation: the signature stays tier-INVARIANT (the grid
        # splits before tier routing, so a repeat query hits the same key
        # no matter which tier serves an extent), but tier MEMBERSHIP is
        # part of it — a TieredPlanner folds its cold/ds index versions
        # in, so settled extents don't outlive part-key index growth in
        # the colder tiers (e.g. the downsampler publishing a window that
        # was queried before it landed).
        tok = getattr(svc.planner, "version_token", None)
        if tok is not None:
            sig = (sig, tok())

        extent_ms = self.config.extent_steps * step
        t0 = time.perf_counter()
        found: list[tuple[int, int, int, StepMatrix]] = []
        stats = QueryStats()
        hits = misses = 0
        with span("cache", extents=len(extents)) as sp:
            for es, ee in extents:
                # evaluate/cache the FULL aligned extent grid [fs, fe] (same
                # step phase as the query), slice to [es, ee] below: every
                # sub-query then has exactly extent_steps steps, so the
                # batched kernels compile once and stay warm
                lo = (es // extent_ms) * extent_ms
                fs = lo + ((start - lo) % step)
                fe = fs + ((lo + extent_ms - 1 - fs) // step) * step
                key = (sig, fs, fe)
                stamp = None if fe <= horizon else version
                m = self._get(key, stamp)
                if m is not None:
                    hits += 1
                else:
                    misses += 1
                    sub = retime_extent(plan, fs, fe)
                    # origin rides along so rule-driven sub-queries admit
                    # under the governor's RULES class, not EXPENSIVE
                    r = svc._execute_uncached(
                        sub, QueryContext(planner_params=pp,
                                          origin=qcontext.origin),
                        materialize=True)
                    if r.partial or r.warnings:
                        # degraded extents must not be cached OR spliced
                        # into a result that looks whole; surrender to the
                        # uncached path so partial semantics match it
                        cache_misses.inc(misses)
                        cache_hits.inc(hits)
                        return svc._execute_uncached(plan, qcontext)
                    # admission priority by recompute cost, not byte size:
                    # the "cache" decision site learns each signature's
                    # recompute wall time; predicted-cheap extents — and
                    # pyramid-served ones, whose windows re-fold from
                    # stored roll-ups without paging payload — admit at
                    # low priority and evict first
                    from filodb_tpu.query import cost_model as cm
                    model = cm.model_for(svc.dataset)
                    d = model.classify(
                        "cache", sig, _CHEAP_RECOMPUTE_S,
                        below_arm="cheap", above_arm="keep",
                        static_arm="keep")
                    model.record_actual(d, r.stats.wall_time_s)
                    cheap = d.arm == "cheap" or bool(r.stats.pyramid)
                    self._put(key, stamp, r.result, cheap=cheap)
                    m = r.result
                    # fold the full expanded counters (incl. per-tier
                    # federation buckets), not just the scan totals
                    stats.merge_counts(r.stats)
                found.append((es, ee, fs, m))
            cache_hits.inc(hits)
            cache_misses.inc(misses)
            if 0 < hits < len(extents):
                cache_partial_hits.inc()
            with span("cache-merge"):
                merged = _merge_extents(
                    [(es, ee, _slice_steps(m, fs, step, es, ee))
                     for es, ee, fs, m in found], step)
            if sp is not None:
                sp.tags.update(hits=hits, misses=misses,
                               bytes=self._bytes)
        if merged is None:
            # non-uniform histogram buckets across extents — rare enough
            # to just evaluate whole
            return svc._execute_uncached(plan, qcontext)
        from filodb_tpu.query.exec.plan import ExecPlan
        ExecPlan._enforce_limits(merged, qcontext)
        stats.cache_hits += hits
        stats.cache_misses += misses
        stats.result_series = merged.num_series
        stats.wall_time_s = time.perf_counter() - t0
        return QueryResult(merged, stats, qcontext.query_id)


def _slice_steps(m: StepMatrix, fs: int, step: int, es: int, ee: int
                 ) -> StepMatrix:
    """View of a full-extent matrix restricted to grid points [es, ee].

    Rows left all-NaN by the slice are dropped: the single-shot path
    compacts them at materialize, and per-step-selective functions (topk)
    can emit a series solely for steps outside the requested sub-range."""
    if m.num_series == 0:
        return m
    i0 = (es - fs) // step
    i1 = (ee - fs) // step
    if i0 == 0 and i1 == len(m.steps_ms) - 1:
        return m
    vals = m.values[:, i0:i1 + 1]
    axes = tuple(range(1, vals.ndim))
    keep = ~np.all(np.isnan(vals), axis=axes)
    keys = m.keys
    if not keep.all():
        vals = vals[keep]
        keys = [k for k, kp in zip(keys, keep) if kp]
    return StepMatrix(keys, vals, m.steps_ms[i0:i1 + 1], m.les)


def _merge_extents(parts: list[tuple[int, int, StepMatrix]], step: int
                   ) -> StepMatrix | None:
    """Splice per-extent matrices back into one grid-spanning matrix.

    Series are aligned by label key across extents; a series absent from an
    extent (no samples in its widened window) fills with NaN, which is
    exactly what the single-shot evaluation produces for it there. Returns
    None when histogram bucket layouts disagree across extents (unmergeable
    — caller falls back to whole evaluation)."""
    if len(parts) == 1:
        es, ee, m = parts[0]
        # copy out: cached arrays (or slices of them) must never be
        # aliased into a result a caller might mutate
        return StepMatrix(list(m.keys), np.array(m.values),
                          np.array(m.steps_ms), m.les)
    key_index: dict = {}
    order: list = []
    les = None
    nbuckets = 0
    dtype = None
    for _, _, m in parts:
        if m.keys != order:  # common case: every extent has the same keys
            for k in m.keys:
                if k not in key_index:
                    key_index[k] = len(order)
                    order.append(k)
        if m.num_series and dtype is None:
            dtype = m.values.dtype
        if m.num_series and m.is_histogram:
            if les is None:
                les = m.les
                nbuckets = m.values.shape[2]
            elif m.les is None or len(m.les) != len(les) \
                    or not np.array_equal(np.asarray(m.les),
                                          np.asarray(les)):
                return None
    steps_full = np.concatenate([
        np.arange(es, ee + 1, step, dtype=np.int64) for es, ee, _ in parts])
    if not order:
        return StepMatrix.empty()
    shape = (len(order), len(steps_full), nbuckets) if nbuckets \
        else (len(order), len(steps_full))
    out = np.full(shape, np.nan, dtype=dtype or np.float64)
    off = 0
    for es, ee, m in parts:
        k = (ee - es) // step + 1
        if m.num_series:
            if bool(nbuckets) != m.is_histogram:
                return None  # scalar/histogram mix across extents
            if m.keys == order:
                out[:, off:off + k] = m.values
            else:
                rows = np.fromiter((key_index[key] for key in m.keys),
                                   dtype=np.intp, count=len(m.keys))
                out[rows, off:off + k] = m.values
        off += k
    return StepMatrix(order, out, steps_full, les)
