"""High-level query facade: PromQL string → results.

Counterpart of the reference's QueryActor + client ask path
(``coordinator/src/main/scala/filodb.coordinator/QueryActor.scala:43,119,171``):
parse → plan → execute against the memstore, returning StepMatrix results.
"""

from __future__ import annotations

import itertools
import time
from dataclasses import dataclass, field

from filodb_tpu.coordinator import mesh_cluster as _mesh_cluster  # noqa: F401
from filodb_tpu.coordinator.planner import SingleClusterPlanner
from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.promql.parser import TimeStepParams, parse_query
from filodb_tpu.query import logical as lp
from filodb_tpu.query.exec.plan import ExecContext
from filodb_tpu.query.model import QueryContext, QueryResult
from filodb_tpu.utils.governor import (
    CHEAP,
    EXPENSIVE,
    RULES,
    default_budget,
    governor,
    tenant_of,
)
from filodb_tpu.utils.metrics import Histogram, get_counter
from filodb_tpu.utils.resilience import Deadline
from filodb_tpu.utils.resilience import config as resilience_config
from filodb_tpu.utils.tracing import (
    config as tracing_config,
    record_slow,
    span,
    traced_batch,
    traced_query,
)

query_latency = Histogram("query_latency_seconds")
partial_results = get_counter("filodb_partial_results")
# what the front door hands over in one pass of its loop: how many members
# a query_range_many call carries (a single counts as a batch of one)
_M_BATCHES = get_counter(
    "filodb_query_batches", help="query_range_many calls")
_M_BATCH_MEMBERS = get_counter(
    "filodb_query_batch_members", help="queries carried by query_range_many "
    "calls; over filodb_query_batches_total, the mean members a batch")


class _BudgetCtx:
    """Minimal ctx for boundary budget checks on engines without an
    ExecContext (the mesh path): carries budget + partial/warnings."""

    def __init__(self, budget):
        self.budget = budget
        self.partial = False
        self.warnings: list[str] = []


def _admission_cost(plan) -> str:
    """Admission cost class for a logical plan: instant queries (a single
    evaluation step) are CHEAP — they stay admissible when the governor is
    CRITICAL; range scans are EXPENSIVE and shed first."""
    import dataclasses
    stack, seen = [plan], 0
    while stack and seen < 64:
        p = stack.pop()
        seen += 1
        start, end = getattr(p, "start", None), getattr(p, "end", None)
        if isinstance(start, int) and isinstance(end, int) and end > 0:
            return CHEAP if start == end else EXPENSIVE
        if dataclasses.is_dataclass(p):
            for f in dataclasses.fields(p):
                v = getattr(p, f.name, None)
                if dataclasses.is_dataclass(v) and not isinstance(v, type):
                    stack.append(v)
    return EXPENSIVE


def plan_tenant(plan) -> str:
    """Tenant id (``ws/ns``) from the first selector's ``_ws_``/``_ns_``
    equality filters — keys the governor's per-tenant inflight gate. Empty
    string (untenanted/unmatchable plan shapes) means no tenant gating."""
    import dataclasses

    from filodb_tpu.core.filters import Equals
    stack, seen = [plan], 0
    while stack and seen < 64:
        p = stack.pop()
        seen += 1
        filters = getattr(p, "filters", None)
        if filters:
            labels = {}
            for cf in filters:
                f = getattr(cf, "filter", None)
                if getattr(cf, "column", None) in ("_ws_", "_ns_") \
                        and isinstance(f, Equals):
                    labels[cf.column] = str(f.value)
            if labels:
                return tenant_of(labels)
        if dataclasses.is_dataclass(p):
            for fld in dataclasses.fields(p):
                v = getattr(p, fld.name, None)
                if dataclasses.is_dataclass(v) and not isinstance(v, type):
                    stack.append(v)
    return ""


@dataclass
class QueryService:
    memstore: TimeSeriesMemStore
    dataset: str
    num_shards: int = 1
    spread: int = 0
    time_split_ms: int = 0
    # instant-selector staleness (reference QueryConfig staleSampleAfterMs)
    lookback_ms: int = 300_000
    # "exec" = scatter-gather exec-plan tree (the reference's distribution);
    # "mesh" = lower supported agg(range_fn(sel[w])) by (...) plans onto the
    # (shard × time) device mesh, falling back to exec for everything else
    # (what a server's config defaults to)
    engine: str = "exec"
    mesh: object = None  # jax Mesh override for engine="mesh"
    # per-query deadline; every socket/HTTP timeout on the distributed
    # path derives from it (None = resilience-config default)
    query_timeout_s: float | None = None
    # extent result cache (filodb_tpu.query.result_cache): a config dict /
    # ResultCacheConfig / ResultCache / True enables it; None or False
    # disables. Sits in front of both engines alike.
    result_cache: object = None
    # callable () -> [(shard, status_str)] for queryable-but-not-ACTIVE
    # shards (recovery/handoff); results touching them carry a warning so
    # callers know the answer may lag the live shard (never wrong, at most
    # behind the in-flight tail). Wired by cluster/standalone.
    shard_status_fn: object = None
    # multi-process mesh runtime (coordinator/mesh_cluster.py): when set,
    # mesh-shaped plans scatter to worker processes first; ``None`` from
    # the runtime (slice unavailable / shape declined / FILODB_MULTIPROC=0)
    # falls through to the single-process engines inside the same
    # admission scope. Wired by standalone when mesh_workers.enabled.
    mesh_cluster: object = None
    planner: SingleClusterPlanner = field(init=False)

    # monotonic construction serial: response-cache keys must survive a
    # service being torn down and a new one allocated at the same address
    # (id() aliases; a serial never does)
    _serial_counter = itertools.count(1)

    def __post_init__(self):
        self.planner = SingleClusterPlanner(
            self.dataset, self.num_shards, self.spread,
            time_split_ms=self.time_split_ms)
        self._plan_cache: dict = {}
        self.serial = next(QueryService._serial_counter)
        from filodb_tpu.query.result_cache import ResultCache
        self.result_cache = ResultCache.from_config(self.result_cache)
        self.mesh_engine = None
        if self.engine == "mesh":
            from filodb_tpu.parallel.mesh_engine import MeshQueryEngine
            self.mesh_engine = MeshQueryEngine(mesh=self.mesh, sidecars=True)

    # ---- promql entry points --------------------------------------------

    def query_range(self, promql: str, start_sec: int, step_sec: int,
                    end_sec: int, qcontext: QueryContext | None = None
                    ) -> QueryResult:
        qcontext = qcontext or QueryContext()
        params = TimeStepParams(start_sec, step_sec, end_sec)
        # traced_query: joins an active trace (debug endpoint, rules tick)
        # or head-samples a new one; on exit feeds stage histograms and
        # tail-captures slow queries into the flight recorder
        with traced_query(qcontext, query=promql, dataset=self.dataset) as rec:
            with span("parse", promql=promql):
                plan = self._parse_cached(promql, params)
            result = self.execute_logical(plan, qcontext)
            rec.observe(result)
        return result

    def query_range_many(self, queries, workers: int = 8,
                         return_errors: bool = False) -> list:
        """Execute many in-flight range queries and return results in order.
        Counterpart of the reference QueryActor's concurrent dispatch on its
        ForkJoin query scheduler (``QueryActor.scala:233-237``; the JMH
        ``QueryInMemoryBenchmark`` drives 100 concurrent queries per op,
        cycling 4 plan shapes).

        Two-phase: (1) dispatch every query's device program asynchronously
        (results stay lazy on device); (2) fetch ALL result buffers in one
        batched ``jax.device_get``: a per-query fetch is a blocking
        host↔device round trip, one coalesced transfer amortizes it across
        the whole batch. Each element of ``queries`` is
        ``(promql, start_sec, step_sec, end_sec)``.

        The extent result cache is consulted per query first; cache-answered
        queries skip the mesh dispatch and the batch fetch entirely (their
        matrices are already host-resident).

        With ``return_errors=True`` a failing query yields its exception at
        its own position instead of poisoning the whole batch — one bad
        query costs only itself, not an O(n) sequential re-run.

        A batch of more than one member is head-sampled once and, when
        sampled, leaves one ``query-batch`` trace (``tracing.traced_batch``):
        ``parse``, the members' own ``cache`` spans, ``mesh-execute`` with
        the engine's phase spans, the fall-through members' own
        ``plan-materialize``/``exec-dispatch``, ``batch-fetch``,
        ``finish``."""
        t0 = time.perf_counter()
        n = len(queries)
        _M_BATCHES.inc()
        _M_BATCH_MEMBERS.inc(n)
        if n == 1:
            # a single-member batch has nothing to coalesce; take the
            # fully-traced query_range path so head-sampling and slow-query
            # span capture keep working for the HTTP fronts (which funnel
            # every hot query through here, even singles)
            promql, start_sec, step_sec, end_sec = queries[0]
            try:
                return [self.query_range(promql, start_sec, step_sec,
                                         end_sec)]
            except Exception as e:  # noqa: BLE001
                if not return_errors:
                    raise
                return [e]
        with traced_batch(members=n, dataset=self.dataset):
            return self._run_batch(queries, return_errors, t0)

    def _run_batch(self, queries, return_errors: bool, t0: float) -> list:
        """``query_range_many`` for more than one member."""
        import numpy as np

        n = len(queries)
        plans: list = [None] * n
        outcomes: list = [None] * n  # QueryResult | Exception per query
        with span("parse", members=n):
            for i, q in enumerate(queries):
                promql, start_sec, step_sec, end_sec = q
                params = TimeStepParams(start_sec, step_sec, end_sec)
                try:
                    plans[i] = self._parse_cached(promql, params)
                except Exception as e:  # noqa: BLE001
                    if not return_errors:
                        raise
                    outcomes[i] = e

        if self.result_cache is not None:
            for i, plan in enumerate(plans):
                if plan is None or outcomes[i] is not None:
                    continue
                try:
                    r = self.result_cache.execute(self, plan, QueryContext())
                except Exception as e:  # noqa: BLE001
                    if not return_errors:
                        raise
                    outcomes[i] = e
                    continue
                if r is not None:
                    outcomes[i] = r
        pending = [i for i in range(n)
                   if outcomes[i] is None and plans[i] is not None]

        from filodb_tpu.query.model import QueryStats
        stats_list = {i: QueryStats() for i in pending}
        mesh_results = {i: None for i in pending}
        # The mesh executes against the raw memstore only; a federated
        # planner may route part of a straddling range to colder tiers, so
        # only plans the planner proves memstore-resident may take the
        # mesh shortcut — the rest fall to the exec path (tier routing).
        meshable = [i for i in pending
                    if self._planner_mem_only(plans[i])]
        if meshable and self.mesh_engine is not None \
                and self._mesh_eligible():
            # one device program per shared plan signature (micro-batched
            # step grids); unsupported plans fall through to the exec path.
            # The whole batch takes ONE admission slot: it runs as one
            # device program, and per-item gating would stall the batcher.
            def run_on_mesh(idxs):
                with span("mesh-execute", members=len(idxs)), \
                        governor().admit(cost=EXPENSIVE):
                    return self.mesh_engine.execute_many(
                        [plans[i] for i in idxs], self.memstore,
                        self.dataset, [stats_list[i] for i in idxs])

            try:
                mr = run_on_mesh(meshable)
            except Exception:  # noqa: BLE001
                from filodb_tpu.parallel.mesh_engine import _M_FALLBACK
                _M_FALLBACK["error"].inc(len(meshable))
                if not return_errors:
                    raise
                # A failure inside the device engine (compile refusal, out
                # of memory) is the answer to the query that caused it:
                # re-run one plan at a time so it lands on that query
                # alone, and never re-answer it from the exec tree — that
                # would serve a device fault as a success.
                mr = []
                for i in meshable:
                    try:
                        mr.append(run_on_mesh([i])[0])
                    except Exception as e:  # noqa: BLE001
                        mr.append(e)
            for j, i in enumerate(meshable):
                if isinstance(mr[j], Exception):
                    outcomes[i] = mr[j]
                else:
                    mesh_results[i] = mr[j]

        deferred = set()
        for i in pending:
            if outcomes[i] is not None:
                continue  # failed in the device engine
            if mesh_results[i] is not None:
                outcomes[i] = QueryResult(mesh_results[i], stats_list[i],
                                          None)
                deferred.add(i)
            else:
                try:
                    outcomes[i] = self._execute_uncached(
                        plans[i], materialize=False)
                except Exception as e:  # noqa: BLE001
                    if not return_errors:
                        raise
                    outcomes[i] = e
        # Coalesced device→host fetch: stack same-shaped lazy result buffers
        # into one device array per shape and fetch each stack once: one
        # stacked transfer instead of a blocking fetch per query.
        import jax.numpy as jnp

        from filodb_tpu.query.exec.plan import ExecPlan
        with span("batch-fetch"):
            by_shape: dict[tuple, list[int]] = {}
            for i in pending:
                r = outcomes[i]
                if isinstance(r, Exception):
                    continue
                v = r.result.values
                if not isinstance(v, np.ndarray):
                    by_shape.setdefault((v.shape, str(v.dtype)),
                                        []).append(i)
            for idxs in by_shape.values():
                try:
                    stacked = np.asarray(jnp.stack([outcomes[i].result.values
                                                    for i in idxs]))
                except Exception as e:  # noqa: BLE001
                    if not return_errors:
                        raise
                    for i in idxs:
                        outcomes[i] = e
                    continue
                for j, i in enumerate(idxs):
                    outcomes[i].result.values = stacked[j]
                    deferred.add(i)
        # limits + stats AFTER materialization, so deferred compaction has
        # dropped empty series first (enforcing on the pre-compaction count
        # rejected queries the sequential path accepted) — uniformly for
        # mesh AND exec-path results whose fetch was deferred to this batch
        wall = time.perf_counter() - t0
        with span("finish", members=len(deferred)):
            for i in sorted(deferred):
                try:
                    data = outcomes[i].result.materialize()
                    qcontext = QueryContext()
                    ExecPlan._enforce_limits(data, qcontext)
                except Exception as e:  # noqa: BLE001
                    if not return_errors:
                        raise
                    outcomes[i] = e
                    continue
                outcomes[i].stats.result_series = data.num_series
                # batched execution: the whole pass's wall time is every
                # member's latency (they completed together)
                outcomes[i].stats.wall_time_s = wall
                if not outcomes[i].query_id:
                    outcomes[i].query_id = qcontext.query_id
        # tail capture for the batched path: members of a slow batch land in
        # the flight recorder with stats (the spans are the batch's, in its
        # one query-batch entry — the whole batch runs as one device program)
        thr = tracing_config().slow_query_threshold_ms
        if deferred and thr > 0 and wall * 1000.0 > thr:
            import dataclasses as _dc

            for i in sorted(deferred):
                r = outcomes[i]
                if isinstance(r, QueryResult):
                    record_slow("query", wall * 1000.0,
                                stats=_dc.asdict(r.stats),
                                query=queries[i][0], dataset=self.dataset,
                                batched=True)
        return outcomes

    def _parse_cached(self, promql: str, params: TimeStepParams):
        """PromQL parse memo — the concurrent workload cycles few distinct
        query shapes, and logical plans are immutable."""
        key = (promql, params.start, params.step, params.end,
               self.lookback_ms)
        cached = self._plan_cache.get(key)
        if cached is not None:
            return cached
        plan = parse_query(promql, params, self.lookback_ms)
        if len(self._plan_cache) >= 256:
            self._plan_cache.pop(next(iter(self._plan_cache)))
        self._plan_cache[key] = plan
        return plan

    def query_instant(self, promql: str, time_sec: int,
                      qcontext: QueryContext | None = None) -> QueryResult:
        qcontext = qcontext or QueryContext()
        params = TimeStepParams(time_sec, 0, time_sec)
        plan = parse_query(promql, params, self.lookback_ms)
        with traced_query(qcontext, query=promql, dataset=self.dataset) as rec:
            result = self.execute_logical(plan, qcontext)
            rec.observe(result)
        return result

    def execute_logical(self, plan: lp.LogicalPlan,
                        qcontext: QueryContext | None = None,
                        materialize: bool = True) -> QueryResult:
        qcontext = qcontext or QueryContext()
        if self.result_cache is not None and materialize:
            # extent result cache in front of every engine; None = plan
            # shape (or deployment) the splitter won't touch — fall through
            cached = self.result_cache.execute(self, plan, qcontext)
            if cached is not None:
                # partial results only come out of _execute_uncached (the
                # cache's surrender path), which already counts them
                return cached
        return self._execute_uncached(plan, qcontext, materialize)

    def _execute_uncached(self, plan: lp.LogicalPlan,
                          qcontext: QueryContext | None = None,
                          materialize: bool = True) -> QueryResult:
        """Engine execution without the extent cache — the cache itself
        evaluates per-extent sub-queries through here."""
        qcontext = qcontext or QueryContext()
        t0 = time.perf_counter()
        if isinstance(plan, (lp.LabelValues, lp.LabelNames,
                             lp.SeriesKeysByFilters)):
            return self._metadata(plan, qcontext)
        # attach the node's default scan budget (governor config) unless the
        # caller brought one; it rides the QueryContext to remote leaves
        pp = qcontext.planner_params
        if pp.budget is None:
            pp.budget = default_budget()
        timeout_s = self.query_timeout_s if self.query_timeout_s is not None \
            else resilience_config().query_timeout_s
        deadline = Deadline.after(timeout_s)
        # admission gate: single choke point for the mesh and exec engines
        # (and the cache's per-extent sub-queries); over-capacity queries
        # wait bounded by the deadline, then shed with QueryRejected (503).
        # Standing-query evaluations (QueryContext.origin == "rules")
        # admit as their own lowest-priority class.
        # tiered planners (longtime/tiered_planner) can force a cost
        # class: any query touching a cold tier is EXPENSIVE no matter
        # its shape — paging object-store segments sheds before CHEAP
        # memstore traffic when the governor is CRITICAL
        if qcontext.origin == "rules":
            cost = RULES
        else:
            hint = getattr(self.planner, "cost_hint", None)
            forced = hint(plan) if hint is not None else None
            cost = forced or _admission_cost(plan)
            if forced is None:
                # learned classing: predicted wall time for this plan's
                # signature class replaces the start==end shape heuristic
                # once warm (cold model returns the static class)
                from filodb_tpu.coordinator import adaptive_planner
                cost = adaptive_planner.admission_class(
                    self.dataset, plan, qcontext, cost)
        t_admit = time.perf_counter()
        with governor().admit(deadline=deadline, cost=cost,
                              tenant=plan_tenant(plan)):
            admission_wait_s = time.perf_counter() - t_admit
            if self.mesh_cluster is not None and self._mesh_eligible() \
                    and self._planner_mem_only(plan):
                # multi-process mesh first: lowered descriptors scatter to
                # the worker processes and the root runs the window-
                # boundary reduce. None = slice unavailable / shape
                # declined / disabled — fall through to the single-process
                # engines below WITHOUT re-admitting (one admission per
                # query, whatever path serves it). A worker-side shed
                # raises QueryRejected out of the scope (PR 1/4: overload
                # propagates, unavailability degrades).
                from filodb_tpu.query.model import QueryStats
                stats = QueryStats()
                stats.admission_wait_s += admission_wait_s
                with query_latency.time(), span("mesh-proc-execute"):
                    data = self.mesh_cluster.execute_plan(plan, deadline,
                                                          stats)
                if data is not None:
                    return self._finish_device_result(data, stats,
                                                      qcontext, pp, cost,
                                                      t0)
            if self.mesh_engine is not None and self._mesh_eligible() \
                    and self._planner_mem_only(plan) \
                    and self.mesh_engine.supports(plan):
                from filodb_tpu.query.model import QueryStats
                stats = QueryStats()
                stats.admission_wait_s += admission_wait_s
                with query_latency.time(), span("mesh-execute"):
                    data = self.mesh_engine.execute(self.memstore,
                                                    self.dataset, plan, stats)
                if data is None:
                    # recognized plan the kernels declined at execution
                    # time (e.g. histogram batch under a non-sum agg)
                    from filodb_tpu.parallel.mesh_engine import _M_FALLBACK
                    _M_FALLBACK["declined"].inc()
                if data is not None:  # None = shape the kernels don't cover
                    return self._finish_device_result(data, stats,
                                                      qcontext, pp, cost,
                                                      t0)
            with span("plan-materialize"):
                exec_plan = self.planner.materialize(plan, qcontext)
            ctx = ExecContext(self.memstore, self.dataset, qcontext,
                              deadline=deadline)
            ctx.stats.admission_wait_s += admission_wait_s
            with query_latency.time(), span("exec-dispatch"):
                result = exec_plan.dispatcher.dispatch(exec_plan, ctx)
                if materialize:
                    # device → host once, at the boundary; query_range_many
                    # defers this and batch-fetches across in-flight queries
                    result.result.materialize()
                    # device-resident results skipped in-tree enforcement
                    # (compaction was deferred); enforce on the real count
                    from filodb_tpu.query.exec.plan import (
                        ExecPlan,
                        apply_result_budget,
                    )
                    ExecPlan._enforce_limits(result.result, qcontext)
                    # ...and the result-bytes budget likewise: in-tree
                    # checks only see host-resident matrices
                    result.result = apply_result_budget(result.result, ctx)
                    result.partial = ctx.partial
                    result.warnings = list(ctx.warnings)
        result.stats.wall_time_s = time.perf_counter() - t0
        result.stats.result_series = result.result.num_series
        from filodb_tpu.coordinator import adaptive_planner
        adaptive_planner.settle_query(
            self.dataset, qcontext, result.stats.wall_time_s, cost)
        if result.partial:
            partial_results.inc()
        return self._attach_recovery_warnings(result)

    def _finish_device_result(self, data, stats, qcontext, pp, cost,
                              t0) -> QueryResult:
        """Finishing tail shared by the device engines (single-process
        mesh and multi-process mesh): materialize first so deferred
        compaction applies, then the same resource guards as the exec
        path (real counts), then settle the adaptive cost model."""
        from filodb_tpu.query.exec.plan import (
            ExecPlan,
            apply_result_budget,
        )
        with span("finish") as sp:
            data.materialize()
            ExecPlan._enforce_limits(data, qcontext)
            # result-bytes budget on the materialized matrix (the mesh has
            # no incremental scan hooks, so the boundary check is where it
            # degrades gracefully)
            shim = _BudgetCtx(pp.budget)
            data = apply_result_budget(data, shim)
            stats.wall_time_s = time.perf_counter() - t0
            stats.result_series = data.num_series
            from filodb_tpu.coordinator import adaptive_planner
            adaptive_planner.settle_query(
                self.dataset, qcontext, stats.wall_time_s, cost)
            if sp is not None:
                sp.tags["series"] = stats.result_series
            return self._attach_recovery_warnings(
                QueryResult(data, stats, qcontext.query_id,
                            partial=shim.partial, warnings=shim.warnings))

    def _recovery_warnings(self) -> list[str]:
        """One warning per queryable-but-catching-up shard (recovery replay,
        live-migration handoff, or a read served from a follower replica
        while the leader is unreachable) — satellite rule: queries during
        migration/failover are correct or *flagged*, never silently
        stale."""
        fn = self.shard_status_fn
        if fn is None:
            return []
        try:
            out = []
            for shard, status in fn():
                if status.startswith("served by"):
                    out.append(f"shard {shard} {status}: results may "
                               f"lag live ingest")
                else:
                    out.append(f"shard {shard} recovering ({status}): "
                               f"results may lag live ingest")
            return out
        except Exception:
            return []

    def _attach_recovery_warnings(self, result: QueryResult) -> QueryResult:
        for w in self._recovery_warnings():
            if w not in result.warnings:
                result.warnings.append(w)
        return result

    def _planner_mem_only(self, plan) -> bool:
        """True when the planner certifies the plan reads only memstore-
        resident data (incl. lookback). Planners without tiering (plain
        SingleClusterPlanner) have no ``mem_only`` and are all-raw by
        construction."""
        f = getattr(self.planner, "mem_only", None)
        return True if f is None else bool(f(plan))

    def _mesh_eligible(self) -> bool:
        """The mesh fans ALL series into one device program, so every shard
        of the dataset must be resident in this process's memstore; a
        coordinator facade over remote members sees partial data and must
        use the scatter-gather path."""
        ok = len(self.memstore.shards_for(self.dataset)) >= self.num_shards
        if not ok and self.mesh_engine is not None:
            from filodb_tpu.parallel.mesh_engine import _M_FALLBACK
            _M_FALLBACK["shards"].inc()
        return ok

    # ---- metadata -------------------------------------------------------

    def _metadata(self, plan, qcontext) -> QueryResult:
        from filodb_tpu.query.model import StepMatrix
        import numpy as np
        if isinstance(plan, lp.LabelValues):
            vals = self.memstore.label_values(self.dataset, plan.label,
                                              list(plan.filters) or None)
            meta = [("__label_value__", v) for v in vals]
        elif isinstance(plan, lp.LabelNames):
            meta = [("__label_name__", v)
                    for v in self.memstore.label_names(self.dataset)]
        else:  # SeriesKeysByFilters
            meta = []
            for shard in self.memstore.shards_for(self.dataset):
                for pid in shard.lookup_partitions(list(plan.filters),
                                                   plan.start, plan.end):
                    pk = shard.index.part_key(pid)
                    if pk is not None:
                        meta.append(("__series__", str(sorted(pk.labels))))
        result = StepMatrix.empty()
        result.meta = meta  # metadata rides alongside
        qr = QueryResult(result, query_id=qcontext.query_id)
        return qr

    def chunk_infos(self, filters, start_ms: int, end_ms: int,
                    include_buffer: bool = False) -> list[dict]:
        """Chunk metadata for matching partitions (reference
        ``SelectChunkInfosExec`` debug query)."""
        out = []
        for shard in self.memstore.shards_for(self.dataset):
            for pid in shard.lookup_partitions(list(filters), start_ms,
                                               end_ms):
                part = shard.partition(pid)
                if part is None:
                    continue
                for c in part.chunks_in_range(start_ms, end_ms,
                                              include_buffer):
                    out.append({
                        "shard": shard.shard_num, "partId": pid,
                        "partKey": str(part.part_key), "chunkId": c.id,
                        "numRows": c.num_rows, "startTime": c.start_time,
                        "endTime": c.end_time, "numBytes": c.nbytes,
                    })
        return out

    def series(self, filters, start_sec: int, end_sec: int) -> list[dict]:
        out = []
        for shard in self.memstore.shards_for(self.dataset):
            for pid in shard.lookup_partitions(list(filters),
                                               start_sec * 1000,
                                               end_sec * 1000):
                pk = shard.index.part_key(pid)
                if pk is not None:
                    out.append(pk.label_map)
        return out


class QueryBatcher:
    """Coalesces concurrent in-flight queries into ``query_range_many``
    batches — the serving-side analog of inference micro-batching, and the
    TPU-native answer to the reference's per-query actor dispatch
    (``QueryActor.scala:233-237``): under load the mesh engine evaluates a
    whole batch as one device program, and results fetch in one coalesced
    transfer.

    Handler threads submit and wait; one dispatcher thread drains whatever
    is queued (no artificial batching delay — an idle server answers a lone
    query at single-query latency)."""

    def __init__(self, svc: QueryService, max_batch: int = 64):
        import queue
        import threading

        self.svc = svc
        self.max_batch = max_batch
        self._q: queue.Queue = queue.Queue()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="query-batcher")
        self._thread.start()

    def query_range(self, promql: str, start_sec: int, step_sec: int,
                    end_sec: int):
        import threading

        item = {"params": (promql, start_sec, step_sec, end_sec),
                "event": threading.Event(), "result": None, "error": None}
        self._q.put(item)
        item["event"].wait()
        if item["error"] is not None:
            raise item["error"]
        return item["result"]

    def _loop(self):
        import queue

        while True:
            items = [self._q.get()]
            try:
                while len(items) < self.max_batch:
                    items.append(self._q.get_nowait())
            except queue.Empty:
                pass
            try:
                # per-item error capture: one poison query surfaces at its
                # own position without forcing the old O(n) sequential
                # re-run of the whole batch
                results = self.svc.query_range_many(
                    [it["params"] for it in items], return_errors=True)
                for it, r in zip(items, results):
                    if isinstance(r, Exception):
                        it["error"] = r
                    else:
                        it["result"] = r
            except Exception:  # pragma: no cover - defensive
                # a failure that escaped per-item capture (batch machinery
                # itself); isolate by running each alone
                for it in items:
                    try:
                        it["result"] = self.svc.query_range(*it["params"])
                    except Exception as e:  # noqa: BLE001
                        it["error"] = e
            for it in items:
                it["event"].set()
