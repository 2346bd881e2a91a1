"""Query result model.

Counterpart of reference ``core/src/main/scala/filodb.core/query/``
(``RangeVector.scala:27,121,315``, ``QueryContext.scala:44``, ``ResultTypes``):
but column-oriented — the unit of data flowing through the exec tree is a
``StepMatrix``: a batch of series keys plus a dense [P, K] value matrix (or
[P, K, B] for histogram-valued vectors) over shared step timestamps. NaN marks
"no sample". This is the TPU-first replacement for per-row RangeVector
iterators; a ``StepMatrix`` converts to per-series (ts, value) pairs only at
the API boundary.
"""

from __future__ import annotations

import time as _time
import uuid
from dataclasses import dataclass, field

import numpy as np

from filodb_tpu.core.partkey import METRIC_LABEL


@dataclass(frozen=True)
class RangeVectorKey:
    """Series identity: a frozen label set (reference ``RangeVectorKey``)."""

    labels: tuple[tuple[str, str], ...]

    @staticmethod
    def of(labels: dict[str, str]) -> "RangeVectorKey":
        return RangeVectorKey(tuple(sorted(labels.items())))

    @property
    def label_map(self) -> dict[str, str]:
        return dict(self.labels)

    def without(self, names) -> "RangeVectorKey":
        ns = set(names)
        return RangeVectorKey(tuple((k, v) for k, v in self.labels
                                    if k not in ns))

    def only(self, names) -> "RangeVectorKey":
        ns = set(names)
        return RangeVectorKey(tuple((k, v) for k, v in self.labels if k in ns))

    def drop_metric(self) -> "RangeVectorKey":
        # hot on the query path (every output key of every range function);
        # memoized per instance
        cached = self.__dict__.get("_no_metric")
        if cached is None:
            cached = self.without((METRIC_LABEL,))
            object.__setattr__(self, "_no_metric", cached)
        return cached

    def __hash__(self) -> int:
        # dict-key hot (label-aligning thousands of series per query, e.g.
        # the extent-merge path); the dataclass-generated hash recomputes
        # the labels-tuple hash on every call — memoize per instance
        h = self.__dict__.get("_hash")
        if h is None:
            h = hash(self.labels)
            object.__setattr__(self, "_hash", h)
        return h

    def __str__(self) -> str:
        return "{" + ",".join(f"{k}={v}" for k, v in self.labels) + "}"


@dataclass
class StepMatrix:
    """A batch of series sharing step timestamps.

    values: float64 [P, K]; histogram results use values [P, K, B] + les [B].
    """

    keys: list[RangeVectorKey]
    values: np.ndarray
    steps_ms: np.ndarray  # int64 [K] epoch millis
    les: np.ndarray | None = None

    @property
    def num_series(self) -> int:
        return len(self.keys)

    @property
    def num_steps(self) -> int:
        return len(self.steps_ms)

    @property
    def is_histogram(self) -> bool:
        return self.values.ndim == 3

    def compact(self) -> "StepMatrix":
        """Drop series with no samples at all.

        On device-resident values compaction is DEFERRED to
        ``materialize()``: the boolean row mask needs host arrays, and
        fetching here would cost one blocking device→host round trip per
        query. The flag rides along
        so whichever boundary materializes (including the coalesced
        batch-fetch in ``query_range_many``) applies the same mask."""
        if self.num_series == 0:
            return self
        if not isinstance(self.values, np.ndarray):
            self._pending_compact = True
            return self
        keep = self._keep_mask()
        if keep.all():
            return self
        keys = [k for k, m in zip(self.keys, keep) if m]
        return StepMatrix(keys, self.values[keep], self.steps_ms, self.les)

    def derive(self, keys, values, les=None) -> "StepMatrix":
        """Copy-construct a result whose rows still correspond 1:1 to (a
        subset/permutation of) this matrix's rows. Deferred compaction
        carries over: the all-NaN row mask is recomputed from the NEW
        values at materialize(), so reorder/slice/elementwise transforms
        stay correct."""
        out = StepMatrix(keys, values, self.steps_ms, les)
        if getattr(self, "_pending_compact", False):
            out._pending_compact = True
        return out

    def _keep_mask(self) -> np.ndarray:
        if self.is_histogram:
            return ~np.all(np.isnan(self.values[:, :, -1]), axis=1)
        return ~np.all(np.isnan(self.values), axis=1)

    @staticmethod
    def empty(steps_ms: np.ndarray | None = None) -> "StepMatrix":
        steps = steps_ms if steps_ms is not None else np.array([], np.int64)
        return StepMatrix([], np.zeros((0, len(steps))), steps)

    def materialize(self) -> "StepMatrix":
        """Force device-resident values to host numpy (API boundary), then
        apply any compaction deferred while values lived on device (row
        drops mutate in place — callers hold references to this object)."""
        if not isinstance(self.values, np.ndarray):
            self.values = np.asarray(self.values)
        if getattr(self, "_pending_compact", False):
            self._pending_compact = False
            keep = self._keep_mask()
            if not keep.all():
                self.keys = [k for k, m in zip(self.keys, keep) if m]
                self.values = self.values[keep]
        return self

    @staticmethod
    def concat(parts: list["StepMatrix"]) -> "StepMatrix":
        parts = [p for p in parts if p.num_series > 0]
        if not parts:
            return StepMatrix.empty()
        if len(parts) == 1:
            return parts[0]  # keep possibly-device values intact
        keys = [k for p in parts for k in p.keys]
        if any(not isinstance(p.values, np.ndarray) for p in parts):
            # device-resident parts stay on device: a host concat here would
            # force one blocking fetch per scatter-gather leaf; the service
            # boundary materializes once
            import jax.numpy as jnp
            values = jnp.concatenate([jnp.asarray(p.values) for p in parts],
                                     axis=0)
        else:
            values = np.concatenate([p.values for p in parts], axis=0)
        out = StepMatrix(keys, values, parts[0].steps_ms, parts[0].les)
        if any(getattr(p, "_pending_compact", False) for p in parts):
            # deferred compaction survives concatenation (row-preserving
            # transforms use derive()) so the materialize boundary still
            # applies the row mask
            out._pending_compact = True
        return out


@dataclass
class ScalarResult:
    """A per-step scalar (time(), scalar(v), scalar literals)."""

    values: np.ndarray  # [K]
    steps_ms: np.ndarray


@dataclass
class QueryError:
    message: str
    query_id: str = ""


@dataclass
class QueryStats:
    series_scanned: int = 0
    samples_scanned: int = 0
    result_series: int = 0
    wall_time_s: float = 0.0
    cpu_prep_s: float = 0.0
    device_time_s: float = 0.0
    # distributed observability: leaf/decode/reduce attribution merged
    # across remote children by the gather's settle() fold
    chunks_touched: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    wire_bytes: int = 0
    admission_wait_s: float = 0.0
    decode_s: float = 0.0
    reduce_s: float = 0.0
    # chunk-window folds served from aggregate sidecars without decoding
    # (engine/sidecar_lane.py); decoded edge chunks land in chunks_touched
    sidecar_chunks: int = 0
    # tiered federation (query/federation.py): per-tier attribution of a
    # federated query — {tier: {subqueries, series, samples, chunks,
    # bytes, decodeMs, wallMs}} recorded by TierExec at the routing root;
    # empty for non-federated queries
    tiers: dict = field(default_factory=dict)
    # pyramid-lane attribution (query/engine/pyramid_lane.py): flat
    # numeric counters {bucketNodes, segmentNodes, chunkNodes,
    # decodeNodes, pyramidBytes, payloadBytes} for cold-tier folds
    # served from stored aggregate levels; empty otherwise
    pyramid: dict = field(default_factory=dict)

    def merge_counts(self, other: "QueryStats") -> None:
        """Fold a remote child's stats into this one (count/duration
        accumulators only; wall_time_s/result_series are root-owned)."""
        self.series_scanned += other.series_scanned
        self.samples_scanned += other.samples_scanned
        self.cpu_prep_s += other.cpu_prep_s
        self.device_time_s += other.device_time_s
        self.chunks_touched += other.chunks_touched
        self.cache_hits += other.cache_hits
        self.cache_misses += other.cache_misses
        self.wire_bytes += other.wire_bytes
        self.admission_wait_s += other.admission_wait_s
        self.decode_s += other.decode_s
        self.reduce_s += other.reduce_s
        self.sidecar_chunks += other.sidecar_chunks
        for tier, bucket in other.tiers.items():
            mine = self.tiers.setdefault(tier, {})
            for k, v in bucket.items():
                mine[k] = mine.get(k, 0) + v
        for k, v in other.pyramid.items():
            self.pyramid[k] = self.pyramid.get(k, 0) + v


@dataclass
class TraceContext:
    """Distributed-trace propagation context: rides ``QueryContext`` over
    the plan-shipping wire so remote executors join the root's trace
    (``utils/tracing.py``). ``sampled`` gates remote span collection."""

    trace_id: str = ""
    parent_span_id: int = 0
    sampled: bool = False


@dataclass
class QueryResult:
    result: StepMatrix
    stats: QueryStats = field(default_factory=QueryStats)
    query_id: str = ""
    # partial scatter-gather: some children were lost below the failure
    # threshold (reference HA semantics: degrade, don't fail); the Prom
    # JSON encoder surfaces these as "partial" + "warnings" fields
    partial: bool = False
    warnings: list[str] = field(default_factory=list)
    # remote span-tree ship-back: a sampled executor fills this with
    # Span.as_dict() dicts; the dispatching root grafts them (node-tagged)
    # under its dispatch span and strips them before returning upward
    spans: list = field(default_factory=list)


@dataclass
class PlannerParams:
    """Reference ``PlannerParams`` (spread, sample limits...)."""

    # per-query spread override (reference QueryActor spread overrides,
    # ``QueryActor.scala:56-70``); None = planner default
    spread: "int | None" = None
    sample_limit: int = 1_000_000
    enforce_sample_limit: bool = True
    shard_overrides: list[int] | None = None
    process_failure: bool = True
    # partial scatter-gather tolerance: when True, a gather tolerates
    # child failures up to max_partial_fraction of its children and marks
    # the result partial; above the threshold the query fails. None defers
    # to the process-wide resilience config defaults.
    allow_partial: bool | None = None
    max_partial_fraction: float | None = None
    # per-query scan-time cost budget (utils/governor.QueryBudget); rides
    # the wire with the QueryContext so a distributed query shares one
    # budget across its remote leaves. None = no budget.
    budget: "object | None" = None


@dataclass
class QueryContext:
    """Reference ``QueryContext.scala:44``."""

    query_id: str = field(default_factory=lambda: uuid.uuid4().hex[:12])
    submit_time_ms: int = field(
        default_factory=lambda: int(_time.time() * 1000))
    origin: str = ""
    planner_params: PlannerParams = field(default_factory=PlannerParams)
    # distributed tracing: set by traced_query() when the query is sampled
    # (or joins an active trace); remote executors check trace.sampled
    trace: "TraceContext | None" = None


class QueryLimitExceeded(RuntimeError):
    pass
