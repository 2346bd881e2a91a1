"""SeriesBatch: the dense tensor form of a set of time series.

The bridge between the host-side chunk store and the TPU kernels. Decoding
(host, C++/numpy codecs) happens once per query per partition; the result is
packed into padded arrays whose shapes are bucketed (next power of two) so XLA
compilation caches are reused across queries.

``build_batch`` materialises a batch ONCE. Series that live in a native shard
core (``NativeBackedPartition``) are read by the core, two C calls a shard:
span ``batch-read`` covers grouping them by core, ``batch_count`` — the exact
number of samples each row keeps — and a ``read_samples`` call for every
other series (Python partitions, histogram columns, partitions with paged
chunks, rows the core declines); span ``batch-stack`` the one allocation of
``ts``/``vals``/``counts`` at their final shape — the power of two over the
largest kept count, rounded up to the caller's mesh axes where those do not
divide it; ``ts``/``vals`` from the caller's allocator where it passes one
(the mesh engine's staging pool, ``parallel/staging.py``: arrays an earlier
placement gave back, refilled with padding), else new — then ``batch_fill``,
which decodes and writes the native rows straight into them, and the row
writes of the others. Which way a row goes
is read off the partition, never chosen by an option; the batch is the same
bit for bit. A caller that places the batch on a mesh without a host f64
pass (``host_f64=False``) gets ``vals`` in the device's float dtype with 0
for padding: the very array that is placed, so no later step copies,
converts or re-pads it.

Timestamps are rebased to ``base_ts`` and stored as int32 milliseconds —
queries spanning more than ~24 days are split by the planner (reference analog:
time-split planning, ``SingleClusterPlanner.materializeTimeSplitPlan``).
NaN samples (staleness markers) are filtered host-side so kernels may assume
every in-count sample is valid.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple

import numpy as np

from filodb_tpu.core.memstore.native_shard import NativeBackedPartition
from filodb_tpu.core.memstore.partition import (
    TimeSeriesPartition,
    chunks_queried,
)
from filodb_tpu.memory.codecs import HistogramColumn
from filodb_tpu.utils.metrics import BATCH_ROWS_FALLBACK, BATCH_ROWS_NATIVE
from filodb_tpu.utils.tracing import span

TS_PAD = np.iinfo(np.int32).max


def _next_pow2(n: int, floor: int = 8) -> int:
    v = floor
    while v < n:
        v *= 2
    return v


def _round_up(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def fresh_array(shape, dtype, fill=None) -> np.ndarray:
    """``build_batch``'s default allocator: a new array of ``fill`` (None:
    uninitialised). Zeros come from ``calloc``, which touches no page
    before the first write."""
    if fill is None:
        return np.empty(shape, dtype)
    return np.zeros(shape, dtype) if fill == 0 else np.full(shape, fill, dtype)


def device_float() -> np.dtype:
    """The numpy twin of ``kernels.fdtype()``: the dtype a float array has
    once it is on the device (f32 in a server, f64 under x64)."""
    from filodb_tpu.query.engine.kernels import fdtype

    return np.dtype(fdtype())


class _NativeRead(NamedTuple):
    """What one ``NativeShardCore.batch_count`` said of one (core, column)'s
    series, kept for the ``batch_fill`` that follows."""

    core: object
    col: int                # index into the core's columns
    pids: np.ndarray        # int32 [n]
    rows: np.ndarray        # int32 [n]: the batch row of each, -1 declined
    kept: np.ndarray        # int32 [n]: samples each row keeps
    chunks: np.ndarray      # int32 [n]: sealed chunks in range


@dataclass
class BatchHeader:
    """What a placed batch keeps of its host side once the device holds the
    samples: all a batch-cache hit of the mesh engine reads."""

    base_ts: int
    counts: np.ndarray                # int32 [P]
    part_ids: list[int]
    les: np.ndarray | None = None

    @property
    def is_histogram(self) -> bool:
        return self.les is not None

    @property
    def buckets(self) -> int:
        """Rows a series takes once buckets are flattened into the series
        axis: B of a histogram batch's [P, S, B], else 1."""
        return 1 if self.les is None else len(self.les)


@dataclass
class SeriesBatch:
    """Padded batch of P series with up to S samples each.

    ``ts``/``vals`` are host (numpy) arrays. The exec path keeps the batch
    in its shard's cache and uploads them once (:meth:`device_arrays`); the
    mesh engine places them itself and keeps only :meth:`header`, because
    their memory may be a staging buffer that the next build overwrites.
    For histogram batches ``vals`` has shape [P, S, B] and ``les`` [B].
    """

    base_ts: int                      # epoch ms subtracted from all timestamps
    ts: np.ndarray                    # int32 [P, S], padded with TS_PAD
    # f64 [P, S] padded with NaN, or [P, S, B] padded with 0; a scalar batch
    # built with host_f64=False: device_float() [P, S] padded with 0
    vals: np.ndarray
    counts: np.ndarray                # int32 [P]
    part_ids: list[int]               # originating partition ids (host metadata)
    les: np.ndarray | None = None     # [B] bucket bounds for histogram batches

    @property
    def num_series(self) -> int:
        return len(self.part_ids)

    @property
    def is_histogram(self) -> bool:
        return self.vals.ndim == 3

    def header(self) -> BatchHeader:
        return BatchHeader(self.base_ts, self.counts, self.part_ids,
                           self.les)

    def device_arrays(self):
        """(ts, vals, counts) as device arrays, uploaded once per batch —
        cached batches keep data resident on the TPU across queries."""
        dev = getattr(self, "_device", None)
        if dev is None:
            import jax.numpy as jnp

            dev = (jnp.asarray(self.ts), jnp.asarray(self.vals),
                   jnp.asarray(self.counts))
            self._device = dev
        return dev

    def delta_host(self, counter: bool):
        """Rebased values [P,S] f64 for the delta-family range functions
        (rate/increase/delta/irate/idelta/deriv).

        Values are counter-reset-corrected (when ``counter``) and then
        rebased by each series' first in-range value — all HOST-side in
        float64 — so the later float32 device cast only ever sees
        window-scale magnitudes. Without this, a long-lived counter
        ≥2^24 (~16.7M) loses per-window delta precision entirely on the
        f32 device path (reference RateFunctions.scala:1-303 runs in
        double throughout). Prometheus' extrapolate-to-zero clamp needs
        each window's RAW first sample, so kernels additionally take the
        raw value tensor (``device_arrays()[1]``) as a heuristic-only
        reference — f32 rounding there is irrelevant."""
        cache = getattr(self, "_delta_host", None)
        if cache is None:
            cache = self._delta_host = {}
        hit = cache.get(counter)
        if hit is not None:
            return hit
        vals = self.vals
        valid = ~np.isnan(vals)
        v = np.where(valid, vals, 0.0)
        if counter:
            prev = np.concatenate([v[:, :1], v[:, :-1]], axis=1)
            pvalid = np.concatenate(
                [np.zeros_like(valid[:, :1]), valid[:, :-1]], axis=1)
            dropped = (v < prev) & valid & pvalid
            v = v + np.cumsum(np.where(dropped, prev, 0.0), axis=1)
        # samples are packed contiguously from 0, so the first in-range
        # value is column 0 (corrected first == raw first: no prior reset)
        if v.ndim == 3:  # histogram: per-(series, bucket) rebase
            base = np.where(self.counts[:, None] > 0, v[:, 0], 0.0)
            rebased = np.where(valid, v - base[:, None, :], np.nan)
        else:
            base = np.where(self.counts > 0, v[:, 0], 0.0)
            rebased = np.where(valid, v - base[:, None], np.nan)
        cache[counter] = rebased
        return rebased

    def delta_arrays(self, counter: bool):
        """(ts, rebased_vals, counts, raw_vals) device arrays (cached) —
        the device twin of :meth:`delta_host` for the exec kernel path.
        ``raw_vals`` is the shared upload from :meth:`device_arrays`."""
        cache = getattr(self, "_delta_device", None)
        if cache is None:
            cache = self._delta_device = {}
        hit = cache.get(counter)
        if hit is None:
            import jax.numpy as jnp

            rebased = self.delta_host(counter)
            ts_d, raw_d, counts_d = self.device_arrays()
            hit = cache[counter] = (ts_d, jnp.asarray(rebased), counts_d,
                                    raw_d)
        return hit


def build_batch(partitions: list[TimeSeriesPartition], start: int, end: int,
                value_col: int | None = None, pad_series: bool = True,
                pad_samples: bool = True,
                extra_chunks: dict[int, list] | None = None,
                extra_by_obj: dict[int, list] | None = None,
                mesh_multiples: tuple[int, int] = (1, 1),
                host_f64: bool = True, alloc=fresh_array) -> SeriesBatch:
    """Decode chunks overlapping [start, end] into a SeriesBatch.

    ``start`` already includes the lookback/window extension; ``base_ts`` is
    set to ``start`` so all in-range offsets are non-negative.
    ``extra_chunks`` maps part_id → ODP-paged chunks to merge (single-shard
    callers); ``extra_by_obj`` maps ``id(partition)`` → chunks for callers
    batching across shards, where part_ids are not unique.

    ``mesh_multiples`` are the (series, sample) axis sizes of the mesh the
    batch will be sharded over: the padded shape is rounded up to them, so
    it is the placed shape. ``host_f64=False`` says no host f64 pass
    (``delta_host``, the magnitude check) follows: scalar ``vals`` are then
    allocated in :func:`device_float` with 0 for padding, ready to place.
    Histogram batches keep f64 either way (the mesh flattens them first).

    ``alloc(shape, dtype, fill)`` gives ``ts`` and ``vals``: a C-contiguous
    array with ``fill`` in every cell. The default makes new arrays, which
    the batch then owns for its whole life (the exec leaf's cached batches);
    a caller that passes its own answers for the memory's lifetime.
    """
    P = len(partitions)

    def paged(p):
        extra = extra_by_obj.get(id(p)) if extra_by_obj else None
        if extra is None and extra_chunks:
            extra = extra_chunks.get(p.part_id)
        return extra

    per_series: dict[int, tuple] = {}   # row -> (ts, vals) read one by one
    reads: list[_NativeRead] = []       # one a native (core, column)
    les = None

    def read_series(i):
        nonlocal les
        p = partitions[i]
        ts, vals = p.read_samples(start, end, value_col,
                                  extra_chunks=paged(p))
        if isinstance(vals, HistogramColumn):
            les = vals.les if les is None or len(vals.les) > len(les) \
                else les
            per_series[i] = ts, vals.rows.astype(np.float64)
        else:
            valid = ~np.isnan(vals)
            per_series[i] = ts[valid], vals[valid]

    with span("batch-read", partitions=P) as sp:
        # series that live in a native shard core are read by the core, one
        # call a (core, column); every other series one at a time
        groups: dict[tuple, tuple] = {}
        fallback: list[int] = []
        any_paged = bool(extra_by_obj or extra_chunks)
        g = None
        for i, p in enumerate(partitions):
            if not isinstance(p, NativeBackedPartition) \
                    or (any_paged and paged(p)):
                fallback.append(i)
                continue
            col = p.schema.data.value_column if value_col is None \
                else value_col
            core = p._core
            if g is None or g[0] is not core or g[1] != col:
                g = groups.get((id(core), col))
                if g is None:
                    g = groups[id(core), col] = (core, col, [], [])
            g[2].append(i)
            g[3].append(p.part_id)
        for core, col, rows, pids in groups.values():
            rows = np.asarray(rows, np.int32)
            pids = np.asarray(pids, np.int32)
            counted = core.batch_count(pids, col - 1, start, end)
            if counted is None:     # a library without the entry point
                fallback.extend(rows.tolist())
                continue
            kept, nchunks, flags = counted
            if flags.any():         # declined: histogram, unsorted, ...
                fallback.extend(rows[flags != 0].tolist())
                rows = np.where(flags == 0, rows, -1).astype(np.int32)
            reads.append(_NativeRead(core, col - 1, pids, rows, kept,
                                     nchunks))
        fallback.sort()
        for i in fallback:
            read_series(i)
        if les is not None and reads:
            # a histogram among scalar series makes the batch 3-D, which
            # the native fill does not write: read those the old way too
            again = sorted(i for r in reads for i in r.rows.tolist()
                           if i >= 0)
            reads = []
            for i in again:
                read_series(i)
            fallback += again
        for r in reads:
            chunks_queried.inc(int(r.chunks[r.rows >= 0].sum()))
        n_native = P - len(fallback)
        if sp is not None:
            sp.tags.update(native_rows=n_native,
                           fallback_rows=len(fallback))
        BATCH_ROWS_NATIVE.inc(n_native)
        BATCH_ROWS_FALLBACK.inc(len(fallback))

    with span("batch-stack") as sp:
        maxS = max([len(t) for t, _ in per_series.values()]
                   + [int(r.kept.max(initial=0)) for r in reads],
                   default=0)
        S = _round_up(_next_pow2(maxS) if pad_samples else max(maxS, 1),
                      mesh_multiples[1])
        Pp = _round_up(_next_pow2(P) if pad_series else max(P, 1),
                       mesh_multiples[0])
        # the native fill and the row writes leave what lies beyond a row's
        # count as ``alloc`` made it, and the kernels COUNT ``ts <= step``:
        # every pad cell has to read TS_PAD
        ts_arr = alloc((Pp, S), np.int32, TS_PAD)
        if les is not None:
            vals_arr = alloc((Pp, S, len(les)), np.float64, 0)
        elif host_f64:
            vals_arr = alloc((Pp, S), np.float64, np.nan)
        else:
            # in-count samples are never NaN (filtered above), so 0 for
            # padding is all the mesh kernels need beside the validity mask
            vals_arr = alloc((Pp, S), device_float(), 0)
        counts = np.zeros(Pp, np.int32)
        for r in reads:
            r.core.batch_fill(r.pids, r.col, start, end, r.rows, r.kept,
                              ts_arr, vals_arr, counts)
        for i, (t, v) in per_series.items():
            n = len(t)
            counts[i] = n
            if n:
                ts_arr[i, :n] = (t - start).astype(np.int32)
                if les is not None and v.shape[-1] != vals_arr.shape[-1]:
                    # smaller historic scheme
                    vals_arr[i, :n, : v.shape[-1]] = v
                else:
                    vals_arr[i, :n] = v
        if sp is not None:
            sp.tags["shape"] = list(vals_arr.shape)
    return SeriesBatch(start, ts_arr, vals_arr, counts,
                       [p.part_id for p in partitions], les)


def empty_batch() -> SeriesBatch:
    return SeriesBatch(0, np.full((1, 1), TS_PAD, np.int32),
                       np.full((1, 1), np.nan, np.float64),
                       np.zeros(1, np.int32), [])
