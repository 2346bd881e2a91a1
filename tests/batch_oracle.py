"""The plain oracle for ``build_batch``: the per-series builder as it stood
before series that live in a native shard core were read by the core in one
call a shard. One ``read_samples`` a series (``chunks``, the decode memo, the
range mask, the buffer copy, the stable sort), the NaN filter, then one
allocation and two row writes a series — kept here word for word, as the
reference the native fill is held to bit for bit (``ts``, ``vals``,
``counts``, shape, dtype, ``part_ids``).

Not a test module: imported by ``test_batch_native_fill.py`` and the
x64-off subprocess of ``test_f32_mode.py``.
"""

from __future__ import annotations

import numpy as np

from filodb_tpu.core.memstore.partition import TimeSeriesPartition
from filodb_tpu.memory.codecs import HistogramColumn
from filodb_tpu.query.engine.batch import (
    TS_PAD,
    SeriesBatch,
    _next_pow2,
    _round_up,
    device_float,
)
from filodb_tpu.utils.tracing import span


def per_series_batch(partitions: list[TimeSeriesPartition], start: int, end: int,
                value_col: int | None = None, pad_series: bool = True,
                pad_samples: bool = True,
                extra_chunks: dict[int, list] | None = None,
                extra_by_obj: dict[int, list] | None = None,
                mesh_multiples: tuple[int, int] = (1, 1),
                host_f64: bool = True) -> SeriesBatch:
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
    """
    per_ts: list[np.ndarray] = []
    per_vals: list = []
    les = None
    with span("batch-read", partitions=len(partitions)):
        for p in partitions:
            extra = extra_by_obj.get(id(p)) if extra_by_obj else None
            if extra is None and extra_chunks:
                extra = extra_chunks.get(p.part_id)
            ts, vals = p.read_samples(start, end, value_col,
                                      extra_chunks=extra)
            if isinstance(vals, HistogramColumn):
                les = vals.les if les is None or len(vals.les) > len(les) \
                    else les
                rows = vals.rows.astype(np.float64)
                per_ts.append(ts)
                per_vals.append(rows)
            else:
                valid = ~np.isnan(vals)
                per_ts.append(ts[valid])
                per_vals.append(vals[valid])

    with span("batch-stack") as sp:
        P = len(partitions)
        maxS = max((len(t) for t in per_ts), default=0)
        S = _round_up(_next_pow2(maxS) if pad_samples else max(maxS, 1),
                      mesh_multiples[1])
        Pp = _round_up(_next_pow2(P) if pad_series else max(P, 1),
                       mesh_multiples[0])
        ts_arr = np.full((Pp, S), TS_PAD, np.int32)
        if les is not None:
            B = len(les)
            vals_arr = np.zeros((Pp, S, B), np.float64)
        elif host_f64:
            vals_arr = np.full((Pp, S), np.nan, np.float64)
        else:
            # in-count samples are never NaN (filtered above), so 0 for
            # padding is all the mesh kernels need beside the validity mask
            vals_arr = np.zeros((Pp, S), device_float())
        counts = np.zeros(Pp, np.int32)
        for i, (t, v) in enumerate(zip(per_ts, per_vals)):
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


# --- the series the parity tests build batches of ---------------------------

T0 = 1_600_000_000_000
STEP = 10_000
MAX_CHUNK = 400

# start, end (ms) against series that start at T0 and tick every 10 s: with
# 400-sample chunks a 1,000-sample series holds samples 0..399 and 400..799
# sealed and 800..999 in its write buffer
RANGES = {
    "all": (T0 - 5, T0 + 20_000_000),
    "cuts-sealed": (T0 + 1_234_567, T0 + 6_000_001),
    "cuts-buffer": (T0 + 8_500_000, T0 + 9_400_000),
    "sealed-only": (T0 + 500_000, T0 + 7_990_000),
    "chunk-edges": (T0 + 4_000_000, T0 + 7_990_000),
    "buffer-only": (T0 + 8_000_000, T0 + 20_000_000),
    "one-sample": (T0 + 4_000_000, T0 + 4_000_000),
    "before": (T0 - 10_000_000, T0 - 1),
    "after": (T0 + 40_000_000, T0 + 50_000_000),
}


def _walk(rng, n, whole=False):
    v = 50.0 + np.cumsum(rng.standard_normal(n))
    return np.rint(v) if whole else v


def _series(rng):
    """kind -> (ts int64[n], vals f64[n], seal_after): every shape of series
    the fill must get right. ``seal_after`` seals the buffer once that many
    samples are in (chunks of 1 and 2 rows)."""
    reg = lambda n: T0 + np.arange(n, dtype=np.int64) * STEP
    jit = lambda n: T0 + np.cumsum(rng.integers(7_000, 13_000, n))
    nan_at = lambda v, idx: np.where(np.isin(np.arange(len(v)), idx),
                                     np.nan, v)
    return {
        "regular": (reg(1000), _walk(rng, 1000), ()),
        "whole-numbers": (reg(1000), _walk(rng, 1000, whole=True), ()),
        "jitter": (jit(1000), _walk(rng, 1000), ()),
        "buffer-only": (reg(50) + 8_100_000, _walk(rng, 50), ()),
        "sealed-only": (reg(800), _walk(rng, 800), ()),
        "three-sealed": (jit(1300), _walk(rng, 1300), ()),
        "one-sample": (reg(1) + 4_000_000, np.array([7.25]), ()),
        "tiny-chunks": (reg(30), _walk(rng, 30), (1, 3)),
        "nan-in-chunk": (reg(1000), nan_at(_walk(rng, 1000),
                                           [0, 5, 6, 7, 399, 400, 650]), ()),
        "nan-in-buffer": (jit(1000), nan_at(_walk(rng, 1000),
                                            [800, 900, 901, 902, 999]), ()),
        "all-nan": (reg(450), np.full(450, np.nan), ()),
        # what f64 -> f32 has to round, overflow, flush and keep the sign of
        "extremes": (reg(12), np.array(
            [1e40, -1e40, 1e-50, -0.0, 3.4028235e38, 3.4028236e38,
             16777217.0, 0.1, -1e-46, 1.401298464324817e-45, 2.5, 1e308]),
            ()),
        "empty": (reg(0), np.zeros(0), ()),
    }


class World:
    """Four native shards (and one that ingests in Python) holding every
    kind of series; ``parts`` interleaves the shards row by row."""

    def __init__(self, seed=5, python_shard=True):
        from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
        from filodb_tpu.core.partkey import PartKey
        from filodb_tpu.core.store.config import StoreConfig

        rng = np.random.default_rng(seed)
        self.ms = TimeSeriesMemStore()
        self.by_kind: dict[str, list] = {}
        self.shards = []
        per_shard = []
        for s in range(4 + bool(python_shard)):
            shard = self.ms.setup("ds", s, StoreConfig(
                max_chunk_size=MAX_CHUNK, native_ingest=s < 4))
            self.shards.append(shard)
            mine = []
            for kind, (ts, vals, seal_after) in _series(rng).items():
                key = PartKey.create("gauge", {
                    "_metric_": "m", "_ws_": "w", "_ns_": "n",
                    "kind": kind, "shard": str(s)})
                p = shard.get_or_create_partition(key, int(T0))
                for k, (t, v) in enumerate(zip(ts.tolist(), vals.tolist())):
                    if k in seal_after:
                        p.switch_buffers()
                    assert p.ingest(t, (v,))
                mine.append(p)
                self.by_kind.setdefault(kind, []).append(p)
            per_shard.append(mine)
        self.native_parts = [p for row in zip(*per_shard[:4]) for p in row]
        self.python_parts = per_shard[4] if python_shard else []
        # a Python partition every fifth row
        self.parts = list(self.native_parts)
        for k, p in enumerate(self.python_parts):
            self.parts.insert(5 * k + 2, p)


def mismatches(got: SeriesBatch, want: SeriesBatch) -> list[str]:
    """Names of what differs between two batches, bit for bit (NaN padding
    and -0.0 included): empty when ``got`` is ``want``."""
    bad = []
    for name in ("ts", "vals", "counts"):
        a, b = getattr(got, name), getattr(want, name)
        if a.shape != b.shape or a.dtype != b.dtype:
            bad.append(f"{name}: {a.dtype}{a.shape} != {b.dtype}{b.shape}")
        elif a.tobytes() != b.tobytes():
            rows = np.nonzero((a.view(np.uint8).reshape(len(a), -1)
                               != b.view(np.uint8).reshape(len(b), -1))
                              .any(axis=1))[0]
            bad.append(f"{name}: rows {rows[:8].tolist()} differ")
    if got.part_ids != want.part_ids:
        bad.append("part_ids")
    if got.base_ts != want.base_ts:
        bad.append("base_ts")
    if (got.les is None) != (want.les is None) or (
            got.les is not None and not np.array_equal(got.les, want.les)):
        bad.append("les")
    return bad
