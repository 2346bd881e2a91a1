"""``build_batch`` fills the rows of series that live in a native shard core
through the core — ``batch_count`` then ``batch_fill``, one call each a
shard — and the batch equals, bit for bit, what the per-series builder made
(kept word for word in ``batch_oracle.py``): ``ts``, ``vals``, ``counts``,
shape, dtype, ``part_ids``. Every series the native reader declines, and
every series that does not live in a core, still takes the per-series path,
in the same batch.

x64 is on here (``device_float()`` is f64); the same matrix runs with x64
off, as a server does, in ``test_f32_mode.py``."""

import threading

import numpy as np
import pytest
from batch_oracle import (
    MAX_CHUNK,
    RANGES,
    STEP,
    T0,
    World,
    mismatches,
    per_series_batch,
)

from filodb_tpu.core.memstore.native_shard import (
    NativeBackedPartition,
    NativeShardCore,
    native_available,
)
from filodb_tpu.core.memstore.partition import (
    TimeSeriesPartition,
    chunks_queried,
)
from filodb_tpu.core.partkey import PartKey
from filodb_tpu.core.schemas import DEFAULT_SCHEMAS
from filodb_tpu.memory import codecs
from filodb_tpu.query.engine.batch import TS_PAD, build_batch
from filodb_tpu.utils import tracing
from filodb_tpu.utils.metrics import BATCH_ROWS_FALLBACK, BATCH_ROWS_NATIVE

pytestmark = pytest.mark.skipif(not native_available(),
                                reason="native library unavailable")


@pytest.fixture(scope="module")
def world():
    return World()


def both(parts, lo, hi, **kw):
    with np.errstate(over="ignore"):        # 1e40 -> f32 inf, in both
        return build_batch(parts, lo, hi, **kw), \
            per_series_batch(parts, lo, hi, **kw)


def read_tags(parts, lo, hi, **kw):
    """``batch-read``'s tags for one traced build."""
    tr = tracing.Trace()
    with tracing.activate(tr):
        build_batch(parts, lo, hi, **kw)
    (read,) = [s for s in tr.spans if s.name == "batch-read"]
    return read.tags


# --- the matrix: every range x dtype x mesh rounding, all series at once ----

@pytest.mark.parametrize("multiples", [(1, 1), (4, 2), (3, 1)],
                         ids=["1x1", "4x2", "3x1"])
@pytest.mark.parametrize("host_f64", [True, False], ids=["f64", "device"])
@pytest.mark.parametrize("rng", RANGES)
def test_native_fill_equals_the_per_series_builder(rng, host_f64, multiples,
                                                   world):
    lo, hi = RANGES[rng]
    got, want = both(world.parts, lo, hi, host_f64=host_f64,
                     mesh_multiples=multiples)
    assert mismatches(got, want) == []
    assert got.ts.shape[0] % multiples[0] == 0
    assert got.ts.shape[1] % multiples[1] == 0
    if rng == "all":
        # the matrix is not vacuous: ragged counts, an empty row, NaNs
        # dropped (a 1,000-sample series with 7 NaNs keeps 993)
        real = got.counts[: len(world.parts)]
        assert real.max() == 1300 and real.min() == 0
        assert 993 in real and 995 in real


@pytest.mark.parametrize("rng", ["all", "cuts-sealed", "cuts-buffer"])
def test_rows_that_engage_are_counted(rng, world):
    lo, hi = RANGES[rng]
    n0, f0 = BATCH_ROWS_NATIVE.value, BATCH_ROWS_FALLBACK.value
    tags = read_tags(world.parts, lo, hi)
    assert tags["partitions"] == len(world.parts)
    assert tags["native_rows"] == len(world.native_parts)
    assert tags["fallback_rows"] == len(world.python_parts)
    # the counters move by the same numbers
    assert BATCH_ROWS_NATIVE.value - n0 == tags["native_rows"]
    assert BATCH_ROWS_FALLBACK.value - f0 == tags["fallback_rows"]


@pytest.mark.parametrize("kind", [
    "regular", "whole-numbers", "jitter", "buffer-only", "sealed-only",
    "three-sealed", "one-sample", "tiny-chunks", "nan-in-chunk",
    "nan-in-buffer", "all-nan", "extremes", "empty"])
def test_each_kind_of_series_alone(kind, world):
    """One kind a batch, so the power of two over the largest KEPT count is
    that kind's own (NaNs dropped before the shape is chosen)."""
    parts = world.by_kind[kind][:4]
    assert all(isinstance(p, NativeBackedPartition) for p in parts)
    for lo, hi in RANGES.values():
        got, want = both(parts, lo, hi, host_f64=False)
        assert mismatches(got, want) == []
    if kind == "all-nan":
        assert got.counts.sum() == 0 and got.ts.shape[1] == 8


@pytest.mark.parametrize("pad_series,pad_samples",
                         [(False, False), (True, False), (False, True)])
def test_unpadded_batches(pad_series, pad_samples, world):
    lo, hi = RANGES["cuts-sealed"]
    got, want = both(world.parts, lo, hi, pad_series=pad_series,
                     pad_samples=pad_samples)
    assert mismatches(got, want) == []


def test_chunks_queried_counts_the_same_chunks(world):
    for lo, hi in RANGES.values():
        c0 = chunks_queried.value
        build_batch(world.parts, lo, hi)
        c1 = chunks_queried.value
        per_series_batch(world.parts, lo, hi)
        assert c1 - c0 == chunks_queried.value - c1
    assert c1 - c0 == 0                     # "after": no chunk in range
    lo, hi = RANGES["all"]
    c0 = chunks_queried.value
    build_batch(world.by_kind["three-sealed"], lo, hi)
    assert chunks_queried.value - c0 == 3 * len(world.by_kind["three-sealed"])


# --- both timestamp codecs, on purpose ---------------------------------------

def test_both_timestamp_codecs_are_in_the_matrix(world):
    regular = world.by_kind["regular"][0].chunks
    jitter = world.by_kind["jitter"][0].chunks
    assert {c.vectors[0][0] for c in regular} == \
        {codecs.CODEC_DELTA_DELTA_CONST}
    assert {c.vectors[0][0] for c in jitter} == {codecs.CODEC_DELTA_DELTA}
    assert {c.vectors[1][0] for c in regular + jitter} == \
        {codecs.CODEC_XOR_DOUBLE}


# --- series the native reader must leave to the per-series path -------------

def _evict_first_chunk(p):
    """Flush and evict ``p``'s oldest sealed chunk; returns it, as on-demand
    paging would hand it back."""
    first = p.make_flush_chunks(flush_buffer=False)[0]
    p.mark_flushed(first.id)
    assert p.evict_flushed_chunks() == 1
    return first


@pytest.mark.parametrize("by", ["extra_by_obj", "extra_chunks"])
def test_paged_partitions_take_the_per_series_path(by):
    w = World(seed=9, python_shard=False)
    victims = w.by_kind["regular"][:2] + w.by_kind["three-sealed"][1:2]
    if by == "extra_chunks":                # part ids repeat across shards
        parts = [p for p in w.native_parts if p.shard == victims[0].shard]
        victims = [p for p in victims if p.shard == victims[0].shard]
        extra = {p.part_id: [_evict_first_chunk(p)] for p in victims}
    else:
        parts = w.native_parts
        extra = {id(p): [_evict_first_chunk(p)] for p in victims}
    lo, hi = RANGES["all"]
    got, want = both(parts, lo, hi, **{by: extra})
    assert mismatches(got, want) == []
    # the paged chunk is in the batch, merged by chunk id
    row = parts.index(victims[0])
    assert got.counts[row] == 1000 and got.ts[row, 0] == T0 - lo
    tags = read_tags(parts, lo, hi, **{by: extra})
    assert tags["fallback_rows"] == len(victims)
    # without the paged chunks the same series are read by the core again
    got, want = both(parts, lo, hi)
    assert mismatches(got, want) == [] and got.counts[row] == 600
    assert read_tags(parts, lo, hi)["fallback_rows"] == 0


def _hist_world():
    from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.core.store.config import StoreConfig

    ms = TimeSeriesMemStore()
    shard = ms.setup("ds", 0, StoreConfig(max_chunk_size=40))
    les = np.array([0.1, 1.0, 10.0, np.inf])
    parts = []
    for i in range(3):
        key = PartKey.create("prom-histogram", {
            "_metric_": "lat", "_ws_": "w", "_ns_": "n", "i": str(i)})
        p = shard.get_or_create_partition(key, T0)
        counts = np.zeros(4, np.int64)
        for k in range(100):
            counts += np.array([1, 2, 3, 4]) * (i + 1)
            assert p.ingest(T0 + k * STEP,
                            (float(counts[-1]) * 0.5, float(counts[-1]),
                             (les, counts.copy())))
        parts.append(p)
    assert all(isinstance(p, NativeBackedPartition) for p in parts)
    return parts


def test_a_histogram_batch_is_read_per_series():
    parts = _hist_world()
    lo, hi = T0 + 100_000, T0 + 900_000
    got, want = both(parts, lo, hi)
    assert got.is_histogram and mismatches(got, want) == []
    tags = read_tags(parts, lo, hi)
    assert (tags["native_rows"], tags["fallback_rows"]) == (0, 3)


@pytest.mark.parametrize("col", [1, 2], ids=["sum", "count"])
def test_a_scalar_column_of_a_histogram_partition_is_native(col):
    parts = _hist_world()
    lo, hi = T0 + 100_000, T0 + 900_000
    got, want = both(parts, lo, hi, value_col=col)
    assert not got.is_histogram and mismatches(got, want) == []
    assert got.counts[:3].tolist() == [81, 81, 81]
    assert read_tags(parts, lo, hi, value_col=col)["native_rows"] == 3


def test_a_histogram_among_scalars_fails_as_it_did(world):
    """The per-series builder cannot stack a scalar row into a 3-D batch; the
    native rows are handed back to it, so the failure is the same."""
    parts = world.by_kind["regular"][:2] + _hist_world()[:1]
    lo, hi = RANGES["all"]
    with pytest.raises(ValueError) as old:
        per_series_batch(parts, lo, hi)
    with pytest.raises(ValueError) as new:
        build_batch(parts, lo, hi)
    assert str(new.value) == str(old.value)


def test_each_partition_resolves_its_own_value_column():
    """``value_col=None``: a gauge reads column 1 and a downsampled gauge
    column 5 (``avg``), in one batch and from one core — two native calls."""
    from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.core.store.config import StoreConfig

    ms = TimeSeriesMemStore()
    shard = ms.setup("ds", 0, StoreConfig(max_chunk_size=MAX_CHUNK))
    rng = np.random.default_rng(3)
    parts = []
    for i in range(6):
        ds = i % 2 == 1
        key = PartKey.create("ds-gauge" if ds else "gauge", {
            "_metric_": "m", "_ws_": "w", "_ns_": "n", "i": str(i)})
        p = shard.get_or_create_partition(key, T0)
        for k in range(500 + 10 * i):
            row = rng.standard_normal(5 if ds else 1)
            assert p.ingest(T0 + k * STEP, tuple(row.tolist()))
        parts.append(p)
    lo, hi = T0 + 1_000_000, T0 + 4_900_000
    got, want = both(parts, lo, hi)
    assert mismatches(got, want) == []
    assert read_tags(parts, lo, hi)["native_rows"] == 6
    for col in (1, 3, 5):                   # and a stated column of ds-gauge
        got, want = both(parts[1::2], lo, hi, value_col=col)
        assert mismatches(got, want) == []
    # a column one of the schemas does not have: the per-series path's error
    with pytest.raises(IndexError):
        per_series_batch(parts, lo, hi, value_col=4)
    with pytest.raises(IndexError):
        build_batch(parts, lo, hi, value_col=4)


@pytest.mark.parametrize("flag", [1, 2, 4, 8, 16],
                         ids=["dead", "column", "histogram", "unsorted",
                              "codec"])
def test_a_flagged_row_is_read_per_series(flag, world, monkeypatch):
    """Whatever the reason the core declines a row — its own appends never
    leave timestamps out of order, so that flag is forced here — the row is
    re-read the old way and the others stay native."""
    real = NativeShardCore.batch_count
    flagged = {id(p) for p in world.by_kind["jitter"][:2]
               + world.by_kind["nan-in-chunk"][3:]}
    where = {(id(p._core), p.part_id) for p in world.native_parts
             if id(p) in flagged}

    def batch_count(self, pids, col, t0, t1):
        kept, chunks, flags = real(self, pids, col, t0, t1)
        for i, pid in enumerate(pids.tolist()):
            if (id(self), pid) in where:
                flags[i], kept[i] = flag, 0
        return kept, chunks, flags

    monkeypatch.setattr(NativeShardCore, "batch_count", batch_count)
    lo, hi = RANGES["cuts-sealed"]
    c0 = chunks_queried.value
    got = build_batch(world.parts, lo, hi, host_f64=False)
    c1 = chunks_queried.value
    monkeypatch.undo()
    want = per_series_batch(world.parts, lo, hi, host_f64=False)
    assert mismatches(got, want) == []
    assert c1 - c0 == chunks_queried.value - c1     # no chunk counted twice
    monkeypatch.setattr(NativeShardCore, "batch_count", batch_count)
    tags = read_tags(world.parts, lo, hi)
    assert tags["fallback_rows"] == len(world.python_parts) + 3


class _Without:
    """A loaded library that predates an entry point."""

    def __init__(self, lib, *missing):
        self._lib, self._missing = lib, missing

    def __getattr__(self, name):
        if name in self._missing:
            raise AttributeError(name)
        return getattr(self._lib, name)


def test_a_library_without_the_entry_point_stays_per_series():
    w = World(seed=2, python_shard=False)
    for core in {id(p._core): p._core for p in w.native_parts}.values():
        core._lib = _Without(core._lib, "shard_batch_count",
                             "shard_batch_fill")
        assert core.batch_count(np.zeros(1, np.int32), 0, 0, 1) is None
    lo, hi = RANGES["cuts-sealed"]
    got, want = both(w.native_parts, lo, hi, host_f64=False)
    assert mismatches(got, want) == []
    tags = read_tags(w.native_parts, lo, hi)
    assert (tags["native_rows"], tags["fallback_rows"]) == \
        (0, len(w.native_parts))


def test_python_partitions_alone_never_reach_a_core(world):
    assert all(type(p) is TimeSeriesPartition for p in world.python_parts)
    lo, hi = RANGES["all"]
    got, want = both(world.python_parts, lo, hi)
    assert mismatches(got, want) == []
    assert read_tags(world.python_parts, lo, hi)["native_rows"] == 0


def test_an_empty_batch_of_no_series():
    got, want = both([], 0, 10)
    assert mismatches(got, want) == [] and got.ts.shape == (8, 8)


# --- the core's two calls on their own ---------------------------------------

def _bare_partition(core, name, ncols=1):
    key = PartKey.create("gauge", {"_metric_": name, "_ws_": "w",
                                   "_ns_": "n"})
    return NativeBackedPartition(core, core.create_part(key, ncols), key,
                                 DEFAULT_SCHEMAS["gauge"], MAX_CHUNK)


@pytest.mark.parametrize("n", [1, 2, 400])
@pytest.mark.parametrize("jitter", [False, True], ids=["const", "delta"])
def test_the_entry_point_against_decode_any(n, jitter):
    """Chunks the core sealed itself, decoded by ``batch_fill`` and by
    ``codecs.decode_any``: the same timestamps and the same doubles. A
    one-row chunk always takes the constant-slope codec."""
    rng = np.random.default_rng(n)
    core = NativeShardCore(MAX_CHUNK, 1)
    p = _bare_partition(core, "m")
    ts = T0 + (np.cumsum(rng.integers(1, 30_000, n)) if jitter
               else np.arange(n) * STEP)
    vals = rng.standard_normal(n) * 1e3
    for t, v in zip(ts.tolist(), vals.tolist()):
        assert p.ingest(t, (v,))
    p.switch_buffers()
    (chunk,) = p.chunks
    want_codec = codecs.CODEC_DELTA_DELTA if jitter and n > 2 \
        else codecs.CODEC_DELTA_DELTA_CONST
    assert chunk.vectors[0][0] == want_codec
    assert chunk.vectors[1][0] == codecs.CODEC_XOR_DOUBLE
    want_ts = codecs.decode_any(chunk.vectors[0])
    want_vals = codecs.decode_any(chunk.vectors[1])
    pids = np.array([p.part_id], np.int32)
    kept, chunks, flags = core.batch_count(pids, 0, T0, T0 + 10**9)
    assert (kept.tolist(), chunks.tolist(), flags.tolist()) == ([n], [1], [0])
    out_ts = np.full((1, 512), TS_PAD, np.int32)
    out_vals = np.full((1, 512), np.nan)
    counts = np.zeros(1, np.int32)
    core.batch_fill(pids, 0, T0, T0 + 10**9, np.zeros(1, np.int32), kept,
                    out_ts, out_vals, counts)
    assert counts[0] == n
    np.testing.assert_array_equal(out_ts[0, :n], want_ts - T0)
    assert out_vals[0, :n].tobytes() == want_vals.tobytes()
    assert (out_ts[0, n:] == TS_PAD).all() and np.isnan(out_vals[0, n:]).all()


def test_f32_rows_are_numpys_rounding():
    """What x64-off servers place: the C cast of ``batch_fill`` is numpy's
    f64 -> f32 assignment, bit for bit."""
    w = World(seed=4, python_shard=False)
    lo, hi = RANGES["all"]
    want = per_series_batch(w.native_parts, lo, hi)
    P, S = want.vals.shape
    ts = np.full((P, S), TS_PAD, np.int32)
    vals = np.zeros((P, S), np.float32)
    counts = np.zeros(P, np.int32)
    rows_of = {}
    for i, p in enumerate(w.native_parts):
        rows_of.setdefault(id(p._core), (p._core, [], []))
        rows_of[id(p._core)][1].append(i)
        rows_of[id(p._core)][2].append(p.part_id)
    for core, rows, pids in rows_of.values():
        rows, pids = np.asarray(rows, np.int32), np.asarray(pids, np.int32)
        kept, _, flags = core.batch_count(pids, 0, lo, hi)
        assert not flags.any()
        core.batch_fill(pids, 0, lo, hi, rows, kept, ts, vals, counts)
    with np.errstate(over="ignore"):
        want_f32 = np.asarray(np.nan_to_num(want.vals, nan=0.0), np.float32)
    assert vals.tobytes() == want_f32.tobytes()
    assert ts.tobytes() == want.ts.tobytes()
    assert counts.tobytes() == want.counts.tobytes()


def test_the_core_says_why_it_declines():
    core = NativeShardCore(40, 1)
    p = _bare_partition(core, "scalar")
    for k in range(100):
        assert p.ingest(T0 + k * STEP, (float(k),))
    hist = _hist_world()[0]
    pids = np.array([p.part_id, 7, -1], np.int32)
    kept, chunks, flags = core.batch_count(pids, 0, T0, T0 + 10**9)
    assert flags.tolist() == [0, 1, 1] and kept.tolist() == [100, 0, 0]
    assert chunks.tolist() == [2, 0, 0]
    _, _, flags = core.batch_count(pids[:1], 1, T0, T0 + 10**9)
    assert flags.tolist() == [2]                    # no such column
    _, _, flags = core.batch_count(pids[:1], -1, T0, T0 + 10**9)
    assert flags.tolist() == [2]
    hpid = np.array([hist.part_id], np.int32)
    _, _, flags = hist._core.batch_count(hpid, 2, T0, T0 + 10**9)
    assert flags.tolist() == [4]                    # the histogram column
    p.free()
    _, _, flags = core.batch_count(pids[:1], 0, T0, T0 + 10**9)
    assert flags.tolist() == [1]                    # freed


def test_fill_keeps_at_most_what_was_counted():
    """Between the two calls a series may grow; the fill stops at the count
    the batch was sized for, and skips rows given as -1."""
    core = NativeShardCore(MAX_CHUNK, 1)
    a, b = _bare_partition(core, "a"), _bare_partition(core, "b")
    for k in range(450):
        assert a.ingest(T0 + k * STEP, (float(k),))
        assert b.ingest(T0 + k * STEP, (float(-k),))
    pids = np.array([a.part_id, b.part_id], np.int32)
    hi = T0 + 10**9
    kept, _, _ = core.batch_count(pids, 0, T0, hi)
    assert kept.tolist() == [450, 450]
    for k in range(450, 900):               # seals the buffer that was read
        assert a.ingest(T0 + k * STEP, (float(k),))
    ts = np.full((2, 512), TS_PAD, np.int32)
    vals = np.full((2, 512), np.nan)
    counts = np.full(2, -7, np.int32)
    core.batch_fill(pids, 0, T0, hi, np.array([1, -1], np.int32), kept, ts,
                    vals, counts)
    assert counts.tolist() == [-7, 450]
    np.testing.assert_array_equal(vals[1, :450], np.arange(450.0))
    assert np.isnan(vals[1, 450:]).all() and np.isnan(vals[0]).all()
    assert (ts[0] == TS_PAD).all()


# --- a writer beside the reader ----------------------------------------------

def test_builds_beside_an_ingest_thread_see_consistent_prefixes():
    """An ingest thread appends and seals while batches are built: every row
    is a prefix of its series — the first ``count`` samples, in order, with
    the values that belong to them — and later builds never see less."""
    from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.core.store.config import StoreConfig

    ms = TimeSeriesMemStore()
    shards = [ms.setup("ds", s, StoreConfig(max_chunk_size=64))
              for s in range(2)]
    parts = []
    for i in range(24):
        key = PartKey.create("gauge", {"_metric_": "m", "_ws_": "w",
                                       "_ns_": "n", "i": str(i)})
        parts.append(shards[i % 2].get_or_create_partition(key, T0))
    total = 1500
    errors, done = [], threading.Event()

    def writer():
        try:
            for k in range(total):
                for i, p in enumerate(parts):
                    assert p.ingest(T0 + k * STEP, (float(k * 100 + i),))
        except Exception as e:  # pragma: no cover - the regression
            errors.append(e)
        finally:
            done.set()

    t = threading.Thread(target=writer)
    t.start()
    builds, seen, last = 0, np.zeros(len(parts), np.int64), False
    partial = 0                 # builds that met the writer half way
    try:
        while not last:
            last = builds >= 200 and done.is_set()  # one more, after the end
            b = build_batch(parts, T0, T0 + total * STEP, host_f64=False)
            builds += 1
            n = b.counts[: len(parts)].astype(np.int64)
            assert (n >= seen).all()
            partial += bool(((n > 0) & (n < total)).any())
            seen = n
            k = np.arange(b.ts.shape[1])
            live = k[None, :] < n[:, None]
            want_ts = np.where(live, k[None, :] * STEP, TS_PAD)
            want_vals = np.where(
                live, k[None, :] * 100.0 + np.arange(len(parts))[:, None],
                0.0)
            np.testing.assert_array_equal(b.ts[: len(parts)], want_ts)
            np.testing.assert_array_equal(b.vals[: len(parts)], want_vals)
    finally:
        t.join(timeout=120)
    assert not t.is_alive() and not errors, errors
    assert builds > 200 and partial > 0 and (seen == total).all()
