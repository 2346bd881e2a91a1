"""Held batches: where the native core reads every row of a selector and
the span the shards hold of it is at most four times the asked range, the mesh
engine builds its batch once over that span and keeps it under a key
without the request's range (``parallel/mesh_engine.py``, ``_held_build``).
Every answer here is held to the exec tree, and where a range build could
have answered, to that build too, row for row:

(a) series and whole ``by`` groups whose samples lie only outside the
    asked range; (b) ranges past the newest sample and before the oldest;
(c) an append between two requests, and ingest between every two; (d)
groupings and range functions over one selector sharing one placement;
(e) selectors the rule keeps on a range build: a histogram column, paged
chunks, Python partitions, a grid past int32 ms; (f) a store that holds
far more than the range; (g) request threads sharing one engine."""

import threading

import numpy as np
import pytest
from mesh_oracle import DATASET, NUM_SHARDS, START, histogram_store

from filodb_tpu.coordinator.ingestion import ingest_routed
from filodb_tpu.coordinator.query_service import QueryService
from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.core.memstore.native_shard import native_available
from filodb_tpu.core.partkey import PartKey
from filodb_tpu.core.record import IngestRecord, RecordContainer, SomeData
from filodb_tpu.core.store.config import StoreConfig
from filodb_tpu.parallel import mesh_engine
from filodb_tpu.parallel.mesh_engine import _M_BATCH, _M_BUILDS
from filodb_tpu.testing.data import counter_series, counter_stream
from filodb_tpu.utils.metrics import BATCH_ROWS_FALLBACK, BATCH_ROWS_NATIVE

pytestmark = pytest.mark.skipif(not native_available(),
                                reason="native library unavailable")

# reads [START - 900, START + 3000] with a 5m window: 3,900 s, more than a
# quarter of the store's 7,190 s, so the store's span is held for it
ARGS = (START - 600, 60, START + 3000)
STEP = 10_000


def gauge(host: str, instance: str) -> PartKey:
    return PartKey.create("gauge", {
        "_metric_": "held_gauge", "_ws_": "demo", "_ns_": "App-0",
        "host": host, "instance": instance})


# (key, first second, last second) relative to START: the series of hosts
# H0-H3 cover the asked range; ``old``/``new`` and the hosts HX/HY have
# samples only before or only after it
SERIES = [(gauge(f"H{i % 4}", f"instance-{i}"), -1800, 3000)
          for i in range(12)] + [
    (gauge("H0", "old"), -3600, -1810),
    (gauge("H1", "new"), 3100, 3590),
    (gauge("HX", "old-0"), -3600, -2400),
    (gauge("HX", "old-1"), -3000, -1200),
    (gauge("HY", "new-0"), 3200, 3590),
]
# the series that stopped reporting before the asked range, marked ended
# at their last sample as a flush marks a stopped series
ENDED = ("old", "old-0", "old-1")


def stream(series, first_s, last_s, seed=3):
    """Every series' samples in time order, 10 s apart."""
    rng = np.random.default_rng(seed)
    container, offset = RecordContainer(), 0
    for t in range(first_s, last_s + 1, STEP // 1000):
        for key, lo, hi in series:
            if lo <= t <= hi:
                container.add(IngestRecord(key, (START + t) * 1000,
                                           (50.0 + rng.normal(),)))
                if len(container) >= 100:
                    yield SomeData(container, offset)
                    container, offset = RecordContainer(), offset + 1
    if len(container):
        yield SomeData(container, offset)


def make_store(series, first_s, last_s, native=True):
    ms = TimeSeriesMemStore()
    for s in range(NUM_SHARDS):
        ms.setup(DATASET, s, StoreConfig(max_chunk_size=100,
                                         groups_per_shard=4,
                                         native_ingest=native))
    ingest_routed(ms, DATASET, stream(series, first_s, last_s), NUM_SHARDS,
                  spread=1)
    return ms


def held_store(native=True):
    ms = make_store(SERIES, -3600, 3600, native)
    for shard in ms.shards_for(DATASET):
        for p in shard.partitions:
            if p is not None and \
                    p.part_key.label_map["instance"] in ENDED:
                shard.mark_part_ended(p.part_id, p.latest_ts)
    return ms


@pytest.fixture(scope="module")
def store():
    return held_store()


def services(ms):
    return (QueryService(ms, DATASET, NUM_SHARDS, spread=1),
            QueryService(ms, DATASET, NUM_SHARDS, spread=1, engine="mesh"))


def assert_same(want, got):
    if want.num_series == got.num_series == 0:
        return
    order_w = np.argsort([str(k) for k in want.keys])
    order_g = np.argsort([str(k) for k in got.keys])
    assert [str(want.keys[i]) for i in order_w] == \
        [str(got.keys[i]) for i in order_g]
    np.testing.assert_allclose(np.asarray(got.values)[order_g],
                               np.asarray(want.values)[order_w],
                               rtol=1e-6, atol=1e-9, equal_nan=True)


def range_built(ms, query, args, monkeypatch):
    """The answer of a range build: the same engine code with the held
    rule saying no."""
    with monkeypatch.context() as m:
        m.setattr(mesh_engine, "_held_build", lambda *a: None)
        return services(ms)[1].query_range(query, *args).result


def builds():
    return {s: c.value for s, c in _M_BUILDS.items()}


def moved(before):
    return {s: v - before[s] for s, v in builds().items()}


QUERIES = {
    "raw": "held_gauge",
    "series": "avg_over_time(held_gauge[5m])",
    "sum-by-host": "sum by (host)(avg_over_time(held_gauge[5m]))",
    "count-by-host": "count by (host)(max_over_time(held_gauge[5m]))",
    "avg-by-host-scaled": "avg by (host)(sum_over_time(held_gauge[5m])) * 2",
    "topk": "topk(3, max_over_time(held_gauge[5m]))",
    "delta": "delta(held_gauge[5m])",
}


# ---------------------------------------------------------------------------
# (a) samples only outside the asked range

@pytest.mark.parametrize("name", list(QUERIES))
def test_outside_series_and_groups_leave_the_range_answer(
        store, name, monkeypatch):
    query = QUERIES[name]
    exec_svc, mesh_svc = services(store)
    b0 = builds()
    got = mesh_svc.query_range(query, *ARGS).result
    assert moved(b0) == {"held": 1, "range": 0}
    assert_same(exec_svc.query_range(query, *ARGS).result, got)
    want = range_built(store, query, ARGS, monkeypatch)
    # the range build's rows, in its order, with its values
    assert [str(k) for k in got.keys] == [str(k) for k in want.keys]
    # to rounding: window sums are differences of prefix sums, which
    # run over the held rows from an earlier first sample
    np.testing.assert_allclose(np.asarray(got.values),
                               np.asarray(want.values), rtol=1e-12,
                               equal_nan=True)
    labels = {v for k in got.keys for _, v in k.labels}
    assert not labels & {"old", "old-0", "old-1", "new", "new-0", "HX",
                         "HY"}


def test_a_series_that_stopped_in_range_is_still_answered(store):
    """``old`` is ended before the asked range; moved earlier, the range
    holds its last samples and the answer has its row."""
    exec_svc, mesh_svc = services(store)
    args = (START - 2400, 60, START - 600)
    query = "avg_over_time(held_gauge[5m])"
    got = mesh_svc.query_range(query, *args).result
    assert_same(exec_svc.query_range(query, *args).result, got)
    assert "old" in {k.label_map["instance"] for k in got.keys}


# ---------------------------------------------------------------------------
# (b) ranges past the newest sample and before the oldest

@pytest.mark.parametrize("args,build", [
    ((START + 1800, 60, START + 5400), "held"),   # ends after the newest
    ((START - 7200, 60, START - 1800), "held"),   # starts before the oldest
    ((START - 9000, 60, START - 7800), None),     # no series: no build
    ((START + 1200, 60, START + 2400), "range"),  # under a quarter of it
], ids=["after-newest", "before-oldest", "empty", "short"])
@pytest.mark.parametrize("name", ["raw", "sum-by-host", "topk"])
def test_ranges_beyond_the_held_span(store, args, build, name, monkeypatch):
    query = QUERIES[name]
    exec_svc, mesh_svc = services(store)
    b0 = builds()
    got = mesh_svc.query_range(query, *args).result
    assert moved(b0) == {"held": int(build == "held"),
                         "range": int(build == "range")}
    assert_same(exec_svc.query_range(query, *args).result, got)
    want = range_built(store, query, args, monkeypatch)
    assert [str(k) for k in got.keys] == [str(k) for k in want.keys]
    # to rounding: window sums are differences of prefix sums, which
    # run over the held rows from an earlier first sample
    np.testing.assert_allclose(np.asarray(got.values),
                               np.asarray(want.values), rtol=1e-12,
                               equal_nan=True)


def test_one_held_build_answers_every_long_enough_range(store):
    """The store holds [START - 3600, START + 3590]: a range of at least a
    quarter of that hits the held batch, wherever it lies; a shorter one
    builds its own range, so its answer does not depend on what was
    held."""
    _, mesh_svc = services(store)
    query = QUERIES["sum-by-host"]
    b0, h0 = builds(), _M_BATCH["hit"].value
    for shift in (0, 7, 60, 1200, -2400):
        mesh_svc.query_range(query, ARGS[0] + shift, ARGS[1],
                             ARGS[2] + shift)
    assert moved(b0) == {"held": 1, "range": 0}
    assert _M_BATCH["hit"].value - h0 == 4
    (entry,) = mesh_svc.mesh_engine._batch_cache.values()
    held = entry[1]
    assert isinstance(held, mesh_engine.HeldHeader)
    assert len(held.part_ids) == len(SERIES)
    assert (held.base_ts, held.end) == ((START - 3600) * 1000,
                                        (START + 3590) * 1000)
    mesh_svc.query_range(query, ARGS[0] + 1800, ARGS[1], ARGS[2] - 600)
    assert moved(b0) == {"held": 1, "range": 1}


# ---------------------------------------------------------------------------
# (c) an append drops the held entry

def test_an_append_rebuilds_the_held_batch():
    ms = held_store()
    exec_svc, mesh_svc = services(ms)
    query = "sum by (host)(count_over_time(held_gauge[5m]))"
    args = (START + 300, 60, START + 3900)
    b0 = builds()
    first = mesh_svc.query_range(query, *args).result
    mesh_svc.query_range(query, *args)
    assert moved(b0) == {"held": 1, "range": 0}
    later = [(key, 3600, 3800) for key, _, _ in SERIES[:4]]
    ingest_routed(ms, DATASET, stream(later, 3600, 3800, seed=9),
                  NUM_SHARDS, spread=1)
    again = mesh_svc.query_range(query, *args).result
    assert moved(b0) == {"held": 2, "range": 0}
    assert_same(exec_svc.query_range(query, *args).result, again)
    assert np.nansum(np.asarray(again.values)) > \
        np.nansum(np.asarray(first.values))
    (entry,) = mesh_svc.mesh_engine._batch_cache.values()
    assert entry[0] == sum(s.data_version for s in ms.shards_for(DATASET))


# ---------------------------------------------------------------------------
# (d) groupings and range functions over one selector share a placement

def test_groupings_and_functions_share_one_placement():
    ms = TimeSeriesMemStore()
    for s in range(NUM_SHARDS):
        ms.setup(DATASET, s, StoreConfig(max_chunk_size=100,
                                         groups_per_shard=4))
    keys = counter_series(16, metric="held_requests_total")
    ingest_routed(ms, DATASET, counter_stream(
        keys, 240, start_ms=START * 1000, seed=7, reset_every=70),
        NUM_SHARDS, spread=1)
    exec_svc, mesh_svc = services(ms)
    queries = [f"sum by ({by})({fn}(held_requests_total[5m]))"
               for fn in ("rate", "increase") for by in ("job", "instance")]
    b0 = builds()
    hit0, miss0 = _M_BATCH["hit"].value, _M_BATCH["miss"].value
    for q in queries:
        assert_same(exec_svc.query_range(q, *ARGS).result,
                    mesh_svc.query_range(q, *ARGS).result)
    assert moved(b0) == {"held": 1, "range": 0}
    assert (_M_BATCH["hit"].value - hit0, _M_BATCH["miss"].value - miss0) \
        == (3, 1)
    cache = mesh_svc.mesh_engine._batch_cache
    assert len(cache) == 2                      # one entry a grouping
    (a, b) = cache.values()
    assert a[5][:3] == b[5][:3] and a[1] is b[1]   # one placement
    assert a[5][3] is not b[5][3] and a[4] != b[4]  # own group ids, keys
    prep = mesh_svc.mesh_engine._prep_cache
    assert len(prep) == 1                       # one counter correction


# ---------------------------------------------------------------------------
# (e) selectors the rule keeps on a range build

def test_a_histogram_column_builds_once_a_range():
    ms = histogram_store()
    exec_svc, mesh_svc = services(ms)
    query = "sum(rate(http_req_latency[5m])) by (instance)"
    for shift in (0, 60):
        args = (ARGS[0] + shift, ARGS[1], ARGS[2] + shift)
        b0, f0 = builds(), BATCH_ROWS_FALLBACK.value
        got = mesh_svc.query_range(query, *args).result
        assert moved(b0) == {"held": 0, "range": 1}
        assert BATCH_ROWS_FALLBACK.value - f0 == 5   # five series a build
        assert_same(exec_svc.query_range(query, *args).result, got)
    assert not any(isinstance(e[1], mesh_engine.HeldHeader)
                   for e in mesh_svc.mesh_engine._batch_cache.values())


def test_paged_chunks_build_once_a_range(tmp_path):
    from filodb_tpu.core.store.localstore import (
        LocalDiskColumnStore,
        LocalDiskMetaStore,
    )
    from filodb_tpu.testing.data import gauge_stream, machine_metrics_series

    ms = TimeSeriesMemStore(LocalDiskColumnStore(str(tmp_path / "data")),
                            LocalDiskMetaStore(str(tmp_path / "data")))
    ms.setup(DATASET, 0, StoreConfig(max_chunk_size=50, groups_per_shard=4))
    shard = ms.get_shard(DATASET, 0)
    for sd in gauge_stream(machine_metrics_series(4), 300,
                           start_ms=START * 1000):
        shard.ingest(sd)
    shard.flush_all(ingestion_time=1)
    assert sum(shard.evict_partition_chunks(p.part_id)
               for p in shard.partitions if p) > 0
    exec_svc = QueryService(ms, DATASET, 1, spread=0)
    mesh_svc = QueryService(ms, DATASET, 1, spread=0, engine="mesh")
    query = "sum(count_over_time(heap_usage[55m]))"
    for end in (START + 2990, START + 3000):
        b0, f0 = builds(), BATCH_ROWS_FALLBACK.value
        got = mesh_svc.query_range(query, end - 600, 60, end).result
        assert moved(b0) == {"held": 0, "range": 1}
        assert BATCH_ROWS_FALLBACK.value - f0 == 4   # paged: one at a time
        assert_same(exec_svc.query_range(query, end - 600, 60, end).result,
                    got)


def test_python_partitions_build_once_a_range():
    ms = held_store(native=False)
    exec_svc, mesh_svc = services(ms)
    query = QUERIES["sum-by-host"]
    for shift in (0, 60):
        args = (ARGS[0] + shift, ARGS[1], ARGS[2] + shift)
        b0, n0 = builds(), BATCH_ROWS_NATIVE.value
        got = mesh_svc.query_range(query, *args).result
        assert moved(b0) == {"held": 0, "range": 1}
        assert BATCH_ROWS_NATIVE.value == n0
        assert_same(exec_svc.query_range(query, *args).result, got)


@pytest.mark.parametrize("name", ["raw", "sum-by-host"])
def test_a_grid_past_int32_ms_from_the_held_start_builds_a_range(
        store, name, monkeypatch):
    """A 13-day range holds the store's span, but its grid ends more than
    ~24.8 days after the span's start, past int32 ms from there. The
    series still ingesting are found there, with no sample: the exec tree
    keeps a row for each (an aggregate's groups too, which the mesh
    engine compacts away, range build or held), so the raw rows are held
    to it and the groups to the range build."""
    exec_svc, mesh_svc = services(store)
    args = (START + 20 * 86_400, 3600, START + 33 * 86_400)
    query = QUERIES[name]
    b0 = builds()
    got = mesh_svc.query_range(query, *args).result
    assert moved(b0) == {"held": 0, "range": 1}
    want = exec_svc.query_range(query, *args).result if name == "raw" \
        else range_built(store, query, args, monkeypatch)
    assert_same(want, got)


# ---------------------------------------------------------------------------
# (f) a store that holds far more than the range, and ingest between requests

LONG = [(gauge(f"H{i % 2}", f"instance-{i}"), -43_200, 0) for i in range(6)]


def test_a_long_store_keeps_the_range_build():
    """12 h in the store: its last half hour (2,100 s with the 5m window)
    is built alone, 211 samples a series and not the store's 4,321, and a
    range of a quarter of the store or more holds it all."""
    ms = make_store(LONG, -43_200, 0)
    exec_svc, mesh_svc = services(ms)
    query = QUERIES["sum-by-host"]
    cache = mesh_svc.mesh_engine._batch_cache
    b0 = builds()
    for args, build, samples in (
            ((START - 1800, 60, START), "range", 211),
            ((START - 21_600, 600, START), "held", 4321)):
        assert_same(exec_svc.query_range(query, *args).result,
                    mesh_svc.query_range(query, *args).result)
        assert moved(b0)[build] == 1
        assert list(cache.values())[-1][1].counts.max() == samples
    assert moved(b0) == {"held": 1, "range": 1}


def test_ingest_between_requests_holds_while_the_span_is_four_ranges():
    """A dashboard of the last half hour (2,100 s with the 5m window) under
    ingest, over a store of 7,800 s: each append moves the data version,
    so the first request after it builds again, held while the store's
    span is at most 8,400 s and then over the range alone; the next
    request hits a held batch and builds another range."""
    live = [(key, -7800, 900) for key, _, _ in LONG]
    ms = make_store(live, -7800, 0)
    exec_svc, mesh_svc = services(ms)
    query = QUERIES["sum-by-host"]
    for end, build in ((0, "held"), (300, "held"), (600, "held"),
                       (900, "range")):
        if end:
            ingest_routed(ms, DATASET, stream(live, end - 290, end,
                                              seed=end), NUM_SHARDS,
                          spread=1)
        b0, h0 = builds(), _M_BATCH["hit"].value
        for shift in (0, -7):
            args = (START + end - 1800 + shift, 60, START + end + shift)
            assert_same(exec_svc.query_range(query, *args).result,
                        mesh_svc.query_range(query, *args).result)
        held = build == "held"
        assert moved(b0) == {"held": int(held), "range": 2 * (not held)}
        assert _M_BATCH["hit"].value - h0 == int(held)
        newest = list(mesh_svc.mesh_engine._batch_cache.values())[-1]
        # a held build reads at most four times a range's 211 samples;
        # the newest range build is the second request's, 7 s earlier: 210
        assert newest[1].counts.max() == \
            ((7800 + end) // 10 + 1 if held else 210)


# ---------------------------------------------------------------------------
# (g) request threads share one engine

def test_request_threads_share_one_engine(store):
    """Two threads ask groupings and functions of one selector while the
    cache, cut to two entries, evicts under them: another grouping's scan
    for a lender meets the other thread's puts."""
    exec_svc, mesh_svc = services(store)
    mesh_svc.mesh_engine._batch_cache_cap = 2
    work = [(QUERIES[name], (ARGS[0] + shift, ARGS[1], ARGS[2] + shift))
            for name in ("sum-by-host", "topk", "count-by-host", "delta")
            for shift in (0, 17, 34)]
    want = [exec_svc.query_range(q, *args).result for q, args in work]
    errors = []

    def ask(order):
        try:
            for i in order:
                query, args = work[i]
                assert_same(want[i], mesh_svc.query_range(query, *args).result)
        except Exception as e:       # noqa: BLE001 — the thread's failure
            errors.append(e)

    order = list(range(len(work)))
    threads = [threading.Thread(target=ask, args=(o,))
               for o in (order, order[::-1])]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
