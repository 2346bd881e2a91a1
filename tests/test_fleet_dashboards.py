"""The ``fleet-110k`` deployment under concurrent dashboards, at a tiny size:
the benchmark's own generator, loader, cell file and plain f64 reference
(``perf/``, loaded by path: the reference imports nothing of the program)
against ``QueryService.query_range_many`` and ``FastHttpServer`` — what the
cell ``fleet-110k.dash-unaligned`` drives on the chip. Also the counters
that cell's per-layer metrics read, and their readers on canned inputs."""

import http.client
import importlib.util
import json
import os
import sys
import threading

import numpy as np
import pytest

from filodb_tpu.coordinator.query_service import QueryService
from filodb_tpu.http import promjson
from filodb_tpu.http.fastserver import FastHttpServer
from filodb_tpu.utils import tracing
from filodb_tpu.utils.metrics import render_prometheus

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERF = os.path.join(ROOT, "perf")
CELL = "fleet-110k.dash-unaligned"
APPS = 8
# ends at any second of the store's second hour, none on a step boundary
END_OFFSETS = (3607, 4111, 5013, 6543, 7001, 7190)
KEYS = (0, 0, 0, 1, 1, 2)  # three members share a plan signature, two, one
READERS = ("batch_members", "dispatches_per_request", "mesh_ms_per_request",
           "mesh_scan_roofline")


def perf_module(*parts):
    """A file of ``perf/`` loaded by path. ``perf/`` is on the path only
    while it loads, for the readers' ``from measure import ...``."""
    path = os.path.join(PERF, *parts) + ".py"
    spec = importlib.util.spec_from_file_location(
        "fleet_dash_" + "_".join(parts), path)
    mod = importlib.util.module_from_spec(spec)
    sys.path.insert(0, PERF)
    try:
        spec.loader.exec_module(mod)
    finally:
        sys.path.remove(PERF)
    return mod


def read_json(*parts):
    with open(os.path.join(PERF, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def fleet():
    """The configuration at ~400 counters + 200 gauges of 8 apps, loaded
    as ``perf/run.py`` loads it: four default-layout shards."""
    config = read_json("configs", "fleet-110k.json")
    params = {**config["params"], **config["rehearsal"]["params"],
              "apps": APPS}
    metrics = perf_module("generators", config["generator"]).make(params, 24)
    loader = perf_module("loader")
    memstore, report = loader.load(metrics)
    assert report["rows"] == 600 * params["samples"]
    return {"config": config, "params": params, "metrics": metrics,
            "memstore": memstore, "layout": loader.server_layout(),
            "cell": read_json("cells", f"{CELL}.json"),
            "reference": perf_module("reference")}


def service(fleet, result_cache=True):
    layout = fleet["layout"]
    return QueryService(fleet["memstore"], layout["dataset"],
                        layout["num_shards"], spread=layout["spread"],
                        engine=layout["engine"],
                        result_cache={} if result_cache else None)


def members(fleet, panel, keys=KEYS, offsets=END_OFFSETS):
    cell, t0 = fleet["cell"], fleet["params"]["t0_sec"]
    promql = cell["panels"][panel]["promql"]
    return [(promql.replace("{key}", str(k)), t0 + off - cell["range_s"],
             cell["step_s"], t0 + off) for k, off in zip(keys, offsets)]


def body_of(result) -> dict:
    return json.loads(promjson.matrix_json_str(result))


def assert_same_answer(a: dict, b: dict, what, ties=False,
                       rtol=1e-9) -> None:
    """The same rows at the same steps, values to f64 rounding. Under
    ``topk`` a tie at the k-th place may show another row at a step (the
    reference holds each side to its tie band): then the cells both show
    agree, and they are nearly all of them."""
    rows_a, rows_b = ({json.dumps(r["metric"], sort_keys=True):
                       {t: float(v) for t, v in r["values"]}
                       for r in body["data"]["result"]} for body in (a, b))
    cells = [(m, t) for m, row in rows_a.items() for t in row]
    both = [(m, t) for m, t in cells if t in rows_b.get(m, {})]
    n_b = sum(map(len, rows_b.values()))
    if ties:
        assert len(both) >= 0.9 * max(len(cells), n_b), what
    else:
        assert len(both) == len(cells) == n_b, what
    np.testing.assert_allclose([rows_a[m][t] for m, t in both],
                               [rows_b[m][t] for m, t in both],
                               rtol=rtol, err_msg=str(what))


def counter(family: str, **tags) -> float:
    """A counter family's value now, summed over the series whose tags
    include ``tags``: the benchmark's own arithmetic (``perf/measure.py``)
    on the program's own Prometheus text, as the readers get it."""
    measure = perf_module("measure")
    now = measure.parse_prometheus(render_prometheus())
    return measure.delta(({}, now), family, **tags)


def restarted_app(fleet) -> int:
    """An app one of whose counters falls back to zero in the store."""
    m = fleet["metrics"]["cpu_seconds_total"]
    fell = np.nonzero((np.diff(m["vals"], axis=1) < 0).any(axis=1))[0]
    assert len(fell), "the generator restarted no counter at this size"
    return int(fell[0]) % APPS


# ---------------------------------------------------------------------------
# (a) six unaligned dashboards as ONE query_range_many call

@pytest.mark.parametrize("result_cache", [True, False],
                         ids=["result-cache-on", "result-cache-off"])
@pytest.mark.parametrize("panel", [0, 1, 2],
                         ids=["sum-rate-5m", "topk-rate-1m", "max-max-1h"])
def test_six_member_call_equals_reference_and_singles(fleet, panel,
                                                      result_cache):
    cell, ref = fleet["cell"], fleet["reference"]
    queries = members(fleet, panel)
    together = service(fleet, result_cache).query_range_many(queries)
    alone_svc = service(fleet, result_cache)
    alone = [alone_svc.query_range_many([q])[0] for q in queries]
    assert len(together) == len(queries)
    rng = np.random.default_rng(panel)
    for q, key, got, one in zip(queries, KEYS, together, alone):
        body = body_of(got)
        assert body["status"] == "success" and not body.get("partial")
        checked = ref.check_panel(
            cell["panels"][panel]["check"], fleet["metrics"],
            fleet["params"]["interval_ms"], key, q[1], q[3], q[2], body, rng)
        assert checked["worst_rel_error"] <= \
            cell["panels"][panel]["check"]["rtol"]
        if result_cache:   # extent by extent both ways: the same bytes
            assert body["data"] == body_of(one)["data"], q
        else:   # one placed batch over the members' union: another base
            check = cell["panels"][panel]["check"]
            assert_same_answer(body, body_of(one), q, ties="topk" in check)
            ref.check_panel(check, fleet["metrics"],
                            fleet["params"]["interval_ms"], key, q[1], q[3],
                            q[2], body_of(one), rng)


# ---------------------------------------------------------------------------
# (b) six closed-loop connections against the event-loop front

def test_six_connections_get_the_single_client_bodies(fleet):
    layout, t0 = fleet["layout"], fleet["params"]["t0_sec"]
    traffic = perf_module("traffic")
    streams = [s[:48] for s in traffic.streams(fleet["cell"],
                                               layout["dataset"], t0, 7)]
    # this store has 8 apps where the cell draws from 100
    for s in streams:
        for r in s:
            r["path"] = r["path"].replace(f"App-{r['key']}%22",
                                          f"App-{r['key'] % APPS}%22")
    assert len(streams) == 6 and sum(map(len, streams)) == 288

    front = FastHttpServer({layout["dataset"]: service(fleet)},
                           port=0).start()
    got: dict = {}
    errors: list = []

    def client(stream):
        conn = http.client.HTTPConnection("127.0.0.1", front.port,
                                          timeout=120)
        try:
            for r in stream:
                conn.request("GET", r["path"])
                resp = conn.getresponse()
                body = resp.read()
                if resp.status != 200:
                    errors.append((resp.status, body[:200]))
                got.setdefault(r["path"], []).append(body)
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append(e)
        finally:
            conn.close()

    batches0 = counter("filodb_query_batches_total")
    members0 = counter("filodb_query_batch_members_total")
    threads = [threading.Thread(target=client, args=(s,)) for s in streams]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
    finally:
        front.stop()
    assert errors == []
    batches = counter("filodb_query_batches_total") - batches0
    carried = counter("filodb_query_batch_members_total") - members0
    # every request went through query_range_many, and some call carried
    # more than one
    assert batches >= 1 and carried > batches
    assert sum(map(len, got.values())) == 288

    # the same URLs, one client, a server with caches of its own
    single = FastHttpServer({layout["dataset"]: service(fleet)},
                            port=0).start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", single.port,
                                          timeout=120)
        for path, bodies in got.items():
            conn.request("GET", path)
            resp = conn.getresponse()
            want = json.loads(resp.read())
            assert resp.status == 200 and want["status"] == "success"
            assert not want.get("partial") and want["data"]["result"], path
            # all of a body but its queryStats, which time the request
            assert all(json.loads(b)["data"] == want["data"]
                       for b in bodies), path
        conn.close()
    finally:
        single.stop()


# ---------------------------------------------------------------------------
# (c) the three counter families

def placed_samples(svc) -> list:
    """Σ counts of every batch the engine holds placed."""
    return [int(entry[1].counts.sum())
            for entry in svc.mesh_engine._batch_cache.values()]


def test_a_single_counts_as_a_batch_of_one(fleet):
    svc = service(fleet)
    b0 = counter("filodb_query_batches_total")
    m0 = counter("filodb_query_batch_members_total")
    svc.query_range_many(members(fleet, 0)[:1])
    assert counter("filodb_query_batches_total") - b0 == 1
    assert counter("filodb_query_batch_members_total") - m0 == 1


def test_counters_of_a_six_member_call_and_of_a_batch_cache_hit(fleet):
    # without the result cache the six members reach execute_many: one
    # dispatch a plan signature, over the union of the members' ranges
    svc = service(fleet, result_cache=False)
    queries = members(fleet, 0)
    b0 = counter("filodb_query_batches_total")
    m0 = counter("filodb_query_batch_members_total")
    s0 = counter("filodb_mesh_samples_scanned_total")
    d0 = counter("filodb_mesh_dispatch_total")
    svc.query_range_many(queries)
    assert counter("filodb_query_batches_total") - b0 == 1
    assert counter("filodb_query_batch_members_total") - m0 == 6
    assert counter("filodb_mesh_dispatch_total") - d0 == len(set(KEYS))
    placed = placed_samples(svc)
    assert len(placed) == len(set(KEYS)) and min(placed) > 0
    scanned = counter("filodb_mesh_samples_scanned_total") - s0
    assert scanned == sum(placed)

    # asked again, every batch is found placed and scanned once more
    hit0 = counter("filodb_mesh_batch_cache_total", event="hit")
    svc.query_range_many(queries)
    assert counter("filodb_mesh_batch_cache_total", event="hit") - hit0 \
        == len(set(KEYS))
    assert counter("filodb_mesh_samples_scanned_total") - s0 == 2 * scanned
    assert counter("filodb_query_batch_members_total") - m0 == 12


def test_samples_scanned_under_the_result_cache_is_a_sum_over_extents(fleet):
    # the default layout: each member's missed extents are evaluated one
    # by one, a dispatch and a scan each
    svc = service(fleet)
    s0 = counter("filodb_mesh_samples_scanned_total")
    d0 = counter("filodb_mesh_dispatch_total")
    svc.query_range_many(members(fleet, 2, keys=(3, 4), offsets=(3607, 7001)))
    dispatches = counter("filodb_mesh_dispatch_total") - d0
    placed = placed_samples(svc)
    assert dispatches == len(placed) >= 2
    assert counter("filodb_mesh_samples_scanned_total") - s0 == sum(placed)


# ---------------------------------------------------------------------------
# (d) lanes and programs no other listed cell runs

@pytest.fixture
def trace_everything():
    import dataclasses

    prev = dataclasses.asdict(tracing.config())
    tracing.configure(sample_rate=1.0, slow_query_threshold_ms=1e-9)
    tracing.flight_recorder().clear()
    yield
    tracing.configure(**prev)
    tracing.flight_recorder().clear()


def test_rate_over_a_restarted_counter_takes_lane_split(fleet,
                                                        trace_everything):
    app = restarted_app(fleet)
    svc = service(fleet)
    d0 = counter("filodb_mesh_dispatch_total", form="split")
    (q,) = members(fleet, 0, keys=(app,), offsets=(7190,))
    result = svc.query_range(*q)
    assert counter("filodb_mesh_dispatch_total", form="split") > d0
    (entry,) = [e for e in tracing.slow_queries() if e["kind"] == "query"]
    pads = [s for s in entry["spans"] if s["name"] == "mesh-pad"]
    assert pads and all(s["tags"]["lane"] == "split" for s in pads)
    forms = {s["tags"]["form"] for s in entry["spans"]
             if s["name"] == "mesh-dispatch"}
    assert forms == {"split"}
    # and the reset is corrected: held to the reference over the restart
    cell = fleet["cell"]
    fleet["reference"].check_panel(
        cell["panels"][0]["check"], fleet["metrics"],
        fleet["params"]["interval_ms"], app, q[1], q[3], q[2],
        body_of(result), np.random.default_rng(0))


def test_max_over_time_runs_the_masked_scan(fleet, trace_everything):
    svc = service(fleet)
    fused0 = counter("filodb_mesh_dispatch_total", form="fused")
    split0 = counter("filodb_mesh_dispatch_total", form="split")
    (q,) = members(fleet, 2, keys=(5,), offsets=(6543,))
    svc.query_range(*q)
    assert counter("filodb_mesh_dispatch_total", form="fused") > fused0
    assert counter("filodb_mesh_dispatch_total", form="split") == split0
    (entry,) = [e for e in tracing.slow_queries() if e["kind"] == "query"]
    assert {s["tags"]["lane"] for s in entry["spans"]
            if s["name"] == "mesh-pad"} == {"raw"}


def test_a_batch_leaves_one_entry_with_its_members(fleet, trace_everything):
    svc = service(fleet)
    svc.query_range_many(members(fleet, 1))
    (entry,) = [e for e in tracing.slow_queries()
                if e["kind"] == "query-batch"]
    assert entry["members"] == 6
    cache = [s for s in entry["spans"] if s["name"] == "cache"]
    assert len(cache) == 6
    assert all("hits" in s["tags"] and "misses" in s["tags"] for s in cache)
    # one mesh-execute a missed extent, inside the batch's one trace
    assert len([s for s in entry["spans"] if s["name"] == "mesh-execute"]) \
        == sum(s["tags"]["misses"] for s in cache)


# ---------------------------------------------------------------------------
# (e) the four readers on canned inputs

def _span(name, ms, span_id, parent_id=0):
    return {"name": name, "depth": 0, "duration_ms": ms, "span_id": span_id,
            "parent_id": parent_id, "tags": {}}


ENTRIES = [
    {"kind": "query-batch", "members": 5, "duration_ms": 100.0, "spans": [
        _span("cache", 40.0, 1), _span("mesh-execute", 30.0, 2, 1),
        _span("mesh-execute", 8.0, 3, 1), _span("cache", 20.0, 4),
        _span("mesh-execute", 12.0, 5, 4)]},
    {"kind": "query", "duration_ms": 30.0, "spans": [
        _span("cache", 25.0, 1), _span("mesh-execute", 10.0, 2, 1)]},
    # a batch's member recorded once more on its own: not another request
    {"kind": "query", "batched": True, "duration_ms": 100.0, "spans": []},
]
BEFORE = {'filodb_query_batches_total': 10.0,
          'filodb_query_batch_members_total': 10.0,
          'filodb_mesh_dispatch_total{form="split"}': 4.0,
          'filodb_mesh_dispatch_total{form="fused"}': 1.0,
          'filodb_mesh_samples_scanned_total': 1000.0}
AFTER = {'filodb_query_batches_total': 14.0,
         'filodb_query_batch_members_total': 28.0,
         'filodb_mesh_dispatch_total{form="split"}': 20.0,
         'filodb_mesh_dispatch_total{form="fused"}': 9.0,
         'filodb_mesh_samples_scanned_total': 1000.0 + 819e6}
RUN = {"latencies_ms": [1.0] * 12, "peaks": {"hbm_bytes_per_s": 819e9}}
TRACE = {"busy_s": 0.4, "window_s": 5.0}
# Δmembers 18 ÷ Δbatches 4; Δdispatches 24 ÷ 12 answered; 60 ms of
# mesh-execute ÷ (5 + 1) members; 819e6 samples × 8 B at 819e9 B/s = 8 ms
# of 400 ms busy
WANT = {"batch_members": 4.5, "dispatches_per_request": 2.0,
        "mesh_ms_per_request": 10.0, "mesh_scan_roofline": 2.0}


def _without(snapshot: dict, *families) -> dict:
    return {k: v for k, v in snapshot.items()
            if not k.startswith(families)}


@pytest.mark.parametrize("name", READERS)
def test_reader_on_canned_inputs(name):
    counters = {"window": (BEFORE, AFTER), "slice": (BEFORE, AFTER)}
    got = perf_module("layer_metrics", name).read(ENTRIES, counters, TRACE,
                                                  RUN)
    assert got == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_on_the_parent(name):
    """The parent commit has none of the three families, and an untraced
    or rehearsed run has no entries, no trace and no slice: nothing to
    read is None, never an exception."""
    gone = ("filodb_query_batch", "filodb_mesh_samples_scanned",
            "filodb_mesh_dispatch") if name == "dispatches_per_request" \
        else ("filodb_query_batch", "filodb_mesh_samples_scanned")
    pair = (_without(BEFORE, *gone), _without(AFTER, *gone))
    read = perf_module("layer_metrics", name).read
    assert read([], {"window": pair, "slice": pair}, TRACE, RUN) is None
    assert read([], {"window": pair, "slice": None}, None,
                {"latencies_ms": [], "peaks": None}) is None


def test_the_listed_cell_is_the_issue_s_file(fleet):
    """The new cell differs from ``dash-review`` in its words, its ``end``
    resolution and the warm-up's last offset, and in nothing else."""
    cell, review = fleet["cell"], read_json("cells",
                                            "fleet-110k.dash-review.json")
    assert {k for k in cell if cell[k] != review[k]} == \
        {"what", "end", "warmup"}
    assert cell["end"] == {"dist": "uniform", "first_s": 3600,
                           "last_s": 7190, "resolution_s": 1}
    assert cell["warmup"] == {"key": 99, "end_offsets_s": [3600, 7190]}
    assert cell["loop"] == {"kind": "closed", "clients": 6}
