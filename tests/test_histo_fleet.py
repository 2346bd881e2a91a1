"""The ``histo-fleet`` deployment under one SRE's latency dashboard, at a
tiny size: the benchmark's own generator, loader, cell file and plain f64
reference (``perf/``, loaded by path: the reference imports nothing of the
program) against ``FastHttpServer`` → ``QueryService`` → the mesh engine —
what the cell ``histo-fleet.latency-panels`` drives on the chip. Also the
controls that make the comparison tight, the batch-cache arithmetic the
cell rests on, the span and counters a histogram batch adds
(``hist-flatten``, ``hist-quantile``,
``filodb_mesh_bucket_samples_scanned_total``) and the cell's three readers
on canned inputs."""

import copy
import functools
import http.client
import importlib.util
import json
import os
import subprocess
import sys
from urllib.parse import quote

import numpy as np
import pytest

from filodb_tpu.coordinator.query_service import QueryService
from filodb_tpu.core.memstore.shard import TimeSeriesShard
from filodb_tpu.http.fastserver import FastHttpServer
from filodb_tpu.utils import tracing
from filodb_tpu.utils.metrics import render_prometheus

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERF = os.path.join(ROOT, "perf")
CONFIG = "histo-fleet"
CELL = "histo-fleet.latency-panels"
# the configuration's rehearsal shape cut to 4 services x 6 instances x 16
# buckets: the cell draws its service from 100, a test names it
APPS, INSTANCES, BUCKETS = 4, 6, 16
# at this seed instance 19 (App-3) restarts at its 404th scrape, ~4,030 s
SEED, RESTARTED_APP = 57, 3
# App-0 answers no request from its 561st to its 620th scrape: ten minutes
# in which every bucket rate, and so the total, is 0
QUIET_APP, QUIET = 0, slice(560, 620)
# ends at any second of the store's second hour, none on a step boundary
END_OFFSETS = (3607, 5013, 6543, 7190)
PANELS = ["p99", "p50", "buckets"]
READERS = ("hist_flatten_ms", "bucket_scan_roofline",
           "fallback_rows_per_request")


@functools.lru_cache(maxsize=None)
def perf_module(*parts):
    """A file of ``perf/`` loaded by path, once. ``perf/`` is on the path
    only while it loads, for the readers' ``from measure import ...``."""
    path = os.path.join(PERF, *parts) + ".py"
    spec = importlib.util.spec_from_file_location(
        "histo_fleet_" + "_".join(parts), path)
    mod = importlib.util.module_from_spec(spec)
    sys.path.insert(0, PERF)
    try:
        spec.loader.exec_module(mod)
    finally:
        sys.path.remove(PERF)
    return mod


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def fleet():
    """24 histogram series of 16 buckets, and beside them 40 counters and
    8 gauges of the fleet configuration for a scalar batch to compare
    with, loaded as ``perf/run.py`` loads a configuration: four
    default-layout shards, every one native."""
    config = read_json(PERF, "configs", f"{CONFIG}.json")
    params = {**config["params"], **config["rehearsal"]["params"],
              "apps": APPS, "instances": INSTANCES, "buckets": BUCKETS}
    metrics = perf_module("generators", config["generator"]).make(params,
                                                                  SEED)
    m = metrics["http_req_latency"]
    counts = m["vals"]["h"]["counts"]
    quiet = np.nonzero(m["labels"]["_ns_"] == f"App-{QUIET_APP}")[0]
    new = np.diff(counts[quiet], axis=1, prepend=0)
    assert (new >= 0).all(), "the quiet app restarts at this seed"
    new[:, QUIET] = 0
    counts[quiet] = np.cumsum(new, axis=1)
    m["vals"]["count"][quiet] = counts[quiet][:, :, -1]
    scalar_config = read_json(PERF, "configs", "fleet-110k.json")
    scalars = perf_module("generators", scalar_config["generator"]).make(
        {**scalar_config["params"], "counter_series": 40, "gauge_series": 8,
         "apps": APPS}, SEED)
    loader = perf_module("loader")
    memstore, report = loader.load({**metrics, **scalars})
    assert report["have_native"] and report["native_shards"]
    assert report["rows"] == (APPS * INSTANCES + 48) * params["samples"]
    return {"config": config, "params": params, "metrics": metrics,
            "memstore": memstore, "layout": loader.server_layout(),
            "cell": read_json(PERF, "cells", f"{CELL}.json"),
            "reference": perf_module("reference")}


def service(fleet, engine="mesh", result_cache=True):
    layout = fleet["layout"]
    return QueryService(fleet["memstore"], layout["dataset"],
                        layout["num_shards"], spread=layout["spread"],
                        engine=engine,
                        result_cache={} if result_cache else None)


class Front:
    """``FastHttpServer`` over one query service, and a connection to it:
    the front door ``perf/run.py:start_server`` puts before the cell."""

    def __init__(self, fleet, svc):
        self.svc = svc
        self.dataset = fleet["layout"]["dataset"]
        self.server = FastHttpServer({self.dataset: svc}, port=0).start()
        self.conn = http.client.HTTPConnection("127.0.0.1", self.server.port,
                                               timeout=300)

    def get(self, path: str) -> dict:
        self.conn.request("GET", path)
        resp = self.conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 200 and body["status"] == "success", body
        assert not body.get("partial")
        return body

    def close(self):
        self.conn.close()
        self.server.stop()


@pytest.fixture(scope="module")
def fronts(fleet):
    """One served stack an engine, kept for the module: programs compile
    once."""
    made = {e: Front(fleet, service(fleet, engine=e))
            for e in ("mesh", "exec")}
    yield made
    for f in made.values():
        f.close()


@pytest.fixture
def front(fleet):
    """A served mesh stack with caches of its own."""
    f = Front(fleet, service(fleet))
    yield f
    f.close()


def panel_request(fleet, panel: int, key: int, offset: int) -> dict:
    """The request the cell's traffic generator sends for one panel."""
    traffic = perf_module("traffic")
    return traffic.request(fleet["cell"], fleet["layout"]["dataset"], panel,
                           key, fleet["params"]["t0_sec"] + offset)


def held_to_reference(fleet, panel: int, request: dict, body: dict) -> dict:
    cell = fleet["cell"]
    return fleet["reference"].check_panel(
        cell["panels"][panel]["check"], fleet["metrics"],
        fleet["params"]["interval_ms"], request["key"],
        request["end"] - cell["range_s"], request["end"], cell["step_s"],
        body, np.random.default_rng(panel))


def counter(family: str, **tags) -> float:
    """A counter family's value now, summed over the series whose tags
    include ``tags``: the benchmark's own arithmetic (``perf/measure.py``)
    on the program's own Prometheus text, as the readers get it."""
    measure = perf_module("measure")
    now = measure.parse_prometheus(render_prometheus())
    return measure.delta(({}, now), family, **tags)


# ---------------------------------------------------------------------------
# (a) the served path against the f64 reference, both engines

@pytest.mark.parametrize("offset", END_OFFSETS)
@pytest.mark.parametrize("panel", range(3), ids=PANELS)
@pytest.mark.parametrize("engine", ["mesh", "exec"])
def test_a_served_panel_equals_the_reference(fleet, fronts, engine, panel,
                                             offset):
    front = fronts[engine]
    rtol = fleet["cell"]["panels"][panel]["check"]["rtol"]
    hits0 = front.svc.mesh_engine.hits if engine == "mesh" else 0
    keys = (offset % APPS, (offset + 1) % APPS)
    for key in keys:
        request = panel_request(fleet, panel, key, offset)
        got = held_to_reference(fleet, panel, request,
                                front.get(request["path"]))
        assert got["worst_rel_error"] <= rtol, got
    if engine == "mesh":    # answered by the device programs, not declined
        assert front.svc.mesh_engine.hits > hits0
        assert front.svc.mesh_engine.misses == 0


@pytest.mark.parametrize("panel", range(3), ids=PANELS)
def test_a_restarted_series_is_among_those_checked(fleet, fronts, panel):
    """One instance's sum, count and every bucket fall to zero inside the
    hour asked for: counter semantics a bucket, on both sides."""
    m = fleet["metrics"]["http_req_latency"]
    total = m["vals"]["h"]["counts"][:, :, -1]
    (fell,) = np.nonzero((np.diff(total, axis=1) < 0).any(axis=1))
    assert [int(i) % APPS for i in fell] == [RESTARTED_APP]
    at_s = (m["ts"][fell[0], int(np.argmax(np.diff(total[fell[0]]) < 0))]
            // 1000 - fleet["params"]["t0_sec"])
    offset = 5013
    assert offset - 3600 < at_s < offset
    request = panel_request(fleet, panel, RESTARTED_APP, offset)
    got = held_to_reference(fleet, panel, request,
                            fronts["mesh"].get(request["path"]))
    assert got["worst_rel_error"] <= \
        fleet["cell"]["panels"][panel]["check"]["rtol"]


@pytest.mark.parametrize("engine", ["mesh", "exec"])
def test_a_step_whose_total_is_zero_is_nan_on_both_sides(fleet, fronts,
                                                         engine):
    """Ten minutes without a request: the bucket rates are 0, shown as 0,
    and a quantile of nothing is NaN — no sample at those steps, in the
    answer and in the reference alike."""
    cell, ref = fleet["cell"], fleet["reference"]
    offset = 6543
    request = panel_request(fleet, 0, QUIET_APP, offset)
    steps, lo, _, groups = ref.evaluate(
        cell["panels"][0]["check"], fleet["metrics"],
        fleet["params"]["interval_ms"], QUIET_APP,
        request["end"] - cell["range_s"], request["end"], cell["step_s"],
        np.random.default_rng(0))
    dead = (lo[0, -1] == 0)         # the +Inf row: the total
    assert 3 <= dead.sum() < len(steps)
    for panel in (0, 1):
        request = panel_request(fleet, panel, QUIET_APP, offset)
        body = fronts[engine].get(request["path"])
        held_to_reference(fleet, panel, request, body)
        (row,) = body["data"]["result"]
        shown = {int(round(float(t) * 1000)) for t, _ in row["values"]}
        assert shown == set(steps[~dead].tolist())
    request = panel_request(fleet, 2, QUIET_APP, offset)
    body = fronts[engine].get(request["path"])
    held_to_reference(fleet, 2, request, body)
    assert all(len(r["values"]) == len(steps) for r in body["data"]["result"])


# ---------------------------------------------------------------------------
# (b) the controls: what the comparison has to refuse

def reference_answer(fleet, panel: int, key: int, offset: int,
                     cast=None) -> tuple:
    """(request, the reference's own answer as a Prom body): the
    reference in the program's place, optionally in another precision."""
    cell, ref = fleet["cell"], fleet["reference"]
    request = panel_request(fleet, panel, key, offset)
    check = cell["panels"][panel]["check"]
    steps, lo, _, groups = ref.evaluate(
        check, fleet["metrics"], fleet["params"]["interval_ms"], key,
        request["end"] - cell["range_s"], request["end"], cell["step_s"],
        np.random.default_rng(0), cast=cast)
    return request, ref.answer_body(check, steps,
                                    np.asarray(lo, np.float64), groups)


def _cast(name: str):
    import ml_dtypes

    kind = {"bfloat16": ml_dtypes.bfloat16, "float16": np.float16}[name]

    def cast(vals):
        with np.errstate(over="ignore"):
            return vals.astype(kind)
    return cast


@pytest.mark.parametrize("precision", ["bfloat16", "float16"])
@pytest.mark.parametrize("panel", range(3), ids=PANELS)
def test_a_lower_precision_is_refused(fleet, panel, precision):
    """The program computes in float32. The reference's own answer in f64
    is accepted; computed in the precisions below float32 it is refused,
    in every panel: the comparison would see a program that did so."""
    ref = fleet["reference"]
    request, good = reference_answer(fleet, panel, 1, 5417)
    got = held_to_reference(fleet, panel, request, good)
    assert got["worst_rel_error"] <= \
        fleet["cell"]["panels"][panel]["check"]["rtol"]
    request, low = reference_answer(fleet, panel, 1, 5417,
                                    cast=_cast(precision))
    with pytest.raises(ref.Mismatch):
        held_to_reference(fleet, panel, request, low)


def _quantile_one_bucket_up(body, les):
    t, v = body["data"]["result"][0]["values"][20]
    b = int(np.searchsorted(les, float(v)))
    body["data"]["result"][0]["values"][20] = [
        t, repr(float(v) + float(les[b + 1] - les[b]))]


def _rates_one_bucket_over(body, les):
    """Every row's rates under the next row's ``le``."""
    rows = sorted(body["data"]["result"],
                  key=lambda r: float(r["metric"]["le"].replace("+", "")))
    values = [r["values"] for r in rows]
    for r, v in zip(rows, values[1:] + values[:1]):
        r["values"] = v


@pytest.mark.parametrize("panel,wrong,says", [
    (0, _quantile_one_bucket_up, "outside the reference's band"),
    (1, _quantile_one_bucket_up, "outside the reference's band"),
    (2, _rates_one_bucket_over, "outside the reference"),
], ids=PANELS)
def test_an_answer_one_bucket_off_is_refused(fleet, panel, wrong, says):
    ref = fleet["reference"]
    request, body = reference_answer(fleet, panel, 2, 5417)
    held_to_reference(fleet, panel, request, copy.deepcopy(body))
    wrong(body, fleet["metrics"]["http_req_latency"]["vals"]["h"]["les"])
    with pytest.raises(ref.Mismatch, match=says):
        held_to_reference(fleet, panel, request, body)


@pytest.mark.parametrize("panel", range(3), ids=PANELS)
def test_half_the_instances_left_out_is_refused(fleet, front, panel,
                                                monkeypatch):
    """The served program with every other selected series dropped at the
    tag index: a sum over three instances of six is not the service's."""
    ref = fleet["reference"]
    lookup = TimeSeriesShard.lookup_partitions
    monkeypatch.setattr(
        TimeSeriesShard, "lookup_partitions",
        lambda self, *a, **k: list(lookup(self, *a, **k))[::2])
    request = panel_request(fleet, panel, 1, 6001)
    with pytest.raises(ref.Mismatch):
        held_to_reference(fleet, panel, request, front.get(request["path"]))


# ---------------------------------------------------------------------------
# (c) the batch-cache arithmetic the cell rests on

def test_a_dashboard_is_one_miss_and_two_hits_an_extent(fleet, front):
    """The three panels share selector, extent, ``sum`` and lane, hence
    one batch-cache key: panel 1 builds and places each missed extent,
    panels 2 and 3 find it placed. ``max`` over the same selector is
    another key, declined by the mesh and answered by the exec tree."""
    def moved(since):
        now = {e: counter("filodb_mesh_batch_cache_total", event=e)
               for e in ("hit", "miss")}
        return {e: now[e] - since[e] for e in now}, now

    _, at = moved({"hit": 0, "miss": 0})
    d0 = counter("filodb_mesh_dispatch_total")
    fb0 = counter("filodb_batch_rows_total", path="fallback")
    per_panel = []
    for panel in range(3):
        request = panel_request(fleet, panel, 2, 4111)
        held_to_reference(fleet, panel, request, front.get(request["path"]))
        step, at = moved(at)
        per_panel.append(step)
    extents = per_panel[0]["miss"]
    assert 2 <= extents <= 3 and per_panel[0]["hit"] == 0
    assert per_panel[1] == per_panel[2] == {"hit": extents, "miss": 0}
    assert counter("filodb_mesh_dispatch_total") - d0 == 3 * extents
    # what `fallback_rows_per_request` counts: a build reads a histogram
    # series one at a time, and only a miss builds
    assert counter("filodb_batch_rows_total", path="fallback") - fb0 \
        == INSTANCES * extents

    # a fourth request, `max` over the same selector and extents
    promql = 'max(rate(http_req_latency{_ws_="demo",_ns_="App-2"}[5m]))'
    end = fleet["params"]["t0_sec"] + 4111
    result = front.svc.query_range(promql, end - 3600, 60, end)
    step, at = moved(at)
    assert step["hit"] == 0 and step["miss"] >= 1
    assert result.result.num_series >= 1
    # and the `sum` entries are still there for the next dashboard panel
    request = panel_request(fleet, 1, 2, 4111)
    front.get(request["path"].replace("0.5", "0.9"))
    step, at = moved(at)
    assert step == {"hit": extents, "miss": 0}


# ---------------------------------------------------------------------------
# (d) the spans and the counter a histogram batch adds

@pytest.fixture
def trace_everything():
    import dataclasses

    prev = dataclasses.asdict(tracing.config())
    tracing.configure(sample_rate=1.0, slow_query_threshold_ms=1e-9)
    tracing.flight_recorder().clear()
    yield
    tracing.configure(**prev)
    tracing.flight_recorder().clear()


def recorded(front, request) -> list:
    """The spans of the one query a request leaves in the recorder."""
    tracing.flight_recorder().clear()
    front.get(request["path"])
    (entry,) = [e for e in tracing.slow_queries() if e["kind"] == "query"]
    return entry["spans"]


def named(spans, name):
    return [s for s in spans if s["name"] == name]


def children(spans, parent):
    """A span's children but the collector's ``gc`` spans, which land
    wherever a collection happened to start."""
    return [s for s in spans if s["parent_id"] == parent["span_id"]
            and s["name"] != "gc"]


def test_hist_flatten_is_a_child_of_mesh_pad_on_a_miss_only(
        fleet, front, trace_everything):
    spans = recorded(front, panel_request(fleet, 0, 1, 6543))
    engines = named(spans, "mesh-execute")
    pads, flats = named(spans, "mesh-pad"), named(spans, "hist-flatten")
    assert len(pads) == len(flats) == len(engines) >= 2
    for pad, flat in zip(pads, flats):
        assert children(spans, pad) == [flat]
        assert flat["depth"] == pad["depth"] + 1
        (decode,) = [s for s in named(spans, "decode")
                     if s["parent_id"] == pad["parent_id"]]
        p, s_, b = decode["tags"]["shape"]
        assert b == BUCKETS
        assert flat["tags"]["rows"] == p * b == pad["tags"]["shape"][0]
        assert flat["tags"]["buckets"] == b
        # what the flatten made: the placed values [P·B, S] (f64 under
        # x64, as here; f32 in a server), the int32 time offsets repeated
        # a bucket, counts and group ids a row
        assert flat["tags"]["bytes"] == p * b * s_ * (8 + 4) + p * b * (4 + 4)
        assert flat["duration_ms"] <= pad["duration_ms"]
        # mesh-pad's own tags are what they were
        assert set(pad["tags"]) == {"lane", "shape", "copied_bytes",
                                    "reused_bytes"}
        assert pad["tags"]["lane"] == "split"
    # the second panel finds every batch placed: none of the five phases
    spans = recorded(front, panel_request(fleet, 1, 1, 6543))
    assert len(named(spans, "mesh-execute")) == len(engines)
    assert not named(spans, "hist-flatten") and not named(spans, "mesh-pad")


def test_a_second_histogram_miss_writes_into_the_first_ones_buffers(
        fleet, front, trace_everything):
    """Another service's dashboard over the same hour builds batches of
    the first one's shapes: every ``[P, S, B]``- and ``[P·B, S]``-sized
    array of its misses — the builder's, the flattened values, ``ts`` a
    bucket row, the mask — comes back out of the staging pool and
    ``source="fresh"`` does not move."""
    def buffer_bytes():
        return {src: counter("filodb_batch_buffer_bytes_total", source=src)
                for src in ("fresh", "reused")}

    at0 = buffer_bytes()
    first = recorded(front, panel_request(fleet, 0, 1, 6543))
    at1 = buffer_bytes()
    assert at1["fresh"] > at0["fresh"]
    shapes = {tuple(s["tags"]["shape"]) for s in named(first, "decode")}
    spans = recorded(front, panel_request(fleet, 0, 2, 6543))
    at2 = buffer_bytes()
    pads, flats = named(spans, "mesh-pad"), named(spans, "hist-flatten")
    builds = named(spans, "decode")
    assert len(pads) == len(flats) == len(builds) >= 2
    assert {tuple(s["tags"]["shape"]) for s in builds} <= shapes
    assert at2["fresh"] == at1["fresh"]
    leased = 0
    for pad, flat, build in zip(pads, flats, builds):
        p, s_, b = build["tags"]["shape"]
        assert b == BUCKETS
        # both [P·B, S] arrays: the values in the placed dtype and ts
        assert flat["tags"]["reused_bytes"] == p * b * s_ * (8 + 4)
        assert flat["tags"]["bytes"] == \
            flat["tags"]["reused_bytes"] + p * b * (4 + 4)
        # mesh-pad's own: the mask alone; what is placed and not the
        # builder's is what the flatten took
        assert pad["tags"]["reused_bytes"] == p * b * s_
        assert pad["tags"]["copied_bytes"] == flat["tags"]["reused_bytes"]
        (stack,) = [s for s in named(spans, "batch-stack")
                    if s["parent_id"] == build["span_id"]]
        assert stack["tags"]["reused_bytes"] == p * s_ * (4 + 8 * b)
        leased += p * s_ * (4 + 8 * b) + p * b * s_ * (8 + 4 + 1)
    assert at2["reused"] - at1["reused"] == leased
    # and the answer out of them is the reference's
    request = panel_request(fleet, 2, 2, 6543)
    held_to_reference(fleet, 2, request, front.get(request["path"]))


def test_a_scalar_batch_opens_no_histogram_span(fleet, front,
                                                trace_everything):
    path = panel_request(fleet, 2, 1, 6543)["path"].replace(
        "http_req_latency", "cpu_seconds_total")
    spans = recorded(front, {"path": path})
    assert named(spans, "mesh-pad")
    assert not named(spans, "hist-flatten")
    assert not named(spans, "hist-quantile")
    assert all("buckets" not in s["tags"]
               for s in named(spans, "mesh-assemble"))


@pytest.mark.parametrize("result_cache", [True, False],
                         ids=["an-extent", "a-request"])
def test_hist_quantile_is_seen_once_where_it_runs(fleet, result_cache,
                                                  trace_everything):
    """Under the result cache a request's missed extents are evaluated
    one by one, a quantile each; without it the request is one dispatch
    and one quantile. The bucket-rates panel runs none."""
    front = Front(fleet, service(fleet, result_cache=result_cache))
    try:
        for panel in (0, 1):
            spans = recorded(front, panel_request(fleet, panel, 3, 5013))
            engines = named(spans, "mesh-execute")
            quantiles = named(spans, "hist-quantile")
            assert len(quantiles) == len(engines)
            assert len(engines) == 1 or result_cache
            for q in quantiles:
                (parent,) = [s for s in spans
                             if s["span_id"] == q["parent_id"]]
                assert parent["name"] == "mesh-assemble"
                assert parent["tags"]["buckets"] == BUCKETS
                assert parent["tags"]["rows"] == BUCKETS
                assert q["tags"]["groups"] == 1
                assert q["tags"]["buckets"] == BUCKETS
                assert q["tags"]["steps"] >= 1
            assert sum(q["tags"]["steps"] for q in quantiles) >= 61
        spans = recorded(front, panel_request(fleet, 2, 3, 5013))
        assert named(spans, "mesh-assemble")
        assert not named(spans, "hist-quantile")
    finally:
        front.close()


@pytest.mark.parametrize("panel", range(2), ids=PANELS[:2])
def test_hist_quantile_says_column_and_counts_the_bucket_rows(
        fleet, front, panel, trace_everything):
    """A first-class histogram's quantile is the ``column`` form: the span
    says so, and ``filodb_hist_quantile_rows_total{form="column"}`` moves
    by groups × buckets a quantile, the ``le`` form's not at all."""
    rows = "filodb_hist_quantile_rows_total"
    at = {f: counter(rows, form=f) for f in ("column", "le")}
    spans = recorded(front, panel_request(fleet, panel, 2, 5013))
    quantiles = named(spans, "hist-quantile")
    assert len(quantiles) == len(named(spans, "mesh-execute")) >= 2
    assert all(q["tags"]["form"] == "column" for q in quantiles)
    assert counter(rows, form="column") - at["column"] == sum(
        q["tags"]["groups"] * q["tags"]["buckets"] for q in quantiles) \
        == len(quantiles) * BUCKETS
    assert counter(rows, form="le") == at["le"]


def test_the_new_spans_are_inside_their_parents_and_the_phases_tile(
        fleet, front, trace_everything):
    """``hist-flatten`` and ``hist-quantile`` are grandchildren: the
    children of ``mesh-execute`` are the eight phases they were, in their
    order, and still cover it."""
    phases = ["mesh-lookup", "decode", "mesh-group", "mesh-pad",
              "mesh-place", "mesh-dispatch", "mesh-fetch", "mesh-assemble"]
    front.get(panel_request(fleet, 0, 0, 3607)["path"])     # compiles
    tiled = 0.0
    for offset in (4111, 5013, 7001):   # the best of three, as the phases'
        spans = recorded(front, panel_request(fleet, 0, 0, offset))
        shares = []
        for eng in named(spans, "mesh-execute"):
            kids = children(spans, eng)
            assert [k["name"] for k in kids] == phases
            shares.append(sum(k["duration_ms"] for k in kids)
                          / eng["duration_ms"])
            for kid in kids:
                inner = children(spans, kid)
                assert sum(c["duration_ms"] for c in inner) \
                    <= kid["duration_ms"] * 1.001 + 1e-3
        tiled = max(tiled, min(shares))
        if tiled >= 0.9:
            break
    assert tiled >= 0.9


SCAN = ("filodb_mesh_samples_scanned_total",
        "filodb_mesh_bucket_samples_scanned_total")


def test_bucket_samples_are_the_rows_a_program_evaluates(fleet, front):
    """A histogram sample is one sample to ``samples_scanned`` and 16
    scalar rows to the device: where a program evaluates the placed rows
    the new counter moves by B times the old one's step, on a scalar batch
    by the same step. Panels 2 and 3 find panel 1's cached evaluation and
    run the group reduce alone: the old counter moves as on the miss, the
    new one not at all. So does another aggregation over the same rows,
    which builds and places a batch of its own that no program reads."""
    def step(request):
        at = [counter(f) for f in SCAN]
        front.get(request["path"])
        return [counter(f) - a for f, a in zip(SCAN, at)]

    miss = step(panel_request(fleet, 0, 1, 4111))
    assert miss[0] > 0 and miss[1] == BUCKETS * miss[0]
    placed = sum(int(e[1].counts.sum())
                 for e in front.svc.mesh_engine._batch_cache.values())
    assert miss[0] == placed
    for panel in (1, 2):
        assert step(panel_request(fleet, panel, 1, 4111)) == [miss[0], 0]
    like = panel_request(fleet, 2, 1, 4111)

    def scalar(promql):
        return {"path": like["path"].replace(
            quote(fleet["cell"]["panels"][2]["promql"].replace("{key}", "1")),
            quote(promql))}

    counters = 'cpu_seconds_total{_ws_="demo",_ns_="App-1"}'
    plain = step(scalar(f"sum(rate({counters}[5m]))"))
    assert plain[0] == plain[1] > 0
    assert step(scalar(f"avg(rate({counters}[5m]))")) == [plain[0], 0]
    # the masked scan of max_over_time reads the placed rows every dispatch
    for agg in ("max", "min"):
        fused = step(scalar(f"{agg}(max_over_time({counters}[5m]))"))
        assert fused[0] == fused[1] == plain[0]


def test_query_stats_still_count_a_histogram_sample_once(fleet, front):
    request = panel_request(fleet, 0, 1, 4111)
    at = [counter(f) for f in SCAN]
    body = front.get(request["path"])
    scanned, rows = (counter(f) - a for f, a in zip(SCAN, at))
    assert body["queryStats"]["samplesScanned"] == scanned == rows / BUCKETS
    assert body["queryStats"]["seriesScanned"] % INSTANCES == 0


# ---------------------------------------------------------------------------
# (e) the three readers on canned inputs

def _span(name, ms, span_id, parent_id=0):
    return {"name": name, "depth": 0, "duration_ms": ms, "span_id": span_id,
            "parent_id": parent_id, "tags": {}}


ENTRIES = [
    # a miss: two extents, a flatten each
    {"kind": "query", "duration_ms": 300.0, "spans": [
        _span("mesh-execute", 150.0, 1), _span("mesh-pad", 100.0, 2, 1),
        _span("hist-flatten", 90.0, 3, 2), _span("mesh-execute", 140.0, 4),
        _span("mesh-pad", 70.0, 5, 4), _span("hist-flatten", 60.0, 6, 5)]},
    # two hits: no flatten
    {"kind": "query", "duration_ms": 30.0, "spans": [
        _span("mesh-execute", 10.0, 1), _span("mesh-assemble", 4.0, 2, 1)]},
    {"kind": "query", "duration_ms": 30.0, "spans": [
        _span("mesh-execute", 10.0, 1)]},
    # a batch of three that flattened once
    {"kind": "query-batch", "members": 3, "duration_ms": 200.0, "spans": [
        _span("mesh-pad", 40.0, 1), _span("hist-flatten", 30.0, 2, 1)]},
    # a batch's member recorded once more on its own: not another request
    {"kind": "query", "batched": True, "duration_ms": 100.0, "spans": []},
]
BEFORE = {'filodb_mesh_samples_scanned_total': 1000.0,
          'filodb_mesh_bucket_samples_scanned_total': 64000.0,
          'filodb_batch_rows_total{path="native"}': 500.0,
          'filodb_batch_rows_total{path="fallback"}': 100.0}
AFTER = {'filodb_mesh_samples_scanned_total': 1000.0 + 819e6 / 64,
         'filodb_mesh_bucket_samples_scanned_total': 64000.0 + 819e6,
         'filodb_batch_rows_total{path="native"}': 500.0,
         'filodb_batch_rows_total{path="fallback"}': 100.0 + 1140.0}
RUN = {"latencies_ms": [1.0] * 12, "peaks": {"hbm_bytes_per_s": 819e9}}
TRACE = {"busy_s": 0.4, "window_s": 5.0}
# 180 ms of hist-flatten ÷ (1 + 1 + 1 + 3) requests; 819e6 rows × 8 B at
# 819e9 B/s = 8 ms of 400 ms busy; 1,140 rows ÷ 12 answered
WANT = {"hist_flatten_ms": 30.0, "bucket_scan_roofline": 2.0,
        "fallback_rows_per_request": 95.0}


@pytest.mark.parametrize("name", READERS)
def test_reader_on_canned_inputs(name):
    counters = {"window": (BEFORE, AFTER), "slice": (BEFORE, AFTER)}
    got = perf_module("layer_metrics", name).read(ENTRIES, counters, TRACE,
                                                  RUN)
    assert got == pytest.approx(WANT[name])


def test_bucket_scan_roofline_is_the_mesh_scan_formula_times_the_buckets():
    counters = {"window": (BEFORE, AFTER), "slice": (BEFORE, AFTER)}
    both = [perf_module("layer_metrics", n).read(ENTRIES, counters, TRACE,
                                                 RUN)
            for n in ("bucket_scan_roofline", "mesh_scan_roofline")]
    assert both[0] == pytest.approx(64 * both[1])


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_on_the_parent(name):
    """The parent commit has neither the span nor the counter, and an
    untraced or rehearsed run has no entries, no trace and no slice:
    nothing to read is None, never an exception."""
    gone = ("filodb_mesh_bucket_samples", "filodb_batch_rows")
    pair = tuple({k: v for k, v in snap.items() if not k.startswith(gone)}
                 for snap in (BEFORE, AFTER))
    without_flatten = [
        {**e, "spans": [s for s in e["spans"] if s["name"] != "hist-flatten"]}
        for e in ENTRIES]
    read = perf_module("layer_metrics", name).read
    assert read(without_flatten, {"window": pair, "slice": pair}, TRACE,
                RUN) is None
    assert read([], {"window": pair, "slice": None}, None,
                {"latencies_ms": [], "peaks": None}) is None


# ---------------------------------------------------------------------------
# (f) the listed cell: its files, its entries, a rehearsal of it

def test_the_cell_file_is_the_issue_s_table(fleet):
    cell = fleet["cell"]
    draft = read_json(PERF, "drafts", f"{CELL}.json")
    assert {k for k in cell if cell[k] != draft[k]} == {"what", "loop",
                                                        "pool"}
    assert cell["config"] == CONFIG
    assert cell["loop"] == {"kind": "closed", "clients": 1}
    assert cell["key"] == {"dist": "zipf", "s": 1.1, "n": 100}
    assert cell["end"] == {"dist": "uniform", "first_s": 3600,
                           "last_s": 7190, "resolution_s": 1}
    assert (cell["range_s"], cell["step_s"]) == (3600, 60)
    assert cell["pool"] == {"seed": 24, "dashboards": 600, "block": 6}
    assert cell["pool"] == read_json(
        PERF, "cells", "tsbs-cpu-10k.double-groupby-1.json")["pool"]
    assert cell["warmup"] == {"key": 99, "end_offsets_s": [3600, 7190]}
    assert cell["verify"] == {"requests": 12}
    assert cell["trace"] == {"slice_s": 5.0, "min_requests": 0}
    sel = 'http_req_latency{_ws_="demo",_ns_="App-{key}"}[5m]'
    assert [p["promql"] for p in cell["panels"]] == [
        f"histogram_quantile(0.99, sum(rate({sel})))",
        f"histogram_quantile(0.5, sum(rate({sel})))",
        f"sum(rate({sel}))"]
    assert [p["check"].get("post", {}).get("q") for p in cell["panels"]] \
        == [0.99, 0.5, None]
    assert all(p["check"]["rtol"] == 5e-5 and p["check"]["window_s"] == 300
               for p in cell["panels"])


def test_the_configuration_is_the_draft_uncut(fleet):
    config = fleet["config"]
    draft = read_json(PERF, "drafts", f"{CONFIG}.json")
    assert "status" not in config
    assert config["params"] == draft["params"] == {
        "apps": 100, "instances": 100, "buckets": 64, "samples": 720,
        "interval_ms": 10000, "t0_sec": 1599999360, "restart_share": 0.02,
        "le_first_s": 0.0005, "le_last_s": 60.0, "latency_sigma": 0.9}
    assert config["layout"] == draft["layout"] == fleet["layout"]
    assert config["guarantees"] == draft["guarantees"]
    assert config["reduced"] == {}
    assert config["assumed"][:3] == draft["assumed"]
    assert len(config["assumed"]) == 4 and "20,000" in config["assumed"][3]
    # every service exists in a rehearsal: the cell draws from all 100
    assert {**config["params"], **config["rehearsal"]["params"]}["apps"] \
        == fleet["cell"]["key"]["n"]


def test_the_benchmark_lists_one_deployment_one_cell_three_readers(fleet):
    bench = read_json(ROOT, "BENCHMARK.json")
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == []
    assert entry["file"] == f"perf/configs/{CONFIG}.json"
    assert entry["source"] == fleet["config"]["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == {**cell, "config": CONFIG, "traffic": "latency-panels",
                    "chips": 1}
    assert len(cell["why"]) <= 200
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(READERS[0])
    new = bench["per_layer"][at:at + 3]
    assert [m["name"] for m in new] == list(READERS)
    # written for this cell; the le-histo-fleet cell reads the roofline too
    assert [m["workloads"] for m in new] == [
        [CELL], [CELL, "le-histo-fleet.latency-panels"], [CELL]]
    assert [(m["unit"], m["better"], m["source"], m["layer"], m["moves"])
            for m in new] == [
        ("ms", "lower", "program_span", "mesh engine", "queries_per_s"),
        ("%", "higher", "device_trace", "mesh device programs",
         "queries_per_s"),
        ("count", "lower", "program_counter", "mesh engine",
         "queries_per_s")]
    # every other metric that names its cells names none of this one's
    assert [m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())] == list(READERS)
    for m in new:
        assert callable(perf_module("layer_metrics", m["name"]).read)


def test_a_rehearsal_of_the_listed_cell_ends_correct(tmp_path):
    """``perf/run.py --workload histo-fleet.latency-panels`` past its look
    for a chip, traced, at the configuration's rehearsal size: counts and
    ``correct``, no timing."""
    p = subprocess.run(
        [sys.executable, os.path.join(PERF, "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 37), "--seconds", "2", "--trace", "1",
         "--rehearsal"],
        capture_output=True, text=True, cwd=ROOT, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
                 TMPDIR=str(tmp_path)))
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["failed"] == 0 and line["attempted"] >= 3
    assert "unlisted" not in line
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert got["mesh_hit_share"] == 100.0 and got["compiles_in_window"] == 0
    # one miss and two hits an extent, whole dashboards or nearly
    assert 60.0 <= got["batch_cache_hit_share"] <= 67.0
    # two instances a service at this size: 2 a build, 2-3 builds of 3
    assert 1.0 < got["fallback_rows_per_request"] <= 2.0
    # counts only in a rehearsal: no span or device reader speaks
    assert "hist_flatten_ms" not in got and "bucket_scan_roofline" not in got
    tags = line["detail"]["span_tags"]
    assert tags["batch-read.fallback_rows"]["max"] == 2
    assert tags["batch-read.native_rows"]["max"] == 0
