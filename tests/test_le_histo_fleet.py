"""The ``le-histo-fleet`` deployment under one SRE's latency dashboard, at a
tiny size: classic Prometheus histograms, a ``prom-counter`` series a bucket
with an ``le`` label and ``_sum``/``_count`` beside them, asked
``BASELINE.json`` config 3's own query,
``histogram_quantile(φ, sum(rate(x_bucket[5m])) by (le))``. The benchmark's
own generator, loader, cell file and plain f64 reference (``perf/``, loaded
by path) against ``FastHttpServer`` → ``QueryService`` → the mesh engine —
what the cell ``le-histo-fleet.latency-panels`` drives on the chip. Also the
same draw in the first-class column layout beside it, the controls that the
``le_quantile`` form refuses, the ``hist-quantile`` span's ``form`` tag and
``filodb_hist_quantile_rows_total``, and the cell's reader on canned
entries."""

import copy
import functools
import http.client
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

from filodb_tpu.coordinator.query_service import QueryService
from filodb_tpu.http import promjson
from filodb_tpu.http.fastserver import FastHttpServer
from filodb_tpu.utils import tracing
from filodb_tpu.utils.metrics import render_prometheus

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERF = os.path.join(ROOT, "perf")
CONFIG = "le-histo-fleet"
CELL = "le-histo-fleet.latency-panels"
# the configuration's widths cut to 3 services x 6 instances x 16 buckets:
# the cell draws its service from 15, a test names it
APPS, INSTANCES, BUCKETS = 3, 6, 16
SEED = 2**31 + 57
END_OFFSETS = (3607, 5013, 7190)
PANELS = ["p99", "p50", "buckets"]


@functools.lru_cache(maxsize=None)
def perf_module(*parts):
    """A file of ``perf/`` loaded by path, once. ``perf/`` is on the path
    only while it loads, for the readers' ``from measure import ...``."""
    path = os.path.join(PERF, *parts) + ".py"
    spec = importlib.util.spec_from_file_location(
        "le_histo_fleet_" + "_".join(parts), path)
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
    """288 bucket counters, 18 ``_sum`` and 18 ``_count`` series, and the
    same draw as 18 first-class histogram series, loaded as
    ``perf/run.py`` loads a configuration: four default-layout shards,
    every one native."""
    config = read_json(PERF, "configs", f"{CONFIG}.json")
    params = {**config["params"], "apps": APPS, "instances": INSTANCES,
              "buckets": BUCKETS}
    metrics = perf_module("generators", config["generator"]).make(params,
                                                                  SEED)
    column = perf_module("generators", "histo").make(params, SEED)
    loader = perf_module("loader")
    memstore, report = loader.load({**metrics, **column})
    n = APPS * INSTANCES
    assert report["have_native"] and report["native_shards"]
    assert report["series"] == n * (BUCKETS + 3)
    assert report["rows"] == report["series"] * params["samples"]
    return {"config": config, "params": params, "metrics": metrics,
            "column": column, "memstore": memstore,
            "layout": loader.server_layout(),
            "cell": read_json(PERF, "cells", f"{CELL}.json"),
            "reference": perf_module("reference")}


def service(fleet, engine="mesh"):
    layout = fleet["layout"]
    return QueryService(fleet["memstore"], layout["dataset"],
                        layout["num_shards"], spread=layout["spread"],
                        engine=engine, result_cache={})


class Front:
    """``FastHttpServer`` over one query service, and a connection to it:
    the front door ``perf/run.py:start_server`` puts before the cell."""

    def __init__(self, fleet, svc):
        self.svc = svc
        self.server = FastHttpServer({fleet["layout"]["dataset"]: svc},
                                     port=0).start()
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


def panel_request(fleet, panel: int, key: int, offset: int) -> dict:
    """The request the cell's traffic generator sends for one panel."""
    return perf_module("traffic").request(
        fleet["cell"], fleet["layout"]["dataset"], panel, key,
        fleet["params"]["t0_sec"] + offset)


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
    on the program's own Prometheus text."""
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
    for key in (offset % APPS, (offset + 1) % APPS):
        request = panel_request(fleet, panel, key, offset)
        body = front.get(request["path"])
        got = held_to_reference(fleet, panel, request, body)
        assert got["worst_rel_error"] <= rtol, got
        if panel < 2:
            (row,) = body["data"]["result"]
            assert row["metric"] == {} and got["buckets_read"] == BUCKETS
        else:
            assert got["groups_answered"] == BUCKETS
    if engine == "mesh":    # answered by the device programs, not declined
        assert front.svc.mesh_engine.hits > hits0
        assert front.svc.mesh_engine.misses == 0


# ---------------------------------------------------------------------------
# (b) the same draw in both layouts answers the same quantile

@pytest.mark.parametrize("offset", END_OFFSETS)
@pytest.mark.parametrize("panel", range(2), ids=PANELS[:2])
def test_the_le_and_column_layouts_give_one_quantile(fleet, fronts, panel,
                                                     offset):
    """``histogram_quantile`` over the first-class column of the same draw
    is held to the ``le`` form's band of the bucket counters, and the two
    answers show the same steps."""
    front = fronts["mesh"]
    key = offset % APPS
    request = panel_request(fleet, panel, key, offset)
    le_body = front.get(request["path"])
    column_promql = fleet["cell"]["panels"][panel]["promql"].replace(
        "http_req_latency_bucket", "http_req_latency").replace(
        " by (le)", "").replace("{key}", str(key))
    assert "_bucket" not in column_promql and "by (le)" not in column_promql
    column = front.svc.query_range(column_promql, request["end"] - 3600, 60,
                                   request["end"])
    column_body = json.loads(promjson.matrix_json_str(column))
    rtol = fleet["cell"]["panels"][panel]["check"]["rtol"]
    for body in (le_body, column_body):
        got = held_to_reference(fleet, panel, request, body)
        assert got["worst_rel_error"] <= rtol and got["cells_checked"] > 50
    (le_row,), (column_row,) = (b["data"]["result"]
                                for b in (le_body, column_body))
    assert [t for t, _ in le_row["values"]] == \
        [t for t, _ in column_row["values"]]
    counts = fleet["column"]["http_req_latency"]["vals"]["h"]["counts"]
    assert np.array_equal(
        fleet["metrics"]["http_req_latency_bucket"]["vals"],
        counts.transpose(0, 2, 1).reshape(-1, counts.shape[1]))


# ---------------------------------------------------------------------------
# (c) the mesh lowers every panel, and every shard reads native

def test_the_mesh_lowers_all_three_panels_natively(fleet):
    front = Front(fleet, service(fleet))
    try:
        at = {f: counter(f) for f in ("filodb_mesh_unsupported_total",
                                      "filodb_mesh_supported_total")}
        native0 = counter("filodb_batch_rows_total", path="native")
        fallback0 = counter("filodb_batch_rows_total", path="fallback")
        builds0 = counter("filodb_mesh_batch_cache_total", event="miss")
        held0 = counter("filodb_mesh_batch_builds_total", span="held")
        for panel in range(3):
            request = panel_request(fleet, panel, 1, 4111)
            held_to_reference(fleet, panel, request,
                              front.get(request["path"]))
        assert counter("filodb_mesh_unsupported_total") \
            == at["filodb_mesh_unsupported_total"]
        assert counter("filodb_mesh_supported_total") \
            > at["filodb_mesh_supported_total"]
        assert front.svc.mesh_engine.misses == 0
        builds = counter("filodb_mesh_batch_cache_total", event="miss") \
            - builds0
        # one build for the dashboard's one selector, held over all the
        # service's samples and found again by every other extent: every
        # bucket series of the service read through a native core, none
        # one at a time
        assert builds == 1
        assert counter("filodb_mesh_batch_builds_total", span="held") \
            - held0 == builds
        assert counter("filodb_batch_rows_total", path="fallback") \
            == fallback0
        assert counter("filodb_batch_rows_total", path="native") - native0 \
            == builds * INSTANCES * BUCKETS
    finally:
        front.close()


# ---------------------------------------------------------------------------
# (d) the controls: what the form refuses

def reference_answer(fleet, panel: int, key: int, offset: int,
                     cast=None) -> tuple:
    """(request, the reference's own answer as a Prom body): the reference
    in the program's place, optionally in another precision — the
    quantile panels' one series without ``le``, the bucket panel's rows by
    ``le``."""
    cell, ref = fleet["cell"], fleet["reference"]
    request = panel_request(fleet, panel, key, offset)
    check = cell["panels"][panel]["check"]
    steps, lo, _, groups = ref.evaluate(
        check, fleet["metrics"], fleet["params"]["interval_ms"], key,
        request["end"] - cell["range_s"], request["end"], cell["step_s"],
        np.random.default_rng(0), cast=cast)
    lo = np.asarray(lo, np.float64)
    if not check.get("post"):
        return request, ref.answer_body(check, steps, lo, groups)
    form = ref.load_form(check["post"]["fn"])
    les, rows, _ = form.buckets(ref.LENT, groups[0], lo, lo)
    q = form.answer(ref.LENT, check, rows, les)
    return request, ref.answer_body({"by": None}, steps, q[None],
                                    ([""], 1, None))


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
    is accepted; computed from counters cast to a precision below float32
    it is refused, in every panel."""
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
                  key=lambda r: float(r["metric"]["le"]))
    values = [r["values"] for r in rows]
    for r, v in zip(rows, values[1:] + values[:1]):
        r["values"] = v


def _no_inf_row(body, les):
    body["data"]["result"] = [r for r in body["data"]["result"]
                              if r["metric"]["le"] != "+Inf"]


def _le_label_kept(body, les):
    body["data"]["result"][0]["metric"]["le"] = "+Inf"


@pytest.mark.parametrize("panel,wrong,says", [
    (0, _quantile_one_bucket_up, "outside the reference's band"),
    (1, _quantile_one_bucket_up, "outside the reference's band"),
    (2, _rates_one_bucket_over, "outside the reference"),
    (2, _no_inf_row, "groups answered"),
    (0, _le_label_kept, "keeps its le label"),
], ids=["p99-one-bucket-up", "p50-one-bucket-up", "rates-one-bucket-over",
        "no-inf-row", "le-label-kept"])
def test_a_wrong_answer_is_refused(fleet, panel, wrong, says):
    ref = fleet["reference"]
    request, body = reference_answer(fleet, panel, 2, 5417)
    held_to_reference(fleet, panel, request, copy.deepcopy(body))
    les = fleet["column"]["http_req_latency"]["vals"]["h"]["les"]
    wrong(body, les)
    with pytest.raises(ref.Mismatch, match=says):
        held_to_reference(fleet, panel, request, body)


def test_a_store_without_an_inf_bucket_is_refused(fleet):
    """The form reads the groups' names as bounds: a deployment whose
    bucket series lack ``+Inf`` has no total, and no quantile is held to
    it."""
    ref = fleet["reference"]
    metric = fleet["metrics"]["http_req_latency_bucket"]
    keep = metric["labels"]["le"] != "+Inf"
    cut = {**fleet["metrics"], "http_req_latency_bucket": {
        **metric, "ts": metric["ts"][keep], "vals": metric["vals"][keep],
        "labels": {k: v[keep] for k, v in metric["labels"].items()}}}
    request, body = reference_answer(fleet, 0, 2, 5417)
    cell = fleet["cell"]
    with pytest.raises(ref.Mismatch, match=r"no \+Inf bucket"):
        ref.check_panel(cell["panels"][0]["check"], cut,
                        fleet["params"]["interval_ms"], 2,
                        request["end"] - 3600, request["end"], 60, body,
                        np.random.default_rng(0))


def test_monotonic_is_prometheus_ensure_monotonic():
    form = perf_module("reference").load_form("le_quantile")
    rows = np.array([[1.0, np.nan], [3.0, np.nan], [2.0, np.nan],
                     [np.nan, np.nan], [5.0, np.nan]])
    got = form.monotonic(rows)
    np.testing.assert_array_equal(got[:, 0], [1.0, 3.0, 3.0, 3.0, 5.0])
    assert np.isnan(got[:, 1]).all()


# ---------------------------------------------------------------------------
# (e) the span's form and the counter by form

@pytest.fixture
def trace_everything():
    import dataclasses

    prev = dataclasses.asdict(tracing.config())
    tracing.configure(sample_rate=1.0, slow_query_threshold_ms=1e-9)
    tracing.flight_recorder().clear()
    yield
    tracing.configure(**prev)
    tracing.flight_recorder().clear()


def recorded(front, path) -> list:
    """The spans of the one query a request leaves in the recorder."""
    tracing.flight_recorder().clear()
    front.get(path)
    (entry,) = [e for e in tracing.slow_queries() if e["kind"] == "query"]
    return entry["spans"]


def named(spans, name):
    return [s for s in spans if s["name"] == name]


ROWS = "filodb_hist_quantile_rows_total"


@pytest.mark.parametrize("panel", range(2), ids=PANELS[:2])
def test_hist_quantile_says_le_and_counts_the_bucket_rows(
        fleet, panel, trace_everything):
    front = Front(fleet, service(fleet))
    try:
        at = {f: counter(ROWS, form=f) for f in ("le", "column")}
        spans = recorded(front,
                         panel_request(fleet, panel, 2, 5013)["path"])
        quantiles = named(spans, "hist-quantile")
        assert len(quantiles) == len(named(spans, "mesh-execute")) >= 2
        for q in quantiles:
            (parent,) = [s for s in spans if s["span_id"] == q["parent_id"]]
            assert parent["name"] == "mesh-assemble"
            assert "buckets" not in parent["tags"]       # a scalar batch
            assert q["tags"]["form"] == "le"
            assert q["tags"]["groups"] == 1
            assert q["tags"]["buckets"] == BUCKETS
            assert q["tags"]["steps"] >= 1
        assert counter(ROWS, form="le") - at["le"] == BUCKETS * len(quantiles)
        assert counter(ROWS, form="column") == at["column"]
        # the bucket-rates panel runs no quantile
        spans = recorded(front, panel_request(fleet, 2, 2, 5013)["path"])
        assert named(spans, "mesh-assemble")
        assert not named(spans, "hist-quantile")
    finally:
        front.close()


# ---------------------------------------------------------------------------
# (f) the cell's reader on canned entries

def _span(name, ms, span_id, parent_id=0, **tags):
    return {"name": name, "depth": 0, "duration_ms": ms, "span_id": span_id,
            "parent_id": parent_id, "tags": tags}


ENTRIES = [
    # a miss: three extents, a quantile each
    {"kind": "query", "duration_ms": 300.0, "spans": [
        _span("mesh-assemble", 3.0, 1),
        _span("hist-quantile", 1.0, 2, 1, form="le", buckets=64),
        _span("hist-quantile", 1.5, 3, 1, form="le", buckets=64),
        _span("hist-quantile", 0.5, 4, 1, form="le", buckets=64)]},
    # a first-class column's quantile is not this reader's
    {"kind": "query", "duration_ms": 30.0, "spans": [
        _span("hist-quantile", 7.0, 1, form="column", buckets=64)]},
    # the bucket-rates panel: no quantile
    {"kind": "query", "duration_ms": 30.0, "spans": [
        _span("mesh-execute", 10.0, 1)]},
    # a batch of two whose members each ran one
    {"kind": "query-batch", "members": 2, "duration_ms": 200.0, "spans": [
        _span("hist-quantile", 2.0, 1, form="le", buckets=64),
        _span("hist-quantile", 3.0, 2, form="le", buckets=64)]},
    # a batch's member recorded once more on its own: not another request
    {"kind": "query", "batched": True, "duration_ms": 100.0, "spans": [
        _span("hist-quantile", 50.0, 1, form="le", buckets=64)]},
]


@pytest.mark.parametrize("entries,want", [
    # 3 + 5 ms of le quantiles over 1 + 1 + 1 + 2 requests
    (ENTRIES, 8.0 / 5),
    (ENTRIES[:1], 3.0),
    # the parent's span has no form: nothing to read
    ([{**e, "spans": [{**s, "tags": {k: v for k, v in s["tags"].items()
                                     if k != "form"}} for s in e["spans"]]}
      for e in ENTRIES], None),
    (ENTRIES[1:3], None),
    ([], None),
], ids=["mean-over-requests", "one-miss", "no-form-tag", "column-only",
        "untraced"])
def test_le_quantile_ms_on_canned_entries(entries, want):
    got = perf_module("layer_metrics", "le_quantile_ms").read(
        entries, {"window": ({}, {}), "slice": None}, None,
        {"latencies_ms": [], "peaks": None})
    assert got == (None if want is None else pytest.approx(want))


# ---------------------------------------------------------------------------
# (g) the listed cell: its files and its entries

def test_the_cell_is_histo_fleet_s_traffic_on_the_le_layout(fleet):
    cell = fleet["cell"]
    histo = read_json(PERF, "cells", "histo-fleet.latency-panels.json")
    same = ("loop", "range_s", "step_s", "end", "verify", "trace")
    assert {k: cell[k] for k in same} == {k: histo[k] for k in same}
    assert cell["config"] == CONFIG
    assert cell["key"] == {"dist": "zipf", "s": 1.1, "n": 15}
    assert cell["pool"]["dashboards"] == 600 and cell["pool"]["block"] == 6
    assert cell["pool"]["seed"] != histo["pool"]["seed"]
    assert cell["warmup"] == {"key": 14, "end_offsets_s": [3600, 7190]}
    sel = 'http_req_latency_bucket{_ws_="demo",_ns_="App-{key}"}[5m]'
    assert [p["promql"] for p in cell["panels"]] == [
        f"histogram_quantile(0.99, sum(rate({sel})) by (le))",
        f"histogram_quantile(0.5, sum(rate({sel})) by (le))",
        f"sum(rate({sel})) by (le)"]
    for panel, q in zip(cell["panels"], (0.99, 0.5, None)):
        check = panel["check"]
        assert check["metric"] == "http_req_latency_bucket"
        assert (check["fn"], check["window_s"], check["agg"], check["by"],
                check["rtol"]) == ("rate", 300, "sum", "le", 5e-5)
        assert check.get("post") == (
            None if q is None else {"fn": "le_quantile", "q": q})


def test_the_configuration_keeps_histo_fleet_s_widths(fleet):
    config = fleet["config"]
    histo = read_json(PERF, "configs", "histo-fleet.json")
    assert config["params"] == {**histo["params"], "apps": 15}
    assert config["layout"] == histo["layout"] == fleet["layout"]
    assert config["guarantees"]["answers"] == histo["guarantees"]["answers"]
    assert list(config["reduced"]) == ["apps"]
    assert config["rehearsal"] == histo["rehearsal"]
    # every service exists in a rehearsal: the cell draws from all 15
    assert {**config["params"], **config["rehearsal"]["params"]}["apps"] \
        == fleet["cell"]["key"]["n"]
    # the le text is what the reference prints for a bound
    ref = fleet["reference"]
    les = fleet["column"]["http_req_latency"]["vals"]["h"]["les"]
    assert fleet["metrics"]["http_req_latency_bucket"]["labels"]["le"][
        :BUCKETS].tolist() == [ref.fmt_le(le) for le in les]


def test_the_benchmark_lists_the_deployment_its_cell_and_reader(fleet):
    bench = read_json(ROOT, "BENCHMARK.json")
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["apps"]
    assert entry["file"] == f"perf/configs/{CONFIG}.json"
    assert entry["source"] == fleet["config"]["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == {**cell, "config": CONFIG, "traffic": "latency-panels",
                    "chips": 1}
    assert len(cell["why"]) <= 200
    assert [m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())] == [
        "mesh_group_ms", "bucket_scan_roofline", "le_quantile_ms"]
    (reader,) = [m for m in bench["per_layer"]
                 if m["name"] == "le_quantile_ms"]
    assert reader == {"name": "le_quantile_ms", "unit": "ms",
                      "better": "lower", "source": "program_span",
                      "layer": "mesh engine", "moves": "query_p50_ms",
                      "workloads": [CELL]}
