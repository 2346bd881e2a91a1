"""The phase readers (``perf/layer_metrics``: what the host does inside
``mesh-execute``, render, and the host's blocked share) on flight-recorder
entries written by hand in the program's format. Run with

    JAX_PLATFORMS=cpu python -m pytest perf/tests -q

Nothing here touches JAX or the program.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

import pytest

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(PERF)
sys.path.insert(0, PERF)

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)

T0_NS = 1_790_000_000_000_000_000
T0_S = T0_NS / 1e9


def reader(name: str):
    path = os.path.join(PERF, "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"reader_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def query(extents: int, t0_ns: int = T0_NS, scale: float = 1.0,
          hit: bool = False) -> dict:
    """One recorded query as ``utils/tracing._finish_query`` writes it:
    ``cache`` over ``extents`` mesh extents (a batch-cache ``hit`` opens
    dispatch, fetch and assemble only), then ``cache-merge``."""
    spans, ids = [], iter(range(1, 10_000))
    at = 0.0

    def add(name, ms, parent, depth):
        nonlocal at
        s = {"name": name, "depth": depth, "start_ms": round(at, 3),
             "duration_ms": ms * scale, "span_id": next(ids),
             "parent_id": parent}
        spans.append(s)
        return s

    add("parse", 0.5, 0, 0)
    at += 0.5 * scale
    cache = add("cache", 0.0, 0, 0)
    for _ in range(extents):
        eng = add("mesh-execute", 0.0, cache["span_id"], 1)
        phases = [("mesh-dispatch", 7), ("mesh-fetch", 300),
                  ("mesh-assemble", 3)]
        if not hit:
            phases = [("mesh-lookup", 40), ("decode", 700),
                      ("mesh-group", 60), ("mesh-pad", 90),
                      ("mesh-place", 110)] + phases
        for name, ms in phases:
            s = add(name, ms, eng["span_id"], 2)
            if name == "decode":
                add("batch-read", 450, s["span_id"], 3)
                at += 450 * scale
                add("batch-stack", 250, s["span_id"], 3)
                at -= 450 * scale
            at += ms * scale
        eng["duration_ms"] = at - eng["start_ms"] + 1.0  # 1 ms uncovered
        at += 1.0
        fin = add("finish", 2, cache["span_id"], 1)
        at += fin["duration_ms"]
    merge = add("cache-merge", 4, cache["span_id"], 1)
    at += merge["duration_ms"]
    cache["duration_ms"] = at - cache["start_ms"]
    # an exec-path decode outside the engine: no phase reader may count it
    add("decode", 5000, 0, 0)
    return {"kind": "query", "when": t0_ns / 1e9 + at / 1e3 + 0.001,
            "t0_unix_ns": t0_ns, "duration_ms": at, "sampled": True,
            "stats": {}, "spans": spans}


THREE = [query(1), query(2), query(1, scale=2.0)]
# per query: 1 extent, 2 extents, 1 extent at twice the durations → the
# median is the 2x entry for single-extent sums
EXPECTED = {
    "mesh_engine_ms": 2 * (40 + 700 + 60 + 90 + 110 + 7 + 300 + 3) + 1,
    "mesh_lookup_ms": 80.0,
    "mesh_decode_ms": 1400.0,
    "mesh_stack_ms": 500.0,
    "mesh_group_ms": 120.0,
    "mesh_place_ms": 400.0,
    "mesh_dispatch_ms": 14.0,
    "mesh_fetch_ms": 600.0,
    "result_finish_ms": 2 * (3 + 2) + 4.0,
}
SPAN_READERS = sorted(EXPECTED)
NEW = SPAN_READERS + ["render_ms", "host_blocked_share"]


def counters(before: dict, after: dict) -> dict:
    return {"window": (before, after), "slice": None}


@pytest.mark.parametrize("name", SPAN_READERS)
def test_phase_present(name):
    got = reader(name)(THREE, counters({}, {}), None, {})
    assert got == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", SPAN_READERS)
def test_phase_absent_is_none(name):
    # the parent commit's entries: ``mesh-execute`` with no children, no
    # ``finish``; and a run that recorded nothing
    old = {"kind": "query", "when": T0_S + 2.1, "duration_ms": 2100.0,
           "spans": [{"name": "mesh-execute", "depth": 0,
                      "duration_ms": 2000.0, "span_id": 1, "parent_id": 0}]}
    want = 2000.0 if name == "mesh_engine_ms" else None
    assert reader(name)([old], counters({}, {}), None, {}) == want
    assert reader(name)([], counters({}, {}), None, {}) is None
    assert reader(name)([{"kind": "query", "duration_ms": 1.0,
                          "spans": []}], counters({}, {}), None, {}) is None


def test_batch_cache_hit_has_device_phases_only():
    hits = [query(1, hit=True)]
    c = counters({}, {})
    for name in ("mesh_lookup_ms", "mesh_decode_ms", "mesh_stack_ms",
                 "mesh_group_ms", "mesh_place_ms"):
        assert reader(name)(hits, c, None, {}) is None, name
    assert reader("mesh_fetch_ms")(hits, c, None, {}) == 300.0
    assert reader("mesh_engine_ms")(hits, c, None, {}) == 311.0


def test_phases_tile_the_engine_span():
    c = counters({}, {})
    whole = reader("mesh_engine_ms")(THREE, c, None, {})
    parts = sum(reader(n)(THREE, c, None, {}) for n in (
        "mesh_lookup_ms", "mesh_decode_ms", "mesh_group_ms",
        "mesh_place_ms", "mesh_dispatch_ms", "mesh_fetch_ms"))
    assert 0.95 * whole <= parts <= whole
    # the self time the accepted reader keeps reading: the uncovered rest
    assert reader("mesh_execute_ms")(THREE, c, None, {}) == \
        pytest.approx(1.0)


def test_render_ms():
    fam = "filodb_http_render_seconds"
    before = {f"{fam}_sum": 1.0, f"{fam}_count": 10.0,
              f'{fam}_bucket{{le="0.1"}}': 9.0}
    after = {f"{fam}_sum": 1.9, f"{fam}_count": 16.0,
             f'{fam}_bucket{{le="0.1"}}': 12.0}
    read = reader("render_ms")
    assert read([], counters(before, after), None, {}) == \
        pytest.approx(150.0)
    assert read([], counters(before, before), None, {}) is None
    assert read([], counters({}, {}), None, {}) is None  # the parent


def fetch_at(start_s: float, seconds: float) -> dict:
    """A query whose one ``mesh-fetch`` starts ``start_s`` after T0_S."""
    return {"kind": "query", "t0_unix_ns": T0_NS, "duration_ms": 0.0,
            "spans": [{"name": "mesh-fetch", "depth": 2,
                       "start_ms": start_s * 1e3,
                       "duration_ms": seconds * 1e3, "span_id": 1,
                       "parent_id": 0}]}


@pytest.mark.parametrize("fetches,want", [
    ([fetch_at(11.0, 1.0)], 10.0),                       # inside
    ([fetch_at(9.5, 1.0)], 5.0),                         # cut at the start
    ([fetch_at(19.75, 1.0)], 2.5),                       # cut at the end
    ([fetch_at(5.0, 30.0)], 100.0),                      # covers the slice
    ([fetch_at(2.0, 1.0), fetch_at(25.0, 1.0)], 0.0),    # both outside
    ([fetch_at(11.0, 2.0), fetch_at(12.0, 2.0)], 30.0),  # overlap: a union
])
def test_host_blocked_share_clips_to_the_slice(fetches, want):
    run = {"slice_wall": (T0_S + 10.0, T0_S + 20.0)}
    got = reader("host_blocked_share")(fetches, counters({}, {}), None, run)
    assert got == pytest.approx(want, abs=1e-6)


def test_host_blocked_share_absent_is_none():
    read = reader("host_blocked_share")
    run = {"slice_wall": (T0_S + 10.0, T0_S + 20.0)}
    c = counters({}, {})
    assert read([fetch_at(11.0, 1.0)], c, None, {"slice_wall": None}) is None
    assert read([], c, None, run) is None
    # the parent's entries: no wall clock, no start offsets
    old = fetch_at(11.0, 1.0)
    del old["t0_unix_ns"]
    assert read([old], c, None, run) is None
    old = fetch_at(11.0, 1.0)
    del old["spans"][0]["start_ms"]
    assert read([old], c, None, run) is None


def test_new_metrics_are_declared_for_the_tsbs_cell():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == ["tsbs-cpu-10k.double-groupby-1"]
        assert m["moves"] == "query_p50_ms"
        # so declared that a rehearsal prints no timing
        assert m["source"] == "program_span"
