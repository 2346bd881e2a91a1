"""The plain oracle for what the mesh engine places on a batch-cache miss.

Until the batch was materialised once (``build_batch`` writes the placed
shape and dtype), the engine built an f64 batch padded to powers of two and
``pad_for_mesh`` then allocated every array again at the mesh's multiples,
copied, and ``nan_to_num``'d the values; ``jax.device_put`` converted f64 to
the device's float dtype. That code lives on here, word for word, as the
reference the new path is held to bit for bit (``parent_placed``), and as
the input preparation of ``tests/test_dist_query.py``.

Not a test module: imported by ``test_mesh_batch_once.py``,
``test_dist_query.py`` and the x64-off subprocess of ``test_f32_mode.py``.
"""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from filodb_tpu.coordinator.ingestion import ingest_routed
from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.core.record import IngestRecord, RecordContainer, SomeData
from filodb_tpu.core.store.config import StoreConfig
from filodb_tpu.parallel import dist_query, mesh_engine
from filodb_tpu.parallel.mesh_engine import MeshQueryEngine, make_query_mesh
from filodb_tpu.promql.parser import TimeStepParams, parse_query
from filodb_tpu.query.engine import batch as batch_mod
from filodb_tpu.testing.data import (
    counter_series,
    counter_stream,
    histogram_series,
    histogram_stream,
    machine_metrics_series,
)
from filodb_tpu.utils import tracing

START = 1_600_000_000
NUM_SHARDS = 4
DATASET = "timeseries"


def pad_for_mesh(ts, vals, counts, group_ids, mesh):
    """Pad P to a multiple of mesh 'shard' size and S to 'time' size;
    returns padded arrays + a validity mask (replaces counts, which don't
    shard along the time axis)."""
    ds = mesh.shape["shard"]
    dtm = mesh.shape["time"]
    P_, S_ = ts.shape
    Pp = -(-P_ // ds) * ds
    Sp = -(-S_ // dtm) * dtm
    ts_p = np.full((Pp, Sp), np.iinfo(np.int32).max, np.int32)
    vals_p = np.zeros((Pp, Sp), vals.dtype)
    valid = np.zeros((Pp, Sp), bool)
    ts_p[:P_, :S_] = ts
    vals_p[:P_, :S_] = np.nan_to_num(vals, nan=0.0)
    valid[:P_, :S_] = np.arange(S_)[None, :] < counts[:, None]
    gid_p = np.zeros(Pp, np.int32)
    gid_p[:P_] = group_ids
    return ts_p, vals_p, valid, gid_p


def parent_placed(batch, gids, mesh, lane, fn, delta_counter):
    """What the engine handed to ``shard_batch_arrays`` before: ``batch`` is
    ``build_batch``'s default (f64, NaN padding, powers of two), ``gids``
    one id a real series. Returns (ts, vals, valid, gid, raw) with the
    float arrays in the dtype ``device_put`` would make of them."""
    B = batch.vals.shape[2] if batch.is_histogram else 1
    gids_full = np.zeros(batch.ts.shape[0], np.int32)
    gids_full[: len(gids)] = gids
    raw_vals = None
    if lane == "raw" or mesh_engine._device_correction_ok(batch.vals):
        mesh_vals = batch.vals
    else:
        counter = fn in ("rate", "increase") or delta_counter
        mesh_vals = batch.delta_host(counter=counter)
        if fn in ("rate", "increase"):
            raw_vals = batch.vals
    bt_ts, bt_counts = batch.ts, batch.counts
    if B > 1:
        Pp_, S_ = bt_ts.shape
        mesh_vals = np.ascontiguousarray(
            mesh_vals.transpose(0, 2, 1)).reshape(Pp_ * B, S_)
        if raw_vals is not None:
            raw_vals = np.ascontiguousarray(
                raw_vals.transpose(0, 2, 1)).reshape(Pp_ * B, S_)
        bt_ts = np.repeat(bt_ts, B, axis=0)
        bt_counts = np.repeat(bt_counts, B)
        gids_full = (gids_full[:, None] * B + np.arange(
            B, dtype=np.int32)[None, :]).reshape(-1)
    ts_p, vals_p, valid, gid_p = pad_for_mesh(
        bt_ts, mesh_vals, bt_counts, gids_full, mesh)
    raw_p = None
    if raw_vals is not None:
        raw_p = np.zeros(vals_p.shape, vals_p.dtype)
        raw_p[: raw_vals.shape[0], : raw_vals.shape[1]] = \
            np.nan_to_num(raw_vals, nan=0.0)
    dt = batch_mod.device_float()
    with np.errstate(over="ignore"):
        return (ts_p, np.asarray(vals_p, dt), valid, gid_p,
                None if raw_p is None else np.asarray(raw_p, dt))


class _Placed(Exception):
    """Stops the engine once the placed arrays are captured, so a case
    costs a batch build and no compilation."""


def capture_placed(eng: MeshQueryEngine, query: str, ms, *,
                   start=START + 600, step=60, end=START + 2400,
                   run: bool = False):
    """Run ``query`` through ``eng`` on a cold batch cache and return what
    it handed to ``shard_batch_arrays`` beside what the parent would have:
    ``got``/``want`` (ts, vals, valid, gid, raw), ``batch`` (what
    ``build_batch`` returned to the engine), ``f64`` (``build_batch``'s
    default for the same series), ``tags`` (span name → tags), ``result``
    (the StepMatrix when ``run``)."""
    low = eng._lower(parse_query(query, TimeStepParams(start, step, end)))
    assert low is not None, f"{query} must lower"
    seen = SimpleNamespace(build=None, batch=None, got=None)
    real_build, real_shard = batch_mod.build_batch, \
        dist_query.shard_batch_arrays

    def build(parts, lo, hi, **kw):
        seen.build = (list(parts), lo, hi, kw.get("extra_by_obj"))
        seen.batch = real_build(parts, lo, hi, **kw)
        return seen.batch

    def shard(mesh, ts, vals, valid, gid, raw=None):
        seen.got = (ts, vals, valid, gid, raw)
        if not run:
            raise _Placed
        return real_shard(mesh, ts, vals, valid, gid, raw)

    batch_mod.build_batch, dist_query.shard_batch_arrays = build, shard
    result = None
    try:
        with tracing.start_trace() as trace:
            try:
                result = eng.execute_lowered_many([low], ms, DATASET)[0]
            except _Placed:
                pass
    finally:
        batch_mod.build_batch, dist_query.shard_batch_arrays = \
            real_build, real_shard
    assert seen.got is not None, "nothing was placed"
    tags = {s.name: dict(s.tags) for s in trace.spans}

    parts, lo, hi, extra = seen.build
    f64 = real_build(parts, lo, hi, extra_by_obj=extra)
    keys = [p.part_key.range_vector_key for p in parts]
    gids = np.zeros(len(keys), np.int32)
    if low.agg is not None:
        uniq: dict = {}
        for i, k in enumerate(keys):
            gids[i] = uniq.setdefault(eng._group_key(k, low), len(uniq))
    sdata = parts[0].schema.data
    is_counter = bool(sdata.columns[sdata.value_column].is_counter)
    want = parent_placed(f64, gids, eng.mesh, tags["mesh-pad"]["lane"],
                         low.fn, low.fn == "delta" and is_counter)
    return SimpleNamespace(got=seen.got, want=want, batch=seen.batch,
                           f64=f64, tags=tags, result=result)


def mismatches(got, want) -> list[str]:
    """Names of the placed arrays that differ from the oracle's in shape,
    dtype or any bit ([] = the device receives the parent's bits)."""
    bad = []
    for name, g, w in zip(("ts", "vals", "valid", "gid", "raw"), got, want):
        if (g is None) != (w is None):
            bad.append(f"{name}: present {g is not None} != {w is not None}")
        elif g is not None and (g.shape != w.shape or g.dtype != w.dtype
                                or g.tobytes() != w.tobytes()):
            bad.append(f"{name}: {g.dtype}{list(g.shape)} != "
                       f"{w.dtype}{list(w.shape)} or bits differ")
    return bad


def pad_leased(cap) -> tuple[int, int]:
    """Bytes a capture's ``mesh-pad`` has to take from the staging pool:
    (the validity mask, the placed ``[P·B, S]`` arrays — ``ts``, values,
    raw values — that are not the builder's own). A histogram batch takes
    the second inside ``hist-flatten``."""
    made = [a for a in (cap.got[0], cap.got[1], cap.got[4])
            if a is not None and a is not cap.batch.ts
            and a is not cap.batch.vals]
    return cap.got[2].nbytes, sum(a.nbytes for a in made)


def pad_reused(cap) -> tuple[int, int]:
    """``reused_bytes`` of a capture's (``mesh-pad``, ``hist-flatten``)
    spans, 0 where the span was not opened."""
    return (cap.tags["mesh-pad"]["reused_bytes"],
            cap.tags.get("hist-flatten", {}).get("reused_bytes", 0))


def placed_mismatches(cap) -> list[str]:
    """``mismatches`` of a capture, allowing the one shape that is not the
    parent's: a histogram on a mesh that divides no power of two rounds its
    series axis up BEFORE the buckets flatten into it (9 series x 10
    buckets, not 80 rows + 1), so more whole rows of padding follow the
    same data."""
    got, want = cap.got, cap.want
    if cap.batch.is_histogram and got[0].shape != want[0].shape:
        n = min(got[0].shape[0], want[0].shape[0])
        for a in (*got, *want):
            if a is not None and a.ndim == 2 and a[n:].any() \
                    and not (a[n:] == batch_mod.TS_PAD).all():
                return ["rows past the common ones are not padding"]
        got = [a if a is None else a[:n] for a in got]
        want = [a if a is None else a[:n] for a in want]
    return mismatches(got, want)


# ---- stores ---------------------------------------------------------------

def _store():
    ms = TimeSeriesMemStore()
    for s in range(NUM_SHARDS):
        ms.setup(DATASET, s, StoreConfig(max_chunk_size=100,
                                         groups_per_shard=4))
    return ms


def ragged_gauge_store(n_series=13, n_samples=200):
    """Gauges as a scraper leaves them: staleness NaNs sprinkled through,
    every fifth series stops reporting early (ragged counts), series 2 is
    stale from end to end (an empty series), 13 series divide no mesh."""
    keys = machine_metrics_series(n_series, metric="gauge_metric")

    def stream():
        rng = np.random.default_rng(5)
        container, offset = RecordContainer(), 0
        for s in range(n_samples):
            for i, k in enumerate(keys):
                if i % 5 == 1 and s >= n_samples // 2 + i:
                    continue
                v = 50.0 + rng.normal()
                if i == 2 or rng.random() < 0.1:
                    v = float("nan")
                container.add(IngestRecord(
                    k, START * 1000 + s * 10_000, (v,)))
                if len(container) >= 100:
                    yield SomeData(container, offset)
                    container, offset = RecordContainer(), offset + 1
        if len(container):
            yield SomeData(container, offset)

    ms = _store()
    ingest_routed(ms, DATASET, stream(), NUM_SHARDS, spread=1)
    return ms


def counter_store(start_value=0.0, n_series=11, n_samples=200):
    """Counters with resets; a third report 30 samples longer (ragged).
    ``start_value`` ≥ ``F32_SAFE_MAX`` sends the split lane, with x64 off,
    to the host f64 pre-pass."""
    keys = counter_series(n_series, metric="http_requests_total")
    ms = _store()
    ingest_routed(ms, DATASET, counter_stream(
        keys, n_samples, start_ms=START * 1000, seed=7, reset_every=70,
        start_value=start_value), NUM_SHARDS, spread=1)
    ingest_routed(ms, DATASET, counter_stream(
        keys[::3], 30, start_ms=(START + n_samples * 10) * 1000, seed=8,
        start_value=start_value), NUM_SHARDS, spread=1)
    return ms


def histogram_store(n_series=5, n_samples=200):
    ms = _store()
    ingest_routed(ms, DATASET, histogram_stream(
        histogram_series(n_series, metric="http_req_latency"), n_samples,
        start_ms=START * 1000, seed=11), NUM_SHARDS, spread=1)
    return ms


STORES = {
    "gauge": ragged_gauge_store,
    "counter": counter_store,
    "big-counter": lambda: counter_store(start_value=3.0e9),
    "histogram": histogram_store,
}

# (shard, time) axis sizes over the eight CPU devices conftest forces; 3×1
# divides no power of two, so the batch's shape is rounded up to it
MESHES = {"1x1": (1, 1), "4x1": (4, 1), "2x2": (2, 2), "4x2": (4, 2),
          "3x1": (3, 1)}

# name → (store, PromQL, lane the engine must pick)
CASES = {
    "raw-avg": ("gauge", "avg by (host)(avg_over_time(gauge_metric[5m]))",
                "raw"),
    "raw-max-fused": ("gauge", "max(max_over_time(gauge_metric[5m]))",
                      "raw"),
    "raw-last-sample": ("gauge", "gauge_metric", "raw"),
    "split-small": ("counter", "sum by (job)(rate(http_requests_total[5m]))",
                    "split"),
    "split-big": ("big-counter",
                  "sum(increase(http_requests_total[5m]))", "split"),
    "split-delta": ("gauge", "delta(gauge_metric[5m])", "split"),
    "split-big-rate": ("big-counter", "sum(rate(http_requests_total[5m]))",
                       "split"),
    "split-delta-sum": ("gauge", "sum(delta(gauge_metric[5m]))", "split"),
    # delta on a COUNTER schema: the device corrects the resets
    "split-delta-counter": ("counter", "delta(http_requests_total[5m])",
                            "split"),
    "histogram-split": ("histogram",
                        "sum(rate(http_req_latency[5m])) by (app)",
                        "split"),
    "histogram-split-one-group": ("histogram",
                                  "sum(rate(http_req_latency[5m]))",
                                  "split"),
    "histogram-raw": ("histogram",
                      "sum(sum_over_time(http_req_latency[5m]))", "raw"),
}


def poison(pool) -> int:
    """Overwrite every free staging buffer with garbage (0xA5 bytes: a
    negative timestamp, which a window would count; a float that is neither
    0 nor NaN; a bool that is neither). Returns the bytes poisoned."""
    n = 0
    for bufs in pool._free.values():
        for buf in bufs:
            buf.view(np.uint8).fill(0xA5)
            n += buf.nbytes
    return n


def forget(eng: MeshQueryEngine) -> None:
    """Drop every cache that could answer without reading a new build."""
    for cache in (eng._batch_cache, eng._prep_cache, eng._bounds_cache,
                  eng._eval_cache):
        cache.clear()


def run_case(case: str, mesh_name: str, stores: dict, run: bool = False,
             poisoned: bool = False):
    """One cell of the equivalence matrix; ``stores`` caches built stores
    by name across calls. ``poisoned``: the captured build writes into
    staging buffers that an earlier run of the same query gave back and
    that were then filled with garbage."""
    store, query, lane = CASES[case]
    ms = stores.get(store)
    if ms is None:
        ms = stores[store] = STORES[store]()
    ds, dtm = MESHES[mesh_name]
    eng = MeshQueryEngine(mesh=make_query_mesh(ds * dtm, dtm))
    if poisoned:
        capture_placed(eng, query, ms, run=True)
        assert poison(eng._staging) == eng._staging.held_bytes > 0
        forget(eng)
    cap = capture_placed(eng, query, ms, run=run)
    assert cap.tags["mesh-pad"]["lane"] == lane, (case, cap.tags["mesh-pad"])
    return cap
