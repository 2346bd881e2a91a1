"""chip_smoke.py — the quickest proof that the served path runs on the chip.

    python chip_smoke.py             # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4   # four chips: the sharded meshes only

Drives the main path once through the entry points a user would call, at a
size a FiloDB operator would call real, and checks every answer against a
plain float64 evaluation of the same PromQL written here:

1. front door   — ``FiloServer`` from a config shaped like ``conf/server.json``;
   Influx lines over the gateway's TCP port (gateway → WAL → native shard
   append); every sample read back over HTTP with ``query`` and ``query_range``.
2. device pages — the two Pallas decode kernels against the host encoder's
   input (the only Pallas kernels the tree has; nothing serves from them yet).
3. load         — ``BASELINE.json`` config 4's 100,000 counter series and
   config 2's 10,000 gauge series, each 720 samples (2 h at a 10 s scrape,
   ``QueryInMemoryBenchmark``'s per-series shape), as serialized record
   containers into the native ingest lane of a default-layout store
   (4 shards, spread 1, 400-sample chunks).
4. queries      — through ``QueryService.query_range`` with the default
   configuration (engine ``mesh``, result cache on): the split
   prepare/bounds/eval/reduce pipeline, the masked scan of a window max
   (dispatch form ``fused``), a post-aggregation
   on the mesh output, and a binary join the mesh does not lower (exec tree).
5. proof        — where the batch lives, bytes in use, mesh hits and fallback
   counters, compile and first/warm seconds. Reported, not judged.

``--chips 4`` runs only 3, the mesh queries of 4 on a 1×1 mesh on device 0
and then on 4×1 and 2×2 meshes, and the placement and collectives of those.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any other platform is refused before any work; any phase that fails makes
the exit code non-zero and the line ``"ok": false``. The phases are plain
functions of a size, so ``tests/test_chip_smoke.py`` calls them on the CPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import socket
import struct
import sys
import tempfile
import time
import traceback

import numpy as np

DATASET = "timeseries"
INTERVAL_MS = 10_000
# the first scrape falls on a result-cache extent boundary (32 steps of 60 s
# = 1920 s), so the two hours are four extents and not five
T0_SEC = 1_599_999_360
COUNTER = "cpu_seconds_total"
GAUGE = "heap_usage"

# f32 on the device against f64 here. A rate is a difference of two
# integer-valued counters below 2^20 (exact in f32) times an extrapolation
# factor built from timestamps in f32 seconds: relative to the batch's base
# they reach ~2,200 s, where an f32 ulp is 2.4e-4 s, against windows of
# 60-300 s — some 1e-5 on a rate, and the step's own edges round the same way
# for every series of a group, so it does not average out in the sum.
# A window maximum only rounds the value itself to f32 (6e-8).
RTOL_RATE = 5e-5
RTOL_MAX = 1e-6
# ``extrapolatedRate`` compares a duration with 1.1 average intervals. With
# integer counters on a regular scrape that comparison is often an exact tie
# (50 s * 11/50 against 10 s * 1.1), which f64, f32 on a CPU and f32 on a TPU
# each round their own way; the extension it decides is worth ~10% of that
# one series' rate. The reference therefore evaluates both outcomes of any
# comparison within this relative band of its threshold (the f32 durations
# above are good to ~3e-5 of 11 s) and accepts an answer between the two.
TIE_BAND = 1e-4


@dataclasses.dataclass(frozen=True)
class Size:
    """Scale of the big store. The per-series shape (720 samples at 10 s)
    is the source's and is never cut; series counts are what a time limit
    may halve."""

    counter_series: int
    gauge_series: int
    apps: int
    samples: int = 720


REAL = Size(counter_series=100_000, gauge_series=10_000, apps=100)


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


class CompileWatch:
    """Counts what JAX compiled, from its own monitoring events. JAX offers
    no way to unregister a listener, so make one per process."""

    def __init__(self):
        import jax

        self.compile_secs: list[float] = []
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_secs.append(secs)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def mark(self) -> tuple:
        return len(self.compile_secs), self.cache_hits, self.cache_misses

    def since(self, mark: tuple) -> dict:
        n, hits, misses = mark
        return {"programs_built": len(self.compile_secs) - n,
                "build_s": round(sum(self.compile_secs[n:]), 2),
                "persistent_cache_hits": self.cache_hits - hits,
                "persistent_cache_misses": self.cache_misses - misses}


# ---------------------------------------------------------------------------
# plain reference: float64, one window at a time, every sample of the window
# looked at (no prefix sums, no binary search, no batching across steps)

def _window_columns(ts, t, window_ms):
    """Column slice that holds the window (t-w, t] of every series, given
    that sample j of each series lies in [first + j*interval, +interval)."""
    first = int(ts[:, 0].min())
    c0 = max((t - window_ms - first) // INTERVAL_MS - 1, 0)
    c1 = min((t - first) // INTERVAL_MS + 2, ts.shape[1])
    return int(c0), int(max(c1, c0))


def ref_rate(ts, vals, steps_ms, window_ms, nudge=0.0):
    """Prometheus ``rate`` as published (``extrapolatedRate``): counter
    resets added back, extrapolated to the window's edges unless the first
    or last sample is further than 1.1 average intervals from the edge, and
    never below a zero crossing. ts int64 ms [N, S], vals f64 [N, S] →
    f64 [N, K], NaN where a window holds fewer than two samples. ``nudge``
    moves the 1.1-interval threshold by that relative amount (see
    ``TIE_BAND``)."""
    n_series = ts.shape[0]
    out = np.full((n_series, len(steps_ms)), np.nan)
    rows = np.arange(n_series)
    for k, t in enumerate(steps_ms):
        c0, c1 = _window_columns(ts, int(t), window_ms)
        if c1 - c0 < 2:
            continue
        tsb, vb = ts[:, c0:c1], vals[:, c0:c1]
        m = (tsb > t - window_ms) & (tsb <= t)
        n = m.sum(1)
        i0 = m.argmax(1)
        i1 = m.shape[1] - 1 - m[:, ::-1].argmax(1)
        pair = m[:, 1:] & m[:, :-1]
        drop = pair & (vb[:, 1:] < vb[:, :-1])
        inc = vb[rows, i1] - vb[rows, i0] + np.where(drop, vb[:, :-1],
                                                     0.0).sum(1)
        t_first = tsb[rows, i0] / 1000.0
        t_last = tsb[rows, i1] / 1000.0
        sampled = t_last - t_first
        with np.errstate(divide="ignore", invalid="ignore"):
            avg = sampled / (n - 1)
            d_start = t_first - (t - window_ms) / 1000.0
            d_end = t / 1000.0 - t_last
            to_zero = np.where(inc > 0, sampled * vb[rows, i0] / inc, np.inf)
            d_start = np.minimum(d_start, to_zero)
            limit = avg * 1.1 * (1.0 + nudge)
            extend = sampled + np.where(d_start < limit, d_start, avg / 2) \
                + np.where(d_end < limit, d_end, avg / 2)
            r = inc * (extend / sampled) / (window_ms / 1000.0)
        out[:, k] = np.where(n >= 2, r, np.nan)
    return out


def ref_max_over_time(ts, vals, steps_ms, window_ms):
    out = np.full((ts.shape[0], len(steps_ms)), np.nan)
    for k, t in enumerate(steps_ms):
        c0, c1 = _window_columns(ts, int(t), window_ms)
        if c1 <= c0:
            continue
        tsb = ts[:, c0:c1]
        m = (tsb > t - window_ms) & (tsb <= t)
        mx = np.where(m, vals[:, c0:c1], -np.inf).max(1)
        out[:, k] = np.where(m.any(1), mx, np.nan)
    return out


def ref_rate_bounds(ts, vals, steps_ms, window_ms):
    """(low, high) per series: the rate with every near-tie at the
    extrapolation threshold decided one way, and the other."""
    a = ref_rate(ts, vals, steps_ms, window_ms, -TIE_BAND)
    b = ref_rate(ts, vals, steps_ms, window_ms, +TIE_BAND)
    return np.minimum(a, b), np.maximum(a, b)


def ref_group(per_series, gids, n_groups, how):
    """sum/max by group, ignoring absent (NaN) series; NaN for a group with
    no series present at that step."""
    out = np.full((n_groups, per_series.shape[1]), np.nan)
    for g in range(n_groups):
        rows = per_series[gids == g]
        if not len(rows):
            continue
        present = ~np.isnan(rows)
        fill = 0.0 if how == "sum" else -np.inf
        agg = np.where(present, rows, fill)
        agg = agg.sum(0) if how == "sum" else agg.max(0)
        out[g] = np.where(present.any(0), agg, np.nan)
    return out


# ---------------------------------------------------------------------------
# data, from a seed

@dataclasses.dataclass
class Metric:
    name: str
    schema: str
    keys: list          # PartKey per series
    app: np.ndarray     # int [N]: group of each series
    ts: np.ndarray      # int64 ms [N, S]
    vals: np.ndarray    # f64 [N, S]


def make_metric(name: str, schema: str, n_series: int, size: Size,
                rng) -> Metric:
    from filodb_tpu.core.partkey import PartKey

    app = np.arange(n_series) % size.apps
    keys = [PartKey.create(schema, {
        "_metric_": name, "_ws_": "demo", "_ns_": f"App-{a}",
        "app": f"app-{a}", "instance": f"inst-{i}"})
        for i, a in enumerate(app)]
    # every target is scraped at its own phase of the interval
    phase = rng.integers(0, INTERVAL_MS, n_series)
    ts = (T0_SEC * 1000 + phase[:, None]
          + np.arange(size.samples, dtype=np.int64)[None, :] * INTERVAL_MS)
    if schema == "prom-counter":
        # busier apps count faster
        incr = rng.integers(0, (10 + app)[:, None],
                            (n_series, size.samples)).astype(np.float64)
        vals = np.cumsum(incr, axis=1)
        # one series in fifty restarts once: the counter falls back to zero
        for i in np.nonzero(rng.random(n_series) < 0.02)[0]:
            at = int(rng.integers(1, size.samples))
            vals[i, at:] -= vals[i, at - 1]
    else:
        vals = 50.0 + np.cumsum(rng.normal(0.0, 1.0,
                                           (n_series, size.samples)), axis=1)
    return Metric(name, schema, keys, app, ts, vals)


def make_data(size: Size, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    return {
        COUNTER: make_metric(COUNTER, "prom-counter", size.counter_series,
                             size, rng),
        GAUGE: make_metric(GAUGE, "gauge", size.gauge_series, size, rng),
    }


# ---------------------------------------------------------------------------
# phase: front door

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def phase_front_door(n_series: int = 40, n_samples: int = 60) -> dict:
    """Gateway → WAL → native shard append → HTTP → Prom JSON, on a server
    built from a config shaped like ``conf/server.json``. Every line the
    gateway took is read back: each sample with ``query_range`` at the
    scrape interval, the newest of each series with ``query``."""
    from filodb_tpu import startup
    from filodb_tpu.client import FiloClient
    from filodb_tpu.config import ServerConfig
    from filodb_tpu.gateway.server import lines_parsed
    from filodb_tpu.standalone import FiloServer

    rng = np.random.default_rng(n_series * 1000 + n_samples)
    # eighths: exact in the f32 the device computes in, so that what comes
    # back can be held to equality
    sent = np.round(rng.normal(100.0, 20.0, (n_series, n_samples)) * 8) / 8
    start_sec = T0_SEC
    lines = []
    for j in range(n_samples):
        t_ns = (start_sec + j * 10) * 1_000_000_000
        for i in range(n_series):
            # two namespaces, so that with spread 1 all four shards hold data
            lines.append(f"{GAUGE},_ws_=demo,_ns_=App-{i % 2},host=h{i} "
                         f"value={sent[i, j]} {t_ns}")
    with tempfile.TemporaryDirectory(prefix="filodb-smoke-") as tmp:
        conf = os.path.join(tmp, "server.json")
        with open(os.path.join(startup.REPO_ROOT, "conf",
                               "server.json")) as f:
            cfg = json.load(f)
        cfg.update(data_dir=os.path.join(tmp, "data"), http_port=_free_port(),
                   gateway_port=_free_port())
        with open(conf, "w") as f:
            json.dump(cfg, f)
        parsed0 = lines_parsed.value
        t0 = time.perf_counter()
        server = FiloServer(ServerConfig.load(conf)).start()
        try:
            with socket.create_connection(
                    ("127.0.0.1", server.gateway.port)) as s:
                s.sendall(("\n".join(lines) + "\n").encode())
            client = FiloClient(port=server.http.port, dataset=DATASET,
                                timeout_s=300.0)
            end_sec = start_sec + (n_samples - 1) * 10
            deadline = time.monotonic() + 120
            while True:
                got = client.query(f"count({GAUGE})", end_sec)
                if lines_parsed.value - parsed0 == len(lines) and got \
                        and float(got[0]["value"][1]) == n_series:
                    break
                if time.monotonic() > deadline:
                    raise AssertionError(
                        f"gateway took {lines_parsed.value - parsed0} of "
                        f"{len(lines)} lines; count() says {got}")
                time.sleep(0.2)
            t_q = time.perf_counter()
            labels, values, _ = client.query_range_matrix(
                GAUGE, start_sec, end_sec, step=10)
            first_query_s = time.perf_counter() - t_q
            assert len(labels) == n_series, len(labels)
            order = [int(lb["host"][1:]) for lb in labels]
            np.testing.assert_array_equal(values, sent[order])
            newest = client.query(GAUGE, end_sec)
            assert len(newest) == n_series
            for r in newest:
                assert float(r["value"][1]) == \
                    sent[int(r["metric"]["host"][1:]), -1]
            native = all(sh._native_core is not None
                         for sh in server.memstore.shards_for(DATASET))
        finally:
            server.shutdown()
    return {"lines": len(lines), "series": n_series, "read_back": "all",
            "native_shards": native,
            "first_range_query_s": round(first_query_s, 2),
            "seconds": round(time.perf_counter() - t0, 1)}


# ---------------------------------------------------------------------------
# phase: device pages

def phase_device_pages(n_values: int = 4096, interpret: bool = False) -> dict:
    """Both Pallas decode kernels against what the host encoder was given."""
    import jax

    from filodb_tpu.memory.device_pages import (
        BLOCK,
        decode_f32_page_pallas,
        decode_ts_page_pallas,
        encode_f32_page,
        encode_ts_page,
    )

    rng = np.random.default_rng(n_values)
    ts = T0_SEC * 1000 + np.cumsum(rng.integers(9_000, 11_000, n_values))
    vals = np.cumsum(rng.integers(0, 20, n_values)).astype(np.float32)
    tpage, vpage = encode_ts_page(ts), encode_f32_page(vals)
    # the kernels decode offsets from each block's base; the i64 bases stay
    # on the host
    offs = jax.jit(lambda s, w, wd: decode_ts_page_pallas(
        s, w, wd, interpret=interpret))(
            tpage.slopes, tpage.widths, tpage.words)
    got_ts = (tpage.bases[:, None] + np.asarray(offs)).reshape(-1)[:n_values]
    np.testing.assert_array_equal(got_ts, ts)
    got = jax.jit(lambda f, s, w, wd: decode_f32_page_pallas(
        f, s, w, wd, interpret=interpret))(
            vpage.bases, vpage.slopes, vpage.widths, vpage.words)
    np.testing.assert_array_equal(
        np.asarray(got).reshape(-1)[:n_values], vals)
    return {"values": n_values, "blocks": -(-n_values // BLOCK),
            "ts_exact": True, "f32_exact": True}


# ---------------------------------------------------------------------------
# phase: load

def _record_templates(metric: Metric, idx) -> tuple:
    """The given series' container records with zero timestamp and value,
    as ``RecordContainer.serialize`` writes them (v2: ``u32 len | u32 hash
    | i64 ts | ... | u8 tag | f64 value``), concatenated, and the byte
    columns where each record's timestamp and value go."""
    from filodb_tpu.core.record import IngestRecord, RecordContainer

    header = len(RecordContainer().serialize())
    recs = [RecordContainer([IngestRecord(metric.keys[i], 0, (0.0,))])
            .serialize()[header:] for i in idx]
    lens = np.fromiter(map(len, recs), np.int64, len(recs))
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
    eight = np.arange(8)
    ts_cols = (starts[:, None] + 8 + eight).ravel()      # after len + hash
    val_cols = (starts[:, None] + lens[:, None] - 8 + eight).ravel()
    return np.frombuffer(b"".join(recs), np.uint8), ts_cols, val_cols


def phase_load(size: Size, seed: int, steps_per_container: int = 40):
    """The big store: a default-layout memstore (``conf/server.json``'s
    dataset block) loaded through serialized record containers, routed to
    shards with the gateway's own hash — what a shard's WAL consumer hands
    to ``memstore.ingest``. Returns (memstore, num_shards, spread, data,
    report)."""
    from filodb_tpu.config import ServerConfig
    from filodb_tpu.core.memstore.memstore import TimeSeriesMemStore
    from filodb_tpu.core.partkey import ingestion_shard
    from filodb_tpu.core.record import BytesContainer, SomeData
    from filodb_tpu.memory import native

    t0 = time.perf_counter()
    data = make_data(size, seed)
    t_gen = time.perf_counter() - t0
    cfg = ServerConfig.load(None)
    ing = cfg.datasets[DATASET]
    num_shards, spread = ing.num_shards, cfg.spreads[DATASET]
    ms = TimeSeriesMemStore()
    for s in range(num_shards):
        ms.setup(DATASET, s, dataclasses.replace(ing.store))
    rows = 0
    offset = 0
    t1 = time.perf_counter()
    for metric in data.values():
        shard_of = np.fromiter(
            (ingestion_shard(k.shard_key_hash(("_ws_", "_ns_", "_metric_")),
                             k.part_hash, num_shards, spread)
             for k in metric.keys), np.int64, len(metric.keys))
        for s in range(num_shards):
            idx = np.nonzero(shard_of == s)[0]
            if not len(idx):
                continue
            base, ts_cols, val_cols = _record_templates(metric, idx)
            for c0 in range(0, size.samples, steps_per_container):
                c1 = min(c0 + steps_per_container, size.samples)
                blob = np.tile(base, (c1 - c0, 1))
                blob[:, ts_cols] = np.ascontiguousarray(
                    metric.ts[idx, c0:c1].T).view(np.uint8).reshape(
                        c1 - c0, -1)
                blob[:, val_cols] = np.ascontiguousarray(
                    metric.vals[idx, c0:c1].T).view(np.uint8).reshape(
                        c1 - c0, -1)
                raw = struct.pack("<BI", 2, blob.shape[0] * len(idx)) \
                    + blob.tobytes()
                rows += ms.ingest(DATASET, s,
                                  SomeData(BytesContainer(raw), offset))
                offset += 1
    n_series = size.counter_series + size.gauge_series
    want = n_series * size.samples
    assert rows == want, (rows, want)
    shards = ms.shards_for(DATASET)
    assert sum(len(sh.index) for sh in shards) == n_series
    report = {"series": n_series, "samples": rows,
              "series_per_shard": [len(sh.index) for sh in shards],
              "have_native": native.HAVE_NATIVE,
              "native_shards": all(sh._native_core is not None
                                   for sh in shards),
              "generate_s": round(t_gen, 1),
              "ingest_s": round(time.perf_counter() - t1, 1),
              "cut_from_real_size": size != REAL}
    return ms, num_shards, spread, data, report


def default_service(ms, num_shards: int, spread: int, mesh=None):
    """A ``QueryService`` configured as a default-config server configures
    its own (``FiloServer.start`` → ``cluster.query_service``)."""
    from filodb_tpu.config import ServerConfig
    from filodb_tpu.coordinator.query_service import QueryService

    cfg = ServerConfig.load(None)
    return QueryService(ms, DATASET, num_shards, spread=spread,
                        engine=cfg.engines[DATASET], mesh=mesh,
                        result_cache=cfg.result_cache)


# ---------------------------------------------------------------------------
# phase: queries

def _by_app(result) -> tuple[np.ndarray, np.ndarray]:
    """(app index [R], values [R, K]) of an aggregated-by-app result."""
    apps = np.array([int(dict(k.labels)["app"].split("-")[1])
                     for k in result.keys], np.int64)
    return apps, np.asarray(result.values, np.float64)


def _sum_rate_bounds(data, steps_ms, size, window_ms):
    m = data[COUNTER]
    lo, hi = ref_rate_bounds(m.ts, m.vals, steps_ms, window_ms)
    return (ref_group(lo, m.app, size.apps, "sum"),
            ref_group(hi, m.app, size.apps, "sum"))


def _assert_between(got, lo, hi, rtol, what="") -> float:
    """Every cell of ``got`` within ``rtol`` of the interval [lo, hi], gaps
    where the reference has gaps. Returns the worst relative distance from
    the interval (0 inside it): how close the device came."""
    assert (np.isnan(got) == np.isnan(lo)).all(), f"{what}: gaps differ"
    with np.errstate(invalid="ignore", divide="ignore"):
        off = np.maximum(np.maximum(lo - got, got - hi), 0.0) \
            / np.maximum(np.abs(lo), np.abs(hi))
    off = np.nan_to_num(off, nan=0.0)
    bad = off > rtol
    assert not bad.any(), (
        f"{what}: {int(bad.sum())} of {bad.size} cells outside the "
        f"reference, worst {off.max():.3g} relative, first at "
        f"{tuple(np.argwhere(bad)[0])}: got {got[bad][0]!r}, want "
        f"[{lo[bad][0]!r}, {hi[bad][0]!r}]")
    return float(off.max())


def _check_sum_rate(result, data, steps_ms, size, window_ms):
    lo, hi = _sum_rate_bounds(data, steps_ms, size, window_ms)
    apps, got = _by_app(result)
    assert sorted(apps) == list(range(size.apps)), len(apps)
    worst = _assert_between(got, lo[apps], hi[apps], RTOL_RATE, "sum(rate)")
    return {"groups_checked": len(apps), "worst_rel_error": worst,
            "cells_with_a_tie": int((hi > lo).sum())}


def _check_max_max(result, data, steps_ms, size, window_ms):
    m = data[GAUGE]
    want = ref_group(ref_max_over_time(m.ts, m.vals, steps_ms, window_ms),
                     m.app, size.apps, "max")
    apps, got = _by_app(result)
    assert sorted(apps) == list(range(size.apps)), len(apps)
    worst = _assert_between(got, want[apps], want[apps], RTOL_MAX, "max")
    return {"groups_checked": len(apps), "worst_rel_error": worst}


def _check_topk(result, data, steps_ms, size, window_ms, k=5):
    lo, hi = _sum_rate_bounds(data, steps_ms, size, window_ms)
    apps, got = _by_app(result)
    checked = 0
    for j in range(len(steps_ms)):
        ranked = np.sort(lo[~np.isnan(lo[:, j]), j])[::-1]
        shown = apps[~np.isnan(got[:, j])]
        assert len(shown) == min(k, len(ranked)), (j, shown)
        for a in shown:
            # inside the top k, up to a near-tie that f32 cannot order
            assert hi[a, j] >= ranked[len(shown) - 1] * (1 - RTOL_RATE), \
                (j, a, hi[a, j], ranked[:k + 1])
        checked += len(shown)
    cell = ~np.isnan(got)  # a row of the result is shown at some steps only
    worst = _assert_between(np.where(cell, got, 0.0),
                            np.where(cell, lo[apps], 0.0),
                            np.where(cell, hi[apps], 0.0), RTOL_RATE, "topk")
    return {"cells_checked": checked, "worst_rel_error": worst}


def _check_join(result, data, steps_ms, size, window_ms, num, den):
    lo, hi = _sum_rate_bounds(data, steps_ms, size, window_ms)
    assert result.num_series == 1, result.num_series
    got = np.asarray(result.values, np.float64)
    worst = _assert_between(got, (lo[num] / hi[den])[None, :],
                            (hi[num] / lo[den])[None, :], 2 * RTOL_RATE,
                            "join")
    m = data[COUNTER]
    return {"series_evaluated": int(((m.app == num) | (m.app == den)).sum()),
            "worst_rel_error": worst}


def mesh_queries() -> list:
    """(name, PromQL, checker, window_ms) for the plans the mesh lowers;
    between them, every device program a default server uses."""
    return [
        ("split_sum_rate", f"sum by (app)(rate({COUNTER}[5m]))",
         _check_sum_rate, 300_000),
        ("fused_max_max", f"max by (app)(max_over_time({GAUGE}[1h]))",
         _check_max_max, 3_600_000),
        ("post_topk", f"topk(5, sum by (app)(rate({COUNTER}[1m])))",
         _check_topk, 60_000),
    ]


def exec_tree_queries(size: Size) -> list:
    """A plan the mesh does not lower — a binary join of two aggregates —
    so that the exec tree's ``kernels.range_eval`` and ``aggregate`` also
    compile and run on the device."""
    num, den = size.apps - 1, size.apps - 2
    q = (f'sum(rate({COUNTER}{{_ns_="App-{num}"}}[5m])) / '
         f'sum(rate({COUNTER}{{_ns_="App-{den}"}}[5m]))')

    def check(result, data, steps_ms, size, window_ms):
        return _check_join(result, data, steps_ms, size, window_ms, num, den)

    return [("exec_tree_join", q, check, 300_000)]


def phase_queries(svc, data, size: Size, queries: list, watch: CompileWatch,
                  on_mesh: bool, skip_s: int = 0) -> dict:
    """Run each query cold and again, check the cold answer against the
    plain reference, and report what it cost. Returns the answers by name.

    The range is everything that was loaded at a 60 s step — a "last two
    hours" panel, whose first windows are short or empty — so that between
    its extents a query places every loaded sample of its metric on the
    device. ``skip_s`` leaves out that much of the start."""
    from filodb_tpu.parallel.mesh_engine import _M_DISPATCH, _M_FALLBACK
    from filodb_tpu.utils.resilience import config as resilience_config

    start = T0_SEC + skip_s
    end = T0_SEC + (size.samples - 1) * INTERVAL_MS // 1000 - 50
    steps_ms = np.arange(start, end + 1, 60, dtype=np.int64) * 1000
    timeout_s = resilience_config().query_timeout_s
    answers = {}
    for name, promql, check, window_ms in queries:
        eng = svc.mesh_engine
        hits0 = eng.hits
        fb0 = {r: c.value for r, c in _M_FALLBACK.items()}
        dispatch0 = {f: c.value for f, c in _M_DISPATCH.items()}
        mark = watch.mark()
        t0 = time.perf_counter()
        first = svc.query_range(promql, start, 60, end)
        first_s = time.perf_counter() - t0
        cold = watch.since(mark)
        mark = watch.mark()
        t0 = time.perf_counter()
        again = svc.query_range(promql, start, 60, end)
        again_s = time.perf_counter() - t0
        warm = watch.since(mark)
        np.testing.assert_array_equal(np.asarray(again.result.values),
                                      np.asarray(first.result.values))
        vals = np.asarray(first.result.values)
        assert vals.shape[1] == len(steps_ms), vals.shape
        assert np.isfinite(vals).any(), "nothing but gaps came back"
        t0 = time.perf_counter()
        checked = check(first.result, data, steps_ms, size, window_ms)
        ref_s = time.perf_counter() - t0
        fallbacks = {r: c.value - fb0[r] for r, c in _M_FALLBACK.items()}
        dispatch = {f: c.value - dispatch0[f]
                    for f, c in _M_DISPATCH.items()}
        mesh_hits = eng.hits - hits0
        if on_mesh:
            assert mesh_hits > 0, f"{name}: the mesh never saw it"
            assert not any(fallbacks.values()), (name, fallbacks)
        else:
            assert mesh_hits == 0 and not any(dispatch.values()), \
                f"{name} was meant for the exec tree"
        say("query", name=name, promql=promql, steps=len(steps_ms),
            result_series=first.result.num_series, **checked,
            first_s=round(first_s, 2), first_build=cold,
            first_vs_query_timeout_s=[round(first_s, 2), timeout_s],
            again_s=round(again_s, 4), again_build=warm,
            again_result_cache_hits=again.stats.cache_hits,
            samples_scanned=first.stats.samples_scanned,
            mesh_hits=mesh_hits, mesh_dispatch=dispatch,
            mesh_fallbacks=fallbacks, reference_s=round(ref_s, 1))
        answers[name] = (first.result.keys, vals)
    return answers


# ---------------------------------------------------------------------------
# phase: proof it was the device

def mesh_batches(svc) -> list:
    """The placed (ts, vals, valid, gids[, raw]) tuples the engine holds."""
    return [entry[5] for entry in svc.mesh_engine._batch_cache.values()
            if entry[5] is not None]


def phase_proof(svc, platform: str) -> dict:
    import jax

    batches = mesh_batches(svc)
    assert batches, "the mesh engine holds no placed batch"
    platforms = sorted({d.platform for placed in batches for a in placed
                        for d in a.devices()})
    assert platforms == [platform], platforms
    # the groupings of one held selector share all but their group ids
    arrays = {id(a): a for placed in batches for a in placed}
    held = sum(a.nbytes for a in arrays.values())
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return {"batch_platforms": platforms, "placed_batches": len(batches),
            "placed_batch_bytes": int(held),
            "device_bytes_in_use": [s.get("bytes_in_use") for s in stats],
            "device_peak_bytes_in_use": [s.get("peak_bytes_in_use")
                                         for s in stats],
            "device_bytes_limit": [s.get("bytes_limit") for s in stats],
            "mesh": dict(svc.mesh_engine.mesh.shape),
            "mesh_hit_rate": round(svc.mesh_engine.hit_rate, 3)}


# ---------------------------------------------------------------------------
# four chips: placement and collectives of the sharded meshes

def phase_placement(svc) -> dict:
    """Each device of the mesh holds its share of every placed batch, and
    the programs carry the collectives the design claims: an all-reduce
    over ``shard`` in the group reduce, an all-gather over ``time`` in the
    window evaluation when the time axis is split."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from filodb_tpu.parallel import dist_query as dq

    mesh = svc.mesh_engine.mesh
    n = mesh.devices.size
    batches = mesh_batches(svc)
    assert batches
    shares = []
    for placed in batches:
        for a in placed[:3]:  # ts, vals, valid: the [P, S] tensors
            per_dev = {}
            for sh in a.addressable_shards:
                per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) \
                    + sh.data.nbytes
            assert len(per_dev) == n, (len(per_dev), n)
            for b in per_dev.values():
                assert abs(b / a.nbytes - 1 / n) < 0.01, (per_dev, a.nbytes)
            shares.append(sorted(per_dev.values()))
    ts = max((placed[0] for placed in batches), key=lambda a: a.nbytes)
    p_, s_ = ts.shape
    k, groups, dt = 32, 128, mesh.shape["time"]

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    pst = P("shard", "time")
    ev = dq.make_mesh_eval_delta(mesh, "rate", counter=True).lower(
        sds((p_, s_), jnp.int32, pst), sds((p_, s_), jnp.float32, pst),
        sds((p_, s_), jnp.bool_, pst), sds((p_, dt * k), jnp.int32, pst),
        sds((p_, dt * k), jnp.int32, pst), sds((k,), jnp.int32, P()),
        sds((), jnp.int32, P()),
        cv=sds((p_, s_), jnp.float32, pst)).compile().as_text()
    red = dq.make_mesh_group_reduce(mesh, groups, "sum").lower(
        sds((p_, k), jnp.float32, P("shard", None)),
        sds((p_,), jnp.int32, P("shard"))).compile().as_text()
    if mesh.shape["shard"] > 1:
        assert "all-reduce" in red
    if dt > 1:
        assert "all-gather" in ev
    stats = [d.memory_stats() or {} for d in mesh.devices.flat]
    return {"mesh": dict(mesh.shape), "batches": len(batches),
            "bytes_per_device_of_largest": shares[0],
            "device_bytes_in_use": [s.get("bytes_in_use") for s in stats],
            "group_reduce_all_reduce": "all-reduce" in red,
            "window_eval_all_gather": "all-gather" in ev}


def _same_answers(got: dict, want: dict, what: str) -> None:
    """A wider mesh reassociates f32 sums; everything else is the same
    arithmetic on the same samples. In a top-k a tie at rank k may then
    fall either way (each mesh's choice was held to the reference), so
    there only the cells both show are compared."""
    for name, (keys, vals) in want.items():
        rows = {str(k): v for k, v in zip(*got[name])}
        exact = name != "post_topk"
        if exact:
            assert list(rows) == list(map(str, keys)), (what, name)
        for key, v in zip(map(str, keys), vals):
            if key not in rows:
                continue
            g = rows[key]
            both = ~np.isnan(g) & ~np.isnan(v)
            if exact:
                assert (np.isnan(g) == np.isnan(v)).all(), (what, name, key)
            np.testing.assert_allclose(g[both], v[both], rtol=1e-5,
                                       err_msg=f"{what} {name} {key}")


# ---------------------------------------------------------------------------

def run_one_chip(size: Size, seed: int, watch: CompileWatch,
                 platform: str) -> None:
    say("front_door", **phase_front_door())
    say("device_pages", **phase_device_pages(interpret=platform != "tpu"))
    ms, num_shards, spread, data, report = phase_load(size, seed)
    assert report["have_native"] and report["native_shards"], report
    say("load", **report)
    svc = default_service(ms, num_shards, spread)
    phase_queries(svc, data, size, mesh_queries(), watch, on_mesh=True)
    phase_queries(svc, data, size, exec_tree_queries(size), watch,
                  on_mesh=False)
    say("proof", **phase_proof(svc, platform))


def run_four_chips(size: Size, seed: int, watch: CompileWatch,
                   platform: str) -> None:
    import gc

    import jax
    from jax.sharding import Mesh

    ms, num_shards, spread, data, report = phase_load(size, seed)
    assert report["have_native"] and report["native_shards"], report
    say("load", **report)
    devs = jax.devices()
    base = None
    for shape in ((1, 1), (4, 1), (2, 2)):
        mesh = Mesh(np.array(devs[: shape[0] * shape[1]]).reshape(shape),
                    ("shard", "time"))
        svc = default_service(ms, num_shards, spread, mesh=mesh)
        say("mesh", shape=list(shape))
        # the last hour only: three meshes on four chips cost twelve times
        # what one pass on one chip does
        answers = phase_queries(svc, data, size, mesh_queries(), watch,
                                on_mesh=True, skip_s=3840)
        say("proof", **phase_proof(svc, platform))
        if base is None:
            base = answers
        else:
            _same_answers(answers, base, f"{shape[0]}x{shape[1]} vs 1x1")
            say("placement", **phase_placement(svc))
        del svc
        gc.collect()  # the next mesh needs the device memory back


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=20260926)
    args = ap.parse_args(argv)

    from filodb_tpu import startup

    device = startup.device_info()
    if device["platform"] != "tpu" or device["count"] != args.chips:
        print(json.dumps({
            "ok": False, "device": device,
            "error": f"needs {args.chips} TPU chip(s); JAX found "
                     f"{device['count']} {device['platform']} device(s)"}))
        return 1
    ok = False
    try:
        cache_dir = startup.configure_jax()

        def cache_entries():
            return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) \
                else 0

        say("start", device=device, compile_cache_dir=cache_dir,
            compile_cache_entries=cache_entries())
        watch = CompileWatch()
        t0 = time.perf_counter()
        run = run_four_chips if args.chips == 4 else run_one_chip
        run(REAL, args.seed, watch, device["platform"])
        say("done", seconds=round(time.perf_counter() - t0, 1),
            **watch.since((0, 0, 0)), compile_cache_entries=cache_entries())
        ok = True
    except Exception:  # the one boundary: report, then fail
        traceback.print_exc()
    print(json.dumps({"ok": ok, "device": device}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
