"""Adaptive multi-lane query engine: sharded mesh, single-device, and
host lanes, cost-routed.

The serving problem this solves: a query's end-to-end latency on an
accelerator is ``sync_floor + device_work``, where ``sync_floor`` is the
host↔device completion-notification latency. Where that floor is small
every query belongs on the device; where it is large (a remote device behind
a high-latency link) small scans are pure overhead on the device lane while
a host-backend evaluation of the SAME jitted kernels answers without it.
Rather than hard-code either posture, this engine runs both lanes behind
one interface and routes each call to whichever lane is measured faster for
its batch-size bucket. The floor of a co-located chip has not been measured
(ROADMAP S2); until it is, note that on a TPU this engine may answer from
the HOST: it builds a second engine over the CPU backend and, cold, serves
from it first. It is not the default engine.

Reference boundary replaced: the reference has exactly one engine posture
(JVM iterators close to the data, ``QueryInMemoryBenchmark.scala:151-239``);
the two-lane design is what a TPU-native redesign needs to dominate it at
every concurrency level, not just under saturation.

Routing mechanics (all measurement, no configuration):

- per (lane, batch-size bucket) cost estimate in seconds/query, EWMA over
  post-warmup samples (each key's first sample is compilation-skewed and
  only seeds the estimate);
- the slower lane is re-probed by SHADOW traffic on a background worker —
  a duplicate of a live batch evaluated off the serving path — so estimates
  track workload drift and ingest churn without a single client ever
  paying the slow lane's latency (a bs=1 probe of the slow lane would put
  its whole sync floor into that client's p99).

Third lane — "single": when the default mesh spans multiple devices, a
one-device mesh engine over the same backend. The sharded SPMD form pays
per-call collective/dispatch overhead that a small batch never amortizes,
while a big scan wants every device; which batch size flips between them
is a property of the deployment (device count, interconnect, core count),
so it is measured per batch-size bucket exactly like device-vs-host, not
configured.
"""

from __future__ import annotations

import logging
import queue
import threading
import time

import numpy as np

from filodb_tpu.parallel.mesh_engine import MeshQueryEngine, _M_ROUTED

log = logging.getLogger(__name__)

_BUCKETS = (1, 4, 16, 64, 256, 1024)


def _bucket(n: int) -> int:
    for b in _BUCKETS:
        if n <= b:
            return b
    return _BUCKETS[-1]


def measure_sync_floor(device, tries: int = 3) -> float:
    """Median seconds for one dispatch→completion→fetch round trip of a
    trivial program on ``device`` — the per-sync latency floor any single
    blocking query pays on that backend. Indicative only (link
    completion latency varies with traffic); routing uses live costs."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1.0)
    # committed input pins execution to ``device``
    x = jax.device_put(jnp.zeros((8,), jnp.float32), device)
    f(x).block_until_ready()  # compile outside the timing
    samples = []
    for _ in range(tries):
        t0 = time.perf_counter()
        f(x).block_until_ready()
        samples.append(time.perf_counter() - t0)
    samples.sort()
    return samples[len(samples) // 2]


class _LaneCost:
    """Warmup-aware EWMA: the first sample of a key carries compilation
    and only seeds; later samples blend."""

    __slots__ = ("est", "n")

    def __init__(self):
        self.est = None
        self.n = 0

    def record(self, per_q: float, alpha: float = 0.3) -> None:
        self.n += 1
        if self.est is None or self.n <= 2:
            # seed and first post-warmup sample replace outright
            self.est = per_q
        else:
            self.est += alpha * (per_q - self.est)


class AdaptiveQueryEngine:
    """Drop-in for ``MeshQueryEngine`` in ``QueryService`` (same
    ``supports`` / ``execute`` / ``execute_many`` surface)."""

    SHADOW_EVERY = 32  # probe the slower lane once per N serving calls

    def __init__(self, mesh=None, variant: str = "gather",
                 sidecars: bool = False):
        # sidecar delegation decides at the top (device lane's _lower):
        # declined plans route to the exec leaf before lane selection runs,
        # so the inner host/single lanes never see them
        self.device_engine = MeshQueryEngine(mesh=mesh, variant=variant,
                                             sidecars=sidecars)
        self._host_engine = None
        self._host_checked = False
        self._single_engine = None
        self._single_checked = False
        self._cost: dict[tuple, _LaneCost] = {}
        self._calls = 0
        self._dataset = ""  # bound on first execute; keys the shared model
        self.sync_floor_s: float | None = None
        self.routed = {"device": 0, "single": 0, "host": 0}
        self.shadowed = {"device": 0, "single": 0, "host": 0}
        self._shadow_q: "queue.Queue|None" = None
        self._shadow_thread = None

    # -- MeshQueryEngine interface pass-throughs --

    def ensure_mesh(self):
        return self.device_engine.ensure_mesh()

    def supports(self, plan) -> bool:
        return self.device_engine.supports(plan)

    @property
    def hits(self):
        return self.device_engine.hits

    @property
    def misses(self):
        return self.device_engine.misses

    # -- host lane construction --

    def _host(self):
        """Build the host lane lazily: a second mesh engine over the CPU
        backend, only when the default backend is NOT already the CPU (a
        CPU-only deployment has nothing to gain from a second copy)."""
        if self._host_checked:
            return self._host_engine
        self._host_checked = True
        try:
            import jax
            import numpy as np
            from jax.sharding import Mesh

            default_platform = jax.devices()[0].platform
            if default_platform == "cpu":
                return None
            cpus = jax.devices("cpu")
            n = max(1, len(cpus))
            mesh = Mesh(np.array(cpus[:n]).reshape(n, 1), ("shard", "time"))
            self._host_engine = MeshQueryEngine(mesh=mesh)
            self.sync_floor_s = measure_sync_floor(jax.devices()[0])
            log.info("adaptive engine: host lane up (%d cpu), device sync "
                     "floor %.1fms", n, self.sync_floor_s * 1e3)
        except Exception:  # pragma: no cover — no cpu backend
            log.exception("host lane unavailable")
            self._host_engine = None
        return self._host_engine

    def _single(self):
        """Build the single-device lane lazily: a mesh engine pinned to a
        1×1 mesh on the default backend, only meaningful when the sharded
        mesh actually spans more than one device."""
        if self._single_checked:
            return self._single_engine
        self._single_checked = True
        try:
            from filodb_tpu.parallel.mesh_engine import make_query_mesh

            mesh = self.device_engine.ensure_mesh()
            if int(np.prod(list(mesh.shape.values()))) > 1:
                self._single_engine = MeshQueryEngine(
                    mesh=make_query_mesh(n_devices=1, time_axis=1))
                log.info("adaptive engine: single-device lane up")
        except Exception:  # pragma: no cover — device init failure
            log.exception("single-device lane unavailable")
            self._single_engine = None
        return self._single_engine

    # -- routing --

    def _lanes(self) -> list:
        lanes = ["device"]
        if self._single() is not None:
            lanes.append("single")
        if self._host() is not None:
            lanes.append("host")
        return lanes

    def _engine_for(self, lane: str):
        return {"device": self.device_engine, "single": self._single_engine,
                "host": self._host_engine}[lane]

    def _cost_of(self, lane: str, b: int) -> "_LaneCost":
        key = (lane, b)
        c = self._cost.get(key)
        if c is None:
            c = self._cost[key] = _LaneCost()
        return c

    def _route(self, n_queries: int) -> str:
        lanes = self._lanes()
        if len(lanes) == 1:
            return "device"
        b = _bucket(n_queries)
        self._calls += 1
        ests = {la: self._cost_of(la, b).est for la in lanes}
        known = {la: e for la, e in ests.items() if e is not None}
        if not known:
            # cold start: the cheapest-dispatch lane answers (host behind
            # a slow link, else the single-device lane — neither pays the
            # sharded form's collective overhead) and shadow probes price
            # the others without any client waiting
            return "host" if "host" in lanes else "single"
        return min(known, key=known.get)

    def _record(self, lane: str, n_queries: int, secs: float) -> None:
        per_q = secs / max(n_queries, 1)
        b = _bucket(n_queries)
        self._cost_of(lane, b).record(per_q)
        # mirror into the shared cost model ("lane" decision site): same
        # EWMA semantics, but there the estimates persist through the
        # metastore and surface in coststats/calibration metrics
        from filodb_tpu.query import cost_model as cm
        cm.model_for(self._dataset).observe("lane", f"b{b}", lane, per_q)

    # -- shadow probing --

    def _ensure_shadow_worker(self):
        if self._shadow_thread is None:
            self._shadow_q = queue.Queue(maxsize=1)

            def run():
                while True:
                    lane, lows, memstore, dataset = self._shadow_q.get()
                    try:
                        eng = self._engine_for(lane)
                        t0 = time.perf_counter()
                        outs = eng.execute_lowered_many(lows, memstore,
                                                        dataset)
                        for o in outs:
                            if o is not None:
                                o.materialize()
                        self._record(lane, len(lows),
                                     time.perf_counter() - t0)
                        self.shadowed[lane] += 1
                    except Exception:  # pragma: no cover
                        log.exception("shadow probe failed (%s)", lane)

            self._shadow_thread = threading.Thread(
                target=run, daemon=True, name="adaptive-shadow")
            self._shadow_thread.start()

    def _maybe_shadow(self, served_lane: str, plans: list, memstore,
                      dataset: str) -> None:
        """Duplicate this batch onto ANOTHER lane off the serving path
        when its estimate is missing or stale-by-schedule (rotating through
        the others on schedule). Never blocks; drops the probe if one is
        already in flight."""
        others = [la for la in self._lanes() if la != served_lane]
        if not others:
            return
        b = _bucket(len(plans))
        missing = [la for la in others
                   if self._cost_of(la, b).est is None]
        if missing:
            other = missing[0]
        elif self._calls % self.SHADOW_EVERY == 0:
            other = others[(self._calls // self.SHADOW_EVERY) % len(others)]
        else:
            return
        lows = [self.device_engine._lower(p) for p in plans]
        lows = [lo for lo in lows if lo is not None]
        if not lows:
            return
        self._ensure_shadow_worker()
        try:
            self._shadow_q.put_nowait((other, lows, memstore, dataset))
        except queue.Full:
            pass

    # -- execution --

    def _shared_decision(self, lane: str, n_queries: int):
        """PR 14's local router stays authoritative while the shared model
        is cold (its pick is the decision's *static* arm); once the shared
        model has min_samples on every lane — mirrored serves, shadow
        probes, or estimates restored from the metastore — its
        predicted-cheapest lane wins. Identical update rules mean the two
        agree whenever both are warm, so behavior only changes when
        persistence knows something the fresh process doesn't."""
        lanes = self._lanes()
        if len(lanes) == 1:
            return lane, None, None
        from filodb_tpu.query import cost_model as cm
        model = cm.model_for(self._dataset)
        d = model.decide("lane", f"b{_bucket(n_queries)}", tuple(lanes),
                         static_arm=lane)
        # settle: the caller records the serve through record_actual
        # (observe=False; _record already mirrored the sample)
        return d.arm, d, model

    def execute(self, memstore, dataset: str, plan, stats=None):
        self._dataset = dataset
        lane, d, model = self._shared_decision(self._route(1), 1)
        eng = self._engine_for(lane)
        t0 = time.perf_counter()
        out = eng.execute(memstore, dataset, plan, stats)
        if out is not None:
            # the lane's true cost includes the result sync
            out.materialize()
            dt = time.perf_counter() - t0
            self._record(lane, 1, dt)
            if d is not None:
                model.record_actual(d, dt, observe=False)
            self.routed[lane] += 1
            _M_ROUTED[lane].inc()
            self._maybe_shadow(lane, [plan], memstore, dataset)
        return out

    def execute_many(self, plans: list, memstore, dataset: str,
                     stats_list: list | None = None) -> list:
        self._dataset = dataset
        lane, d, model = self._shared_decision(self._route(len(plans)),
                                               len(plans))
        eng = self._engine_for(lane)
        t0 = time.perf_counter()
        outs = eng.execute_many(plans, memstore, dataset, stats_list)
        done = [o for o in outs if o is not None]
        if done:
            for o in done:
                o.materialize()
            dt = time.perf_counter() - t0
            self._record(lane, len(done), dt)
            if d is not None:
                model.record_actual(d, dt / max(len(done), 1),
                                    observe=False)
            self.routed[lane] += 1
            _M_ROUTED[lane].inc()
            self._maybe_shadow(lane, plans, memstore, dataset)
        return outs
