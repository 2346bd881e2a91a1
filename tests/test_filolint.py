"""filolint static-analysis suite (filodb_tpu/analysis/).

Two layers:

- fixture tests: each pass against small known-bad / known-good
  sources written into a temp tree, including the PR 7
  blocking-evaluation-under-lock regression shape;
- the repo gate: ``run_all`` over THIS repo must produce no finding
  outside ``conf/filolint_baseline.json``, and no baseline entry may
  be stale or unjustified. This is the tier-1 enforcement point.
"""

import json
import os
import textwrap

import pytest

from filodb_tpu.analysis import (
    AnalysisContext,
    Baseline,
    Finding,
    run_all,
)
from filodb_tpu.analysis import (
    chokepoint,
    cli,
    decisionparity,
    hotpath,
    lifecycle,
    lockdiscipline,
    parity,
)
from filodb_tpu.analysis.model import suppressed

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO_ROOT, "conf", "filolint_baseline.json")


def write_tree(root, files):
    for rel, src in files.items():
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(textwrap.dedent(src))
    return str(root)


def codes(findings):
    return sorted(f.code for f in findings)


def run_pass(tmp_path, mod, files):
    root = write_tree(tmp_path, files)
    ctx = AnalysisContext.build(root)
    assert not ctx.errors, ctx.errors
    return mod.run(ctx)


# --------------------------------------------------------------------------
# LD101 blocking-under-lock

class TestLockDiscipline:
    def test_sleep_under_lock_flagged(self, tmp_path):
        out = run_pass(tmp_path, lockdiscipline, {"filodb_tpu/m.py": """
            import threading, time

            class C:
                def __init__(self):
                    self._lock = threading.Lock()

                def bad(self):
                    with self._lock:
                        time.sleep(1)
            """})
        assert codes(out) == ["LD101"]
        assert "time.sleep" in out[0].message
        assert out[0].symbol == "C.bad"

    def test_pr7_regression_shape_query_under_lock(self, tmp_path):
        # the PR 7 priority inversion: rule evaluation under the state
        # lock, stalling lock-free readers behind a slow query
        out = run_pass(tmp_path, lockdiscipline, {"filodb_tpu/m.py": """
            import threading

            class Manager:
                def __init__(self, svc):
                    self._lock = threading.Lock()
                    self.svc = svc

                def tick(self):
                    with self._lock:
                        return self.svc.query_range("expr", 0, 60, 600)
            """})
        assert codes(out) == ["LD101"]
        assert "query_range" in out[0].detail

    def test_transitive_self_call_chain(self, tmp_path):
        # blocking two hops away: with lock -> self.a() -> self.b() ->
        # sock.recv(); the closure expansion must surface the chain
        out = run_pass(tmp_path, lockdiscipline, {"filodb_tpu/m.py": """
            import threading

            class C:
                def __init__(self, sock):
                    self._lock = threading.Lock()
                    self.sock = sock

                def outer(self):
                    with self._lock:
                        self.a()

                def a(self):
                    return self.b()

                def b(self):
                    return self.sock.recv(4096)
            """})
        assert codes(out) == ["LD101"]
        assert "a.b" in out[0].detail and "recv" in out[0].detail

    def test_blocking_outside_lock_is_fine(self, tmp_path):
        out = run_pass(tmp_path, lockdiscipline, {"filodb_tpu/m.py": """
            import threading, time

            class C:
                def __init__(self):
                    self._lock = threading.Lock()

                def fine(self):
                    with self._lock:
                        x = 1
                    time.sleep(1)
                    return x
            """})
        assert out == []

    def test_condition_wait_exempts_own_lock(self, tmp_path):
        # cond.wait() releases the condition's lock while waiting — the
        # canonical producer/consumer shape must not be flagged
        out = run_pass(tmp_path, lockdiscipline, {"filodb_tpu/m.py": """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._cond = threading.Condition(self._lock)

                def wait_ready(self):
                    with self._cond:
                        self._cond.wait()
            """})
        assert out == []

    def test_nested_def_has_its_own_lock_scope(self, tmp_path):
        # a worker closure defined under a lock runs on its own thread:
        # the held stack must not leak into it
        out = run_pass(tmp_path, lockdiscipline, {"filodb_tpu/m.py": """
            import threading, time

            class C:
                def __init__(self):
                    self._lock = threading.Lock()

                def start(self):
                    with self._lock:
                        def worker():
                            time.sleep(1)
                        self.t = threading.Thread(target=worker)
            """})
        assert codes(out) == []

    def test_dict_get_is_not_a_queue_get(self, tmp_path):
        out = run_pass(tmp_path, lockdiscipline, {"filodb_tpu/m.py": """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.d = {}

                def fine(self, k):
                    with self._lock:
                        return self.d.get(k)
            """})
        assert out == []

    def test_queue_get_under_lock_flagged(self, tmp_path):
        out = run_pass(tmp_path, lockdiscipline, {"filodb_tpu/m.py": """
            import queue, threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._q = queue.Queue()

                def bad(self):
                    with self._lock:
                        return self._q.get()
            """})
        assert codes(out) == ["LD101"]


# --------------------------------------------------------------------------
# LD102 lock-order cycles

class TestLockOrder:
    def test_opposite_orders_make_a_cycle(self, tmp_path):
        out = run_pass(tmp_path, lockdiscipline, {"filodb_tpu/m.py": """
            import threading

            class C:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def one(self):
                    with self._a:
                        with self._b:
                            pass

                def two(self):
                    with self._b:
                        with self._a:
                            pass
            """})
        assert codes(out) == ["LD102"]
        assert "C._a" in out[0].detail and "C._b" in out[0].detail

    def test_consistent_order_is_fine(self, tmp_path):
        out = run_pass(tmp_path, lockdiscipline, {"filodb_tpu/m.py": """
            import threading

            class C:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def one(self):
                    with self._a:
                        with self._b:
                            pass

                def two(self):
                    with self._a:
                        with self._b:
                            pass
            """})
        assert out == []

    def test_cycle_through_self_call(self, tmp_path):
        # one() holds A and calls helper() which takes B; two() nests A
        # under B directly — the deferred-call edges must close the loop
        out = run_pass(tmp_path, lockdiscipline, {"filodb_tpu/m.py": """
            import threading

            class C:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def one(self):
                    with self._a:
                        self.helper()

                def helper(self):
                    with self._b:
                        pass

                def two(self):
                    with self._b:
                        with self._a:
                            pass
            """})
        assert codes(out) == ["LD102"]


# --------------------------------------------------------------------------
# LD103 mixed-guard attribute stores

class TestMixedGuard:
    def test_mixed_stores_flagged(self, tmp_path):
        out = run_pass(tmp_path, lockdiscipline, {"filodb_tpu/m.py": """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0

                def guarded(self):
                    with self._lock:
                        self.n += 1

                def unguarded(self):
                    self.n = 0
            """})
        assert codes(out) == ["LD103"]
        assert out[0].detail == "n"

    def test_init_stores_do_not_count(self, tmp_path):
        out = run_pass(tmp_path, lockdiscipline, {"filodb_tpu/m.py": """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0

                def guarded(self):
                    with self._lock:
                        self.n += 1
            """})
        assert out == []

    def test_locked_suffix_convention_counts_as_guarded(self, tmp_path):
        out = run_pass(tmp_path, lockdiscipline, {"filodb_tpu/m.py": """
            import threading

            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0

                def guarded(self):
                    with self._lock:
                        self._bump_locked()

                def _bump_locked(self):
                    self.n += 1
            """})
        assert out == []


# --------------------------------------------------------------------------
# parity pass

WIRE_FIXTURE = """
    def _build_registry():
        registry = {}
        for cls in (Frame, Ghost):
            registry[cls.__name__] = cls
        for base in (Plan,):
            pass
        return registry
    """

SCRAPE_FIXTURE = """
    NAMES = [
        "filodb_good_total",
        "filodb_lazy_total",
        "filodb_phantom_total",
    ]
    """


class TestParity:
    def run(self, tmp_path, files):
        files.setdefault("filodb_tpu/coordinator/wire.py", WIRE_FIXTURE)
        files.setdefault("tests/test_metrics_scrape.py", SCRAPE_FIXTURE)
        return run_pass(tmp_path, parity, files)

    def test_unregistered_nested_dataclass(self, tmp_path):
        out = self.run(tmp_path, {"filodb_tpu/model.py": """
            from dataclasses import dataclass

            @dataclass
            class Inner:
                x: int

            @dataclass
            class Frame:
                inner: Inner

            class Ghost:
                pass

            class Plan:
                pass
            """})
        pr201 = [f for f in out if f.code == "PR201"]
        assert [f.detail for f in pr201] == ["Inner"]

    def test_stale_registry_name(self, tmp_path):
        # Ghost is named in the registry but no class defines it
        out = self.run(tmp_path, {"filodb_tpu/model.py": """
            from dataclasses import dataclass

            @dataclass
            class Frame:
                x: int

            class Plan:
                pass
            """})
        pr202 = [f for f in out if f.code == "PR202"]
        assert [f.detail for f in pr202] == ["Ghost"]

    def test_subclass_walk_registers_children(self, tmp_path):
        # SubPlan rides through the `for base in (Plan,)` walk: fields
        # referencing it from a registered class are fine
        out = self.run(tmp_path, {"filodb_tpu/model.py": """
            from dataclasses import dataclass

            class Plan:
                pass

            @dataclass
            class SubPlan(Plan):
                x: int

            @dataclass
            class Frame:
                plan: SubPlan

            class Ghost:
                pass
            """})
        assert [f for f in out if f.code == "PR201"] == []

    def test_wire_fields_must_be_registered(self, tmp_path):
        out = self.run(tmp_path, {"filodb_tpu/model.py": """
            class Frame:
                pass

            class Ghost:
                pass

            class Plan:
                pass

            class Orphan:
                __wire_fields__ = ("x",)
            """})
        pr201 = [f for f in out if f.code == "PR201"]
        assert [f.detail for f in pr201] == ["Orphan"]

    def test_metric_parity(self, tmp_path):
        out = self.run(tmp_path, {"filodb_tpu/metrics_mod.py": """
            from filodb_tpu.utils.metrics import Counter, GaugeFn

            good = Counter("filodb_good")
            uncovered = Counter("filodb_uncovered")
            ratio = GaugeFn("filodb_ratio", lambda: None)

            def lazy():
                return Counter("filodb_lazy")
            """,
            "filodb_tpu/model.py": """
            from dataclasses import dataclass

            @dataclass
            class Frame:
                x: int

            class Ghost:
                pass

            class Plan:
                pass
            """})
        # uncovered: module-level, not asserted -> PR203
        pr203 = [f for f in out if f.code == "PR203"]
        assert [f.detail for f in pr203] == ["filodb_uncovered_total"]
        # phantom: asserted, nothing produces it -> PR204; lazy counts
        # as a producer, GaugeFn is exempt from PR203
        pr204 = [f for f in out if f.code == "PR204"]
        assert [f.detail for f in pr204] == ["filodb_phantom_total"]

    def test_pyramid_families_exempt_from_nothing(self, tmp_path):
        # filodb_pyramid_* carries the zero-payload accounting: the lazy
        # exemption PR203 grants does NOT apply (PR207 still fires)
        out = self.run(tmp_path, {"filodb_tpu/metrics_mod.py": """
            from filodb_tpu.utils.metrics import Counter

            good = Counter("filodb_good")

            def lazy():
                return Counter("filodb_pyramid_ghost")

            def lazy2():
                return Counter("filodb_lazy")

            def lazy3():
                return Counter("filodb_phantom")
            """,
            "filodb_tpu/model.py": """
            from dataclasses import dataclass

            @dataclass
            class Frame:
                x: int

            class Ghost:
                pass

            class Plan:
                pass
            """})
        pr207 = [f for f in out if f.code == "PR207"]
        assert [f.detail for f in pr207] == ["filodb_pyramid_ghost_total"]
        # and the plain lazy counter stays exempt from PR203
        assert [f for f in out if f.code == "PR203"] == []

    def test_prom_charset(self, tmp_path):
        out = self.run(tmp_path, {"filodb_tpu/metrics_mod.py": """
            from filodb_tpu.utils.metrics import Counter

            def lazy():
                return Counter("filodb bad-name")
            """,
            "filodb_tpu/model.py": """
            from dataclasses import dataclass

            @dataclass
            class Frame:
                x: int

            class Ghost:
                pass

            class Plan:
                pass
            """})
        pr205 = [f for f in out if f.code == "PR205"]
        assert [f.detail for f in pr205] == ["filodb bad-name"]


# --------------------------------------------------------------------------
# hot-path pass

class TestHotPath:
    def test_host_sync_and_clock_in_kernel(self, tmp_path):
        out = run_pass(tmp_path, hotpath, {
            "filodb_tpu/query/engine/k.py": """
            import time
            import jax
            import numpy as np

            @jax.jit
            def kernel(x, meta):
                t = time.time()
                v = x.item()
                a = np.asarray(meta.steps)
                return v + t + float(meta.window)
            """})
        assert codes(out) == ["HP301", "HP301", "HP301", "HP302"]

    def test_nested_def_inherits_kernel_scope(self, tmp_path):
        out = run_pass(tmp_path, hotpath, {
            "filodb_tpu/query/engine/k.py": """
            import jax

            @jax.jit
            def kernel(x):
                def inner(y):
                    return y.item()
                return inner(x)
            """})
        assert codes(out) == ["HP301"]
        assert out[0].symbol == "kernel.inner"

    def test_pallas_kernel_detected(self, tmp_path):
        out = run_pass(tmp_path, hotpath, {
            "filodb_tpu/query/engine/k.py": """
            from jax.experimental import pallas as pl

            def body(ref, o_ref):
                o_ref[...] = float(ref[...])

            def launch(x):
                return pl.pallas_call(body, out_shape=x)(x)
            """})
        assert codes(out) == ["HP301"]

    def test_non_kernel_and_non_engine_ignored(self, tmp_path):
        out = run_pass(tmp_path, hotpath, {
            "filodb_tpu/query/engine/k.py": """
            def plain(x):
                return x.item()
            """,
            "filodb_tpu/coordinator/c.py": """
            import jax

            @jax.jit
            def kernel(x):
                return x.item()
            """})
        assert out == []

    def test_shard_map_wrapped_kernel_in_parallel(self, tmp_path):
        """The dist_query factory idiom: an undecorated closure becomes a
        kernel by being the first argument of jax.shard_map — and
        parallel/ is in scope alongside query/engine/."""
        out = run_pass(tmp_path, hotpath, {
            "filodb_tpu/parallel/d.py": """
            import jax

            def make_step(mesh):
                def step(ts, vals):
                    def kernel(ts_l, vals_l):
                        return vals_l.sum() + float(ts_l.shape)
                    return jax.shard_map(kernel, mesh=mesh, in_specs=(),
                                         out_specs=())(ts, vals)
                return jax.jit(step)
            """})
        assert codes(out) == ["HP301"]
        assert out[0].symbol == "make_step.step.kernel"

    def test_jit_call_form_wrapped_kernel(self, tmp_path):
        """``jit(fn)`` call form (no decorator) marks ``fn`` a kernel;
        the jitted wrapper's own body is scanned too."""
        out = run_pass(tmp_path, hotpath, {
            "filodb_tpu/parallel/j.py": """
            import time
            from jax import jit

            def prep(vals):
                t = time.time()
                return vals + t

            prep_jitted = jit(prep)
            """})
        assert codes(out) == ["HP302"]
        assert out[0].symbol == "prep"


# --------------------------------------------------------------------------
# RL4xx resource lifecycle

class TestLifecycle:
    def test_rl401_leak_on_exception_narrow_except(self, tmp_path):
        # the remote.py postmortem shape: a checked-out socket crossing
        # raising calls with only a narrow transport-error handler —
        # any other exception class leaks the fd out of the pool
        out = run_pass(tmp_path, lifecycle, {"filodb_tpu/m.py": """
            class D:
                def roundtrip(self, pool, key, msg):
                    sock = pool.checkout(key)
                    try:
                        sock.sendall(msg)
                        resp = sock.recv(4096)
                    except (ConnectionError, OSError):
                        sock.close()
                        raise
                    pool.checkin(key, sock)
                    return resp
            """})
        assert codes(out) == ["RL401"]
        assert "sock" in out[0].detail

    def test_rl401_broad_except_is_protection(self, tmp_path):
        out = run_pass(tmp_path, lifecycle, {"filodb_tpu/m.py": """
            class D:
                def roundtrip(self, pool, key, msg):
                    sock = pool.checkout(key)
                    try:
                        sock.sendall(msg)
                        resp = sock.recv(4096)
                    except BaseException:
                        sock.close()
                        raise
                    pool.checkin(key, sock)
                    return resp
            """})
        assert out == []

    def test_rl401_finally_is_protection(self, tmp_path):
        out = run_pass(tmp_path, lifecycle, {"filodb_tpu/m.py": """
            import socket

            def fetch(host, msg):
                s = socket.create_connection((host, 80))
                try:
                    s.sendall(msg)
                    return s.recv(4096)
                finally:
                    s.close()
            """})
        assert out == []

    def test_rl402_leak_through_helper(self, tmp_path):
        # the acquisition is hidden in a local helper whose summary
        # says "returns a fresh socket"; the caller never releases it
        out = run_pass(tmp_path, lifecycle, {"filodb_tpu/m.py": """
            import socket

            class D:
                def _dial(self):
                    s = socket.create_connection(("h", 80))
                    return s

                def ping(self):
                    sock = self._dial()
                    sock.sendall(b"ping")
            """})
        assert "RL402" in codes(out)
        assert any("self._dial()" in f.detail for f in out)

    def test_release_through_helper_is_clean(self, tmp_path):
        # ...and a release hidden in a helper counts as a release
        out = run_pass(tmp_path, lifecycle, {"filodb_tpu/m.py": """
            import socket

            def _close_quietly(sock):
                try:
                    sock.close()
                except OSError:
                    pass

            def probe(host):
                s = socket.create_connection((host, 80))
                try:
                    s.sendall(b"hi")
                finally:
                    _close_quietly(s)
            """})
        assert out == []

    def test_ownership_transfer_silences(self, tmp_path):
        # storing the socket on self transfers ownership out of the
        # function — constructor caching, not a leak
        out = run_pass(tmp_path, lifecycle, {"filodb_tpu/m.py": """
            import socket

            class Conn:
                def connect(self, host):
                    s = socket.create_connection((host, 80))
                    self._sock = s
                    return self._sock
            """})
        assert out == []

    def test_rl403_thread_not_joined(self, tmp_path):
        out = run_pass(tmp_path, lifecycle, {"filodb_tpu/m.py": """
            import threading

            def fire(work):
                t = threading.Thread(target=work)
                t.start()
            """})
        assert codes(out) == ["RL403"]

    def test_rl403_daemon_or_joined_clean(self, tmp_path):
        out = run_pass(tmp_path, lifecycle, {"filodb_tpu/m.py": """
            import threading

            def daemonized(work):
                t = threading.Thread(target=work, daemon=True)
                t.start()

            def awaited(work):
                t = threading.Thread(target=work)
                t.start()
                t.join()
            """})
        assert out == []

    def test_rl403_self_thread_joined_elsewhere_in_class(self, tmp_path):
        out = run_pass(tmp_path, lifecycle, {"filodb_tpu/m.py": """
            import threading

            class Worker:
                def start(self):
                    self._t = threading.Thread(target=self.run)
                    self._t.start()

                def stop(self):
                    self._t.join()

            class Leaky:
                def start(self):
                    self._t = threading.Thread(target=self.run)
                    self._t.start()
            """})
        assert codes(out) == ["RL403"]
        assert out[0].symbol.startswith("Leaky")

    def test_rl404_ack_outside_finally(self, tmp_path):
        out = run_pass(tmp_path, lifecycle, {"filodb_tpu/m.py": """
            class W:
                def drain_bad(self):
                    item = self._q.get()
                    self.handle(item)
                    self._q.task_done()

                def drain_good(self):
                    item = self._q.get()
                    try:
                        self.handle(item)
                    finally:
                        self._q.task_done()
            """})
        assert codes(out) == ["RL404"]
        assert out[0].symbol == "W.drain_bad"


# --------------------------------------------------------------------------
# CP5xx choke points

class TestChokepoint:
    def test_cp501_deadline_dropped_at_new_call_site(self, tmp_path):
        # a NEW dispatcher subclass that blocks on the network without
        # consulting any deadline — the invariant PR 1 review restored
        # by hand
        out = run_pass(tmp_path, chokepoint, {"filodb_tpu/m.py": """
            class GoodDispatcher(PlanDispatcher):
                def dispatch(self, plan, ctx):
                    ctx.deadline.check()
                    return self._sock.recv(4096)

            class BadDispatcher(PlanDispatcher):
                def dispatch(self, plan, ctx):
                    return self._sock.recv(4096)
            """})
        assert codes(out) == ["CP501"]
        assert out[0].symbol == "BadDispatcher.dispatch"

    def test_cp501_closure_sees_helper_deadline(self, tmp_path):
        # the deadline reference may live in a self-call helper
        out = run_pass(tmp_path, chokepoint, {"filodb_tpu/m.py": """
            class D(PlanDispatcher):
                def dispatch(self, plan, ctx):
                    return self._roundtrip(plan, ctx)

                def _roundtrip(self, plan, ctx):
                    self._sock.settimeout(ctx.deadline.remaining())
                    return self._sock.recv(4096)
            """})
        assert out == []

    def test_cp502_dispatch_outside_admission(self, tmp_path):
        out = run_pass(tmp_path, chokepoint, {
            "filodb_tpu/coordinator/m.py": """
            class Svc:
                def run_bad(self, plan, ctx):
                    return plan.dispatcher.dispatch(plan, ctx)

                def run_good(self, plan, ctx):
                    with governor().admit(cost=2):
                        return plan.dispatcher.dispatch(plan, ctx)
            """})
        assert codes(out) == ["CP502"]
        assert out[0].symbol == "Svc.run_bad"

    def test_cp502_plan_tree_internals_exempt(self, tmp_path):
        # below the gate, dispatch recursion is already admitted
        out = run_pass(tmp_path, chokepoint, {
            "filodb_tpu/query/exec/m.py": """
            class Node:
                def execute(self, ctx):
                    return self.child.dispatcher.dispatch(self.child, ctx)
            """})
        assert out == []

    def test_cp503_direct_bookkeeping(self, tmp_path):
        out = run_pass(tmp_path, chokepoint, {
            "filodb_tpu/coordinator/m.py": """
            def flaky(peer):
                breaker_for(peer).record_failure()
            """,
            "filodb_tpu/utils/resilience.py": """
            class CircuitBreaker:
                def ok(self):
                    self.record_success()
            """})
        assert codes(out) == ["CP503"]
        assert out[0].path == "filodb_tpu/coordinator/m.py"

    def test_cp503_force_open_exempt(self, tmp_path):
        # a failure-detector verdict, not a call outcome
        out = run_pass(tmp_path, chokepoint, {
            "filodb_tpu/coordinator/m.py": """
            def member_lost(peer):
                breaker_for(peer).force_open()
            """})
        assert out == []

    def test_cp504_double_outcome_one_path(self, tmp_path):
        out = run_pass(tmp_path, chokepoint, {
            "filodb_tpu/coordinator/m.py": """
            def call(breaker, req):
                with breaker.calling() as out:
                    resp = send(req)
                    out.success()
                    out.success()
                    return resp
            """})
        assert codes(out) == ["CP504"]

    def test_cp504_alternative_paths_clean(self, tmp_path):
        # the remote_exec shape: each handler is its own path, one
        # outcome per path
        out = run_pass(tmp_path, chokepoint, {
            "filodb_tpu/coordinator/m.py": """
            def call(breaker, req):
                with breaker.calling() as out:
                    try:
                        resp = send(req)
                    except HTTPError:
                        out.success()
                        raise
                    except DecodeError:
                        out.failure()
                        raise
                    return resp
            """})
        assert out == []


# --------------------------------------------------------------------------
# DC601 adaptive-decision settle parity

class TestDecisionParity:
    def test_unsettled_decide_flagged(self, tmp_path):
        out = run_pass(tmp_path, decisionparity, {"filodb_tpu/m.py": """
            def route(model, sig):
                d = model.decide("sidecar", sig, ("a", "b"), "a")
                return "x"
            """})
        assert codes(out) == ["DC601"]

    def test_unsettled_classify_flagged(self, tmp_path):
        out = run_pass(tmp_path, decisionparity, {"filodb_tpu/m.py": """
            def classed(model, sig):
                d = model.classify("admit", sig, 0.05, "cheap",
                                   "expensive", "cheap")
                return d.arm == "cheap"
            """})
        # returning d.arm counts as a return hand-off of d — so settle
        # the bare comparison case by NOT binding d in the return
        assert codes(out) == []
        out = run_pass(tmp_path, decisionparity, {"filodb_tpu/m.py": """
            def classed(model, sig):
                d = model.classify("admit", sig, 0.05, "cheap",
                                   "expensive", "cheap")
                arm = d.arm
                return "ok"
            """})
        assert codes(out) == ["DC601"]

    def test_record_actual_settles(self, tmp_path):
        out = run_pass(tmp_path, decisionparity, {"filodb_tpu/m.py": """
            def route(model, sig, elapsed):
                d = model.decide("paging", sig, ("exact", "wide"), "exact")
                model.record_actual(d, elapsed)
                return d.arm
            """})
        assert out == []

    def test_defer_settles(self, tmp_path):
        out = run_pass(tmp_path, decisionparity, {"filodb_tpu/m.py": """
            def route(model, ctx, sig):
                d = model.decide("sidecar", sig, ("a", "b"), "a")
                model.defer(ctx, d)
                return d.arm == "a"
            """})
        assert out == []

    def test_return_hand_off_settles(self, tmp_path):
        # the decision rides out in a tuple and the caller owns the settle
        out = run_pass(tmp_path, decisionparity, {"filodb_tpu/m.py": """
            def shared_decision(model, arms, arm, sig):
                d = model.decide("paging", sig, tuple(arms), arm)
                return d.arm, d, model
            """})
        assert out == []

    def test_closure_checked_independently(self, tmp_path):
        # a settle in the enclosing function does not excuse a decide
        # trapped inside a closure that never settles
        out = run_pass(tmp_path, decisionparity, {"filodb_tpu/m.py": """
            def outer(model, sig, elapsed):
                def inner():
                    d = model.decide("sidecar", sig, ("a", "b"), "a")
                    return "x"
                other = model.decide("paging", sig, ("a", "b"), "a")
                model.record_actual(other, elapsed)
                return inner
            """})
        assert codes(out) == ["DC601"]
        assert out[0].symbol == "outer.inner"

    def test_cost_model_module_exempt(self, tmp_path):
        out = run_pass(tmp_path, decisionparity, {
            "filodb_tpu/query/cost_model.py": """
            def helper(self, sig):
                d = self.decide("sidecar", sig, ("a", "b"), "a")
                return "x"
            """})
        assert out == []

    def test_inline_suppression(self, tmp_path):
        root = write_tree(tmp_path, {"filodb_tpu/m.py": """
            def route(model, sig):
                d = model.decide("sidecar", sig, ("a", "b"), "a")  # filolint: disable=DC601
                return "x"
            """})
        assert run_all(root, passes=[decisionparity]) == []


# --------------------------------------------------------------------------
# model: suppression, baseline, CLI

class TestModel:
    def test_inline_suppression(self, tmp_path):
        root = write_tree(tmp_path, {"filodb_tpu/m.py": """
            import threading, time

            class C:
                def __init__(self):
                    self._lock = threading.Lock()

                def bad(self):
                    with self._lock:
                        time.sleep(1)  # filolint: disable=LD101
            """})
        out = run_all(root, passes=[lockdiscipline])
        assert out == []

    def test_suppression_is_code_scoped(self):
        lines = ["x = 1  # filolint: disable=LD101"]
        assert suppressed(lines, 1, "LD101")
        assert not suppressed(lines, 1, "LD103")
        assert suppressed(["y  # filolint: disable=all"], 1, "HP302")

    def test_key_is_line_free(self):
        a = Finding("LD101", "p.py", 10, "C.m", "d", "msg")
        b = Finding("LD101", "p.py", 99, "C.m", "d", "msg")
        assert a.key == b.key

    def test_baseline_diff_and_update(self, tmp_path):
        f1 = Finding("LD101", "p.py", 1, "C.m", "d1", "m1")
        f2 = Finding("LD101", "p.py", 2, "C.m", "d2", "m2")
        bl = Baseline()
        bl.update([f1])
        bl.entries[f1.key]["justification"] = "intentional"
        new, stale = bl.diff([f1, f2])
        assert [f.key for f in new] == [f2.key]
        assert stale == []
        new, stale = bl.diff([f2])
        assert [e["key"] for e in stale] == [f1.key]
        # update keeps the human-written justification
        bl.update([f1, f2])
        assert bl.entries[f1.key]["justification"] == "intentional"
        assert "TODO" in bl.entries[f2.key]["justification"]
        path = str(tmp_path / "bl.json")
        bl.save(path)
        assert Baseline.load(path).entries == bl.entries

    def test_cli_gate_roundtrip(self, tmp_path, capsys):
        root = write_tree(tmp_path, {"filodb_tpu/m.py": """
            import threading, time

            class C:
                def __init__(self):
                    self._lock = threading.Lock()

                def bad(self):
                    with self._lock:
                        time.sleep(1)
            """})
        bl = str(tmp_path / "baseline.json")
        assert cli.main(["--root", root, "--baseline", bl]) == 1
        assert cli.main(["--root", root, "--baseline", bl,
                         "--update-baseline"]) == 0
        assert cli.main(["--root", root, "--baseline", bl]) == 0
        out = json.loads(json.dumps(json.load(open(bl))))
        assert out["entries"][0]["code"] == "LD101"
        capsys.readouterr()

    def test_cli_parse_error_exits_2(self, tmp_path, capsys):
        root = write_tree(tmp_path,
                          {"filodb_tpu/bad.py": "def broken(:\n"})
        assert cli.main(["--root", root]) == 2
        capsys.readouterr()

    def test_cli_sarif_output(self, tmp_path, capsys):
        root = write_tree(tmp_path, {"filodb_tpu/m.py": """
            import threading, time

            class C:
                def __init__(self):
                    self._lock = threading.Lock()

                def bad(self):
                    with self._lock:
                        time.sleep(1)
            """})
        bl = str(tmp_path / "baseline.json")
        assert cli.main(["--root", root, "--baseline", bl,
                         "--format", "sarif"]) == 1
        doc = json.loads(capsys.readouterr().out)
        assert doc["version"] == "2.1.0"
        run = doc["runs"][0]
        assert run["tool"]["driver"]["name"] == "filolint"
        # the minimal tree also trips the parity placeholders (PR202/4)
        (res,) = [r for r in run["results"] if r["ruleId"] == "LD101"]
        loc = res["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"] == "filodb_tpu/m.py"
        assert loc["region"]["startLine"] > 0
        # line-free key rides along for CI result matching
        assert res["partialFingerprints"]["filolintKey"].startswith(
            "LD101:")
        assert any(r["id"] == "LD101"
                   for r in run["tool"]["driver"]["rules"])

    def test_cli_changed_only_filters_to_diff_scope(self, tmp_path,
                                                    capsys):
        import subprocess

        root = write_tree(tmp_path, {
            "filodb_tpu/clean.py": "X = 1\n",
            "filodb_tpu/m.py": """
            import threading, time

            class C:
                def __init__(self):
                    self._lock = threading.Lock()

                def bad(self):
                    with self._lock:
                        time.sleep(1)
            """})
        bl = str(tmp_path / "baseline.json")

        def git(*a):
            subprocess.run(["git", *a], cwd=root, check=True,
                           capture_output=True)

        git("init", "-q")
        git("-c", "user.email=t@t", "-c", "user.name=t",
            "commit", "-q", "--allow-empty", "-m", "seed")
        git("add", "-A")
        git("-c", "user.email=t@t", "-c", "user.name=t",
            "commit", "-q", "-m", "base")
        # nothing changed vs HEAD -> the LD101 in m.py is out of scope
        assert cli.main(["--root", root, "--baseline", bl,
                         "--changed-only"]) == 0
        capsys.readouterr()
        # touch m.py -> back in scope
        with open(os.path.join(root, "filodb_tpu", "m.py"), "a") as f:
            f.write("\n")
        assert cli.main(["--root", root, "--baseline", bl,
                         "--changed-only"]) == 1
        capsys.readouterr()

    def test_changed_only_dependent_closure(self, tmp_path):
        # helper.py changed -> caller.py (which imports it) is in scope
        root = write_tree(tmp_path, {
            "filodb_tpu/__init__.py": "",
            "filodb_tpu/helper.py": "def f():\n    return 1\n",
            "filodb_tpu/caller.py":
                "from filodb_tpu.helper import f\n",
            "filodb_tpu/unrelated.py": "Y = 2\n",
        })
        ctx = AnalysisContext.build(root)
        scope = cli._dependent_closure(
            ctx, {"filodb_tpu/helper.py"})
        assert "filodb_tpu/caller.py" in scope
        assert "filodb_tpu/unrelated.py" not in scope


# --------------------------------------------------------------------------
# the repo gate (tier-1 enforcement)

class TestRepoGate:
    def test_repo_has_no_unbaselined_findings(self):
        findings = run_all(REPO_ROOT)
        bl = Baseline.load(BASELINE)
        new, stale = bl.diff(findings)
        assert not new, "new filolint findings (fix or baseline with " \
            "justification):\n" + "\n".join(f.render() for f in new)
        assert not stale, "stale baseline entries (remove them):\n" + \
            "\n".join(e["key"] for e in stale)

    def test_repo_parses_clean(self):
        ctx = AnalysisContext.build(REPO_ROOT)
        assert ctx.errors == []

    def test_every_baseline_entry_is_justified(self):
        bl = Baseline.load(BASELINE)
        assert bl.entries, "baseline should exist and be non-empty"
        unjustified = [k for k, e in bl.entries.items()
                       if not e.get("justification")
                       or "TODO" in e["justification"]]
        assert not unjustified, unjustified
