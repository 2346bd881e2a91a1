"""The mesh engine's host staging pool (``parallel/staging.py``): the
``[P, S]`` arrays of one placement — ``build_batch``'s ``ts``/``vals``, the
validity mask, the ``split`` lane's converted copy, a histogram's flattened
values and repeated ``ts`` — are written into buffers an earlier placement
gave back, and nothing of that shows in an answer: a build into POISONED
buffers is the fresh build bit for bit, the placed arrays never alias host
memory, the arrays a placement only reads stay as they were, a buffer
returns only once the placed arrays are ready, and a cached entry keeps
the header a hit reads and no host samples.

x64 is on here; the poisoned matrix runs with x64 off, as a server does, in
``test_f32_mode.py``."""

import threading

import numpy as np
import pytest
from batch_oracle import RANGES, World, mismatches, per_series_batch
from mesh_oracle import (
    CASES,
    DATASET,
    START,
    STORES,
    forget,
    pad_leased,
    pad_reused,
    placed_mismatches,
    poison,
    run_case,
)

from filodb_tpu.coordinator.query_service import QueryService
from filodb_tpu.core.memstore.native_shard import native_available
from filodb_tpu.parallel import dist_query, mesh_engine, staging
from filodb_tpu.parallel.mesh_engine import (
    _M_BATCH,
    _M_EVAL,
    F32_SAFE_MAX,
    MeshQueryEngine,
    _device_correction_ok,
    make_query_mesh,
)
from filodb_tpu.parallel.staging import Lease, StagingPool
from filodb_tpu.promql.parser import TimeStepParams, parse_query
from filodb_tpu.query.engine.batch import BatchHeader, build_batch
from filodb_tpu.utils import tracing
from filodb_tpu.utils.metrics import BATCH_BUFFER_FRESH, BATCH_BUFFER_REUSED

ARGS = (START + 600, 60, START + 2400)


@pytest.fixture(scope="module")
def stores():
    return {}


def store(stores, name):
    if name not in stores:
        stores[name] = STORES[name]()
    return stores[name]


def services(ms, mesh=(1, 1)):
    """(exec, mesh) services over ``ms``; the mesh engine on ``mesh``."""
    ds, dtm = mesh
    exec_svc = QueryService(ms, DATASET, 4, spread=1)
    mesh_svc = QueryService(ms, DATASET, 4, spread=1, engine="mesh")
    mesh_svc.mesh_engine.mesh = make_query_mesh(ds * dtm, dtm)
    return exec_svc, mesh_svc


def assert_same(want, got):
    order_w = np.argsort([str(k) for k in want.keys])
    order_g = np.argsort([str(k) for k in got.keys])
    assert [str(want.keys[i]) for i in order_w] == \
        [str(got.keys[i]) for i in order_g]
    np.testing.assert_allclose(np.asarray(got.values)[order_g],
                               np.asarray(want.values)[order_w],
                               rtol=1e-6, atol=1e-9, equal_nan=True)


def free_buffers(pool) -> list:
    return [b for bufs in pool._free.values() for b in bufs]


def buffer_bytes():
    return BATCH_BUFFER_FRESH.value, BATCH_BUFFER_REUSED.value


@pytest.fixture
def device_dtype(monkeypatch):
    """Make the device's float dtype what a test names, as ``fdtype`` and
    ``device_float`` report it (x64 is on in this process; a server's is
    f32): decides the lane and the dtype that is placed, runs no program."""
    import jax.numpy as jnp

    from filodb_tpu.query.engine import kernels

    def set_to(name):
        monkeypatch.setattr(kernels, "fdtype", lambda: jnp.dtype(name))

    return set_to


# --- (a) a build into poisoned buffers is the fresh build, bit for bit ------

@pytest.mark.skipif(not native_available(),
                    reason="native library unavailable")
@pytest.mark.parametrize("host_f64", [True, False], ids=["f64", "device"])
@pytest.mark.parametrize("rng", ["all", "cuts-sealed", "cuts-buffer"])
def test_native_and_fallback_rows_into_poisoned_buffers(rng, host_f64):
    """Rows the shard cores fill and rows read one at a time, ragged counts
    and an empty row: the native fill leaves a row's tail alone, so every
    pad cell is the pool's refill."""
    world = World()
    lo, hi = RANGES[rng]
    pool = StagingPool()
    kw = dict(host_f64=host_f64, mesh_multiples=(4, 2))
    with np.errstate(over="ignore"):
        want = per_series_batch(world.parts, lo, hi, **kw)
        first = pool.lease()
        build_batch(world.parts, lo, hi, alloc=first.take, **kw)
        first.give_back()
        assert poison(pool) == want.ts.nbytes + want.vals.nbytes
        lease = pool.lease()
        got = build_batch(world.parts, lo, hi, alloc=lease.take, **kw)
    assert pool.held_bytes == 0                 # both came from the pool
    assert mismatches(got, want) == []


MATRIX = ["raw-avg", "raw-max-fused", "raw-last-sample", "split-small",
          "split-delta", "histogram-split", "histogram-raw",
          # since mesh-pad's own arrays are the pool's too: a histogram
          # ``rate`` into one group, the scalar split lane over counters
          "histogram-split-one-group", "split-delta-counter",
          "split-big-rate"]


@pytest.mark.parametrize("mesh_name", ["1x1", "2x2"])
@pytest.mark.parametrize("case", MATRIX)
def test_a_query_into_poisoned_buffers_places_the_parents_bits(
        case, mesh_name, stores):
    """ts, vals, the mask, the group ids: what the device receives from
    poisoned staging buffers is what the parent's fresh arrays held —
    the split lane's converted copy and a histogram's flattened values and
    repeated ``ts`` among them — and the answer is the exec tree's."""
    cap = run_case(case, mesh_name, stores, run=True, poisoned=True)
    assert placed_mismatches(cap) == []
    assert cap.tags["batch-stack"]["reused_bytes"] == \
        cap.batch.ts.nbytes + cap.batch.vals.nbytes
    mask, made = pad_leased(cap)
    assert (made > 0) == (CASES[case][2] == "split" or
                          cap.batch.is_histogram)
    assert pad_reused(cap) == ((mask, made) if cap.batch.is_histogram
                               else (mask + made, 0))
    exec_svc = QueryService(stores[CASES[case][0]], DATASET, 4, spread=1)
    assert_same(exec_svc.query_range(CASES[case][1], *ARGS).result,
                cap.result)


# --- (a2) what mesh-pad itself writes: the converted copy, the flatten ------

def parent_placed_values(vals):
    """The parent's expressions for the placed value array of a host
    ``[P, S]`` or ``[P, S, B]`` array: flatten, ``astype``, NaN to 0."""
    from filodb_tpu.query.engine.batch import device_float

    if vals.ndim == 3:
        p, s_, b = vals.shape
        vals = np.ascontiguousarray(vals.transpose(0, 2, 1)).reshape(p * b,
                                                                     s_)
    out = vals.astype(device_float())
    np.putmask(out, np.isnan(out), 0)
    return out


@pytest.mark.parametrize("device", ["float32", "float64"])
@pytest.mark.parametrize("source", ["float32", "float64"])
@pytest.mark.parametrize("shape", [(8, 16), (8, 16, 5)],
                         ids=["scalar", "histogram"])
def test_placed_values_into_garbage_is_the_parents_array(
        shape, source, device, device_dtype):
    """One pass that transposes and rounds, into a buffer and a mark
    buffer that hold the last build's bytes: the parent's flatten,
    ``astype`` and NaN pass bit for bit, every element overwritten, the
    input only read (it may be ``delta_host``'s cached array)."""
    device_dtype(device)
    rng = np.random.default_rng(3)
    vals = (rng.normal(size=shape) * 10.0 ** rng.integers(
        -3, 9, size=shape)).astype(source)
    vals[rng.random(shape) < 0.2] = np.nan
    vals[2] = np.nan                                    # an empty series
    vals[:, 11:] = np.nan                               # the padding
    vals[0, 0] = np.inf
    kept = vals.copy()
    want = parent_placed_values(vals)
    pool = StagingPool()
    first = pool.lease()
    first.take(want.shape, want.dtype)
    first.take(want.shape, np.bool_)
    first.give_back()
    assert poison(pool) == want.nbytes + want.size
    lease = pool.lease()
    marks = lease.take(want.shape, np.bool_)
    got = mesh_engine._placed_values(vals, lease.take, marks)
    assert pool.held_bytes == 0                 # both came from the pool
    assert got.dtype == want.dtype and got.flags.c_contiguous
    assert got.shape == want.shape and got.tobytes() == want.tobytes()
    assert not np.shares_memory(got, vals)
    assert vals.tobytes() == kept.tobytes()


@pytest.mark.parametrize("case", ["split-big-rate", "split-big",
                                  "split-small", "split-delta",
                                  "histogram-split"])
def test_a_placement_only_reads_built_vals_and_delta_hosts_array(
        case, stores, device_dtype):
    """The device dtype is f32 here, as in a server: counters over 2**20
    take the host's f64 pre-pass, whose array ``delta_host`` caches on the
    batch. Neither it nor ``built.vals`` (placed beside it for the
    extrapolation clamp) is written by the conversion into the pool's
    buffers, and the converted copies are arrays of their own."""
    device_dtype("float32")
    cap = run_case(case, "2x2", stores)
    assert placed_mismatches(cap) == []
    vals_p, raw_p = cap.got[1], cap.got[4]
    assert vals_p.dtype == np.float32 and cap.batch.vals.dtype == np.float64
    # the same series built again, untouched by any placement
    assert cap.batch.vals.tobytes() == cap.f64.vals.tobytes()
    assert not np.shares_memory(vals_p, cap.batch.vals)
    cached = getattr(cap.batch, "_delta_host", {})
    assert len(cached) == (1 if case.startswith("split-big") else 0)
    for counter, rebased in cached.items():
        assert rebased.tobytes() == cap.f64.delta_host(counter).tobytes()
        assert cap.batch.delta_host(counter) is rebased
        assert not np.shares_memory(vals_p, rebased)
        assert np.isnan(rebased).any() and not np.isnan(vals_p).any()
    assert (raw_p is not None) == (case in ("split-big-rate", "split-big"))
    if raw_p is not None:
        assert not np.shares_memory(raw_p, cap.batch.vals)
        assert raw_p is not vals_p and raw_p.dtype == np.float32


# --- (b) the placed arrays never alias the staging buffers ------------------

@pytest.mark.parametrize("mesh", [(1, 1), (4, 2)], ids=["1x1", "4x2"])
def test_overwriting_returned_buffers_changes_no_answer(mesh, stores):
    """On a CPU mesh a put may alias aligned host memory; the engine asks
    that it does not. After the give-back the buffers are garbage, then
    another query's build: the first query's placed arrays (a batch-cache
    hit) still answer right."""
    exec_svc, mesh_svc = services(store(stores, "gauge"), mesh)
    eng = mesh_svc.mesh_engine
    qa = "avg by (host)(avg_over_time(gauge_metric[5m]))"
    # the same series under another selector: a build of the same shapes
    qb = 'sum(sum_over_time(gauge_metric{host!=""}[5m]))'
    want_a = exec_svc.query_range(qa, *ARGS).result
    assert_same(want_a, mesh_svc.query_range(qa, *ARGS).result)
    assert poison(eng._staging) > 0
    hit0 = _M_BATCH["hit"].value
    held = eng._staging.held_bytes
    assert_same(want_a, mesh_svc.query_range(qa, *ARGS).result)
    assert _M_BATCH["hit"].value == hit0 + 1
    assert eng._staging.held_bytes == held      # a hit takes nothing
    reused0 = BATCH_BUFFER_REUSED.value
    assert_same(exec_svc.query_range(qb, *ARGS).result,
                mesh_svc.query_range(qb, *ARGS).result)
    assert BATCH_BUFFER_REUSED.value - reused0 == held  # qb wrote into them
    poison(eng._staging)
    assert_same(want_a, mesh_svc.query_range(qa, *ARGS).result)
    assert_same(exec_svc.query_range(qb, *ARGS).result,
                mesh_svc.query_range(qb, *ARGS).result)
    assert _M_BATCH["hit"].value == hit0 + 3


def test_shard_batch_arrays_copies_on_the_cpu_mesh():
    """The contract itself: what ``shard_batch_arrays`` returns holds its
    own memory, one device or eight."""
    import jax

    for n, t in ((1, 1), (8, 2)):
        mesh = make_query_mesh(n, t)
        ts = np.arange(64 * 128, dtype=np.int32).reshape(64, 128)
        vals = np.ones((64, 128), np.float64)
        valid = np.ones((64, 128), bool)
        gid = np.zeros(64, np.int32)
        want = [a.copy() for a in (ts, vals, valid, gid, vals)]
        placed = dist_query.shard_batch_arrays(mesh, ts, vals, valid, gid,
                                               vals)
        jax.block_until_ready(placed)
        for a in (ts, vals, valid, gid):
            a.view(np.uint8).fill(0xA5)
        for p, w in zip(placed, want):
            np.testing.assert_array_equal(np.asarray(p), w)


# --- (c) a miss that no program reads: given back only when ready -----------

def test_eval_cache_hit_gives_back_after_the_placed_arrays_are_ready(
        stores, monkeypatch):
    """``sum(rate(sel))`` then ``avg(rate(sel))`` once the batch cache
    dropped the first's held batch, as an eviction would: the second is a
    batch-cache miss that builds the same held batch again, whose
    evaluated windows come from the eval cache, so no program reads its
    new arrays and only ``block_until_ready`` knows the puts are done."""
    import jax

    exec_svc, mesh_svc = services(store(stores, "counter"))
    eng = mesh_svc.mesh_engine
    q1 = "sum(rate(http_requests_total[5m]))"
    q2 = "avg(rate(http_requests_total[5m]))"
    assert_same(exec_svc.query_range(q1, *ARGS).result,
                mesh_svc.query_range(q1, *ARGS).result)
    order = []
    real_ready, real_give = jax.block_until_ready, Lease.give_back

    def ready(x):
        order.append(("ready", x))
        return real_ready(x)

    def give(self):
        order.append(("give", list(self._taken)))
        return real_give(self)

    monkeypatch.setattr(jax, "block_until_ready", ready)
    monkeypatch.setattr(Lease, "give_back", give)
    eng._batch_cache.clear()
    hit0 = _M_EVAL["hit"].value
    with tracing.start_trace() as trace:
        got = mesh_svc.query_range(q2, *ARGS).result
    assert _M_EVAL["hit"].value == hit0 + 1
    tags = {s.name: s.tags for s in trace.spans}
    assert tags["mesh-dispatch"]["eval_cache"] == "hit"
    assert tags["batch-stack"]["reused_bytes"] > 0
    assert [what for what, _ in order] == ["ready", "give"]
    (_, placed), (_, taken) = order
    entry = next(reversed(eng._batch_cache.values()))
    # ts, vals, the mask, the split lane's converted copy
    assert placed is entry[5] and len(taken) == 4
    assert_same(exec_svc.query_range(q2, *ARGS).result, got)
    monkeypatch.undo()
    # the buffers are anyone's now; both entries answer from the device
    poison(eng._staging)
    hits = _M_BATCH["hit"].value
    for q in (q1, q2):
        assert_same(exec_svc.query_range(q, *ARGS).result,
                    mesh_svc.query_range(q, *ARGS).result)
    assert _M_BATCH["hit"].value == hits + 2


# --- (d) a placement that does not reach its end ----------------------------

def test_an_exception_between_take_and_give_back_drops_the_buffers(
        stores, monkeypatch):
    exec_svc, mesh_svc = services(store(stores, "gauge"))
    eng = mesh_svc.mesh_engine
    q = "avg by (host)(avg_over_time(gauge_metric[5m]))"
    mesh_svc.query_range(q, *ARGS)
    held = eng._staging.held_bytes
    before = free_buffers(eng._staging)
    assert held > 0 and len(before) == 3
    forget(eng)

    def boom(*a, **kw):
        raise RuntimeError("the put failed")

    monkeypatch.setattr(dist_query, "shard_batch_arrays", boom)
    low = eng._lower(parse_query(q, TimeStepParams(*ARGS)))
    with pytest.raises(RuntimeError, match="the put failed"):
        eng.execute_lowered_many([low], mesh_svc.memstore, DATASET)
    # taken, never given back: the pool no longer knows them
    assert eng._staging.held_bytes == 0 and free_buffers(eng._staging) == []
    monkeypatch.undo()
    fresh0 = BATCH_BUFFER_FRESH.value
    assert_same(exec_svc.query_range(q, *ARGS).result,
                eng.execute_lowered_many([low], mesh_svc.memstore,
                                         DATASET)[0])
    assert BATCH_BUFFER_FRESH.value - fresh0 == held
    after = free_buffers(eng._staging)
    assert len(after) == 3 and not {id(b) for b in after} & \
        {id(b) for b in before}


def test_the_histogram_early_return_drops_the_buffers(stores):
    """``avg`` over histograms is the exec tree's: the engine finds out
    after the build and returns None — its lease is never given back."""
    ms = store(stores, "histogram")
    eng = MeshQueryEngine(mesh=make_query_mesh(1, 1))

    def run(q):
        low = eng._lower(parse_query(q, TimeStepParams(*ARGS)))
        return eng.execute_lowered_many([low], ms, DATASET)[0]

    want = run("sum(rate(http_req_latency[5m]))")
    before = free_buffers(eng._staging)
    # ts and vals as built; the mask, the values and ts a bucket row
    assert len(before) == 5
    assert run("avg(rate(http_req_latency[5m]))") is None
    # the build took ts and vals; mesh-pad's three were never asked for
    left = free_buffers(eng._staging)
    assert len(left) == 3 and {id(b) for b in left} < {id(b) for b in before}
    assert len({b.shape for b in left}) == 1
    assert sorted(b.dtype.str for b in left) == sorted(
        np.dtype(t).str for t in (np.bool_, np.int32, np.float64))
    assert eng._staging.held_bytes == sum(b.nbytes for b in left)
    forget(eng)
    again = run("sum(rate(http_req_latency[5m]))")
    np.testing.assert_array_equal(np.asarray(again.values),
                                  np.asarray(want.values))


@pytest.mark.parametrize("case", ["split-small", "histogram-split"])
def test_an_exception_inside_mesh_pad_drops_what_was_leased(
        case, stores, monkeypatch):
    """The conversion fails after it took its buffer: the builder's arrays,
    the mask and that buffer are dropped with the lease; what the phase had
    not asked for yet (a histogram's repeated ``ts``) stays in the pool."""
    store_name, q, _ = CASES[case]
    exec_svc, mesh_svc = services(store(stores, store_name))
    eng = mesh_svc.mesh_engine
    mesh_svc.query_range(q, *ARGS)
    before = free_buffers(eng._staging)
    held = eng._staging.held_bytes
    histogram = case.startswith("histogram")
    assert len(before) == (5 if histogram else 4) and held > 0
    forget(eng)
    real = mesh_engine._placed_values

    def boom(vals, take, marks):
        real(vals, take, marks)
        raise RuntimeError("the copy failed")

    monkeypatch.setattr(mesh_engine, "_placed_values", boom)
    low = eng._lower(parse_query(q, TimeStepParams(*ARGS)))
    with pytest.raises(RuntimeError, match="the copy failed"):
        eng.execute_lowered_many([low], mesh_svc.memstore, DATASET)
    left = free_buffers(eng._staging)
    assert [b.dtype for b in left] == ([np.int32] if histogram else [])
    assert eng._staging.held_bytes == sum(b.nbytes for b in left) < held
    assert {id(b) for b in left} <= {id(b) for b in before}
    monkeypatch.undo()
    fresh0 = BATCH_BUFFER_FRESH.value
    assert_same(exec_svc.query_range(q, *ARGS).result,
                eng.execute_lowered_many([low], mesh_svc.memstore,
                                         DATASET)[0])
    assert BATCH_BUFFER_FRESH.value - fresh0 == \
        held - sum(b.nbytes for b in left)
    assert eng._staging.held_bytes == held


def test_nothing_is_given_back_twice():
    pool = StagingPool()
    lease = pool.lease()
    a = lease.take((8, 8), np.int32, 7)
    lease.give_back()
    lease.give_back()
    assert free_buffers(pool) == [a] and pool.held_bytes == a.nbytes
    one, two = pool.lease(), pool.lease()
    x, y = one.take((8, 8), np.int32, 1), two.take((8, 8), np.int32, 2)
    assert x is a and y is not a
    assert (x == 1).all() and (y == 2).all()


def test_a_buffer_has_one_holder_under_threads():
    """The threaded front calls the engine from several threads: a buffer
    is in the pool or with one lease."""
    pool = StagingPool()
    bad = []

    def worker(k):
        for _ in range(200):
            lease = pool.lease()
            a = lease.take((64, 64), np.int32, k)
            b = lease.take((64, 64), np.int32, k)
            if a is b or not ((a == k).all() and (b == k).all()):
                bad.append(k)
            lease.give_back()

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert bad == []
    free = free_buffers(pool)
    assert len({id(b) for b in free}) == len(free) <= 12
    assert pool.held_bytes == sum(b.nbytes for b in free)


# --- (e) the cap, in bytes --------------------------------------------------

def test_the_cap_holds_and_the_oldest_shape_goes_first():
    pool = StagingPool(cap_bytes=64 << 10)
    shapes = [(16, 2 ** k) for k in range(4, 9)]    # 1 .. 16 KiB of int32
    for rounds in range(3):
        for shape in shapes:
            lease = pool.lease()
            lease.take(shape, np.int32, 0)
            lease.take(shape, np.float64, np.nan)
            lease.give_back()
            assert pool.held_bytes <= pool.cap_bytes
            assert pool.held_bytes == sum(b.nbytes
                                          for b in free_buffers(pool))
    # 1+2 + 2+4 + ... + 16+32 KiB = 93: the smallest, given first, went
    kept = {b.shape for b in free_buffers(pool)}
    assert (16, 16) not in kept and (16, 256) in kept
    # a take makes a shape the newest: it outlives a flood of another
    pool = StagingPool(cap_bytes=3 * 4096)
    for shape in ((32, 32), (16, 64)):
        lease = pool.lease()
        lease.take(shape, np.int32, 0)
        lease.give_back()
    lease = pool.lease()
    lease.take((32, 32), np.int32, 0)
    lease.give_back()                               # (32, 32) is newest
    flood = [pool.lease() for _ in range(2)]
    for le in flood:
        le.take((8, 128), np.int32, 0)
    for le in flood:
        le.give_back()
    assert {b.shape for b in free_buffers(pool)} == {(32, 32), (8, 128)}


def test_a_buffer_over_the_cap_is_never_kept():
    pool = StagingPool(cap_bytes=4096)
    lease = pool.lease()
    small = lease.take((4, 4), np.int32, 0)
    lease.take((64, 64), np.int32, 0)               # 16 KiB
    lease.give_back()
    assert free_buffers(pool) == [small]
    assert staging.POOL_CAP_BYTES == 2 * (64 + 64 + 16) * 2 ** 20
    assert MeshQueryEngine()._staging.cap_bytes == staging.POOL_CAP_BYTES


def test_a_reused_buffer_is_refilled_whole():
    pool = StagingPool()
    for dtype, fill in ((np.int32, np.iinfo(np.int32).max),
                        (np.float64, np.nan), (np.float32, 0),
                        (np.float64, 0)):
        lease = pool.lease()
        lease.take((8, 16), dtype, fill)
        lease.give_back()
        poison(pool)
        lease = pool.lease()
        got = lease.take((8, 16), dtype, fill)
        want = np.full((8, 16), fill, dtype)
        assert got.dtype == want.dtype and got.tobytes() == want.tobytes()
        assert got.flags.c_contiguous
        assert pool.held_bytes == 0


# --- (f) the exec leaf's batches own ordinary arrays ------------------------

def test_the_exec_leafs_cached_batch_is_never_written_into(monkeypatch):
    """The exec leaf keeps its batches in the shard's cache and reads their
    ``ts``/``vals`` for as long as they live: it passes no allocator."""
    from filodb_tpu.query.exec import plan

    monkeypatch.setenv("FILODB_SIDECARS", "0")  # the decode lane, always
    ms = STORES["gauge"]()              # no exec leaf has cached here yet
    exec_svc, mesh_svc = services(ms)
    q = "gauge_metric"
    cached, passed = [], []

    def recording(*a, **kw):
        passed.append("alloc" in kw)
        cached.append(build_batch(*a, **kw))
        return cached[-1]

    monkeypatch.setattr(plan, "build_batch", recording)
    before = buffer_bytes()
    want = exec_svc.query_range(q, *ARGS).result
    assert buffer_bytes() == before and cached and not any(passed)
    built = len(cached)
    exec_svc.query_range(q, *ARGS)
    assert len(cached) == built                 # answered from the cache
    bits = [(b.ts.tobytes(), b.vals.tobytes()) for b in cached]
    pool = mesh_svc.mesh_engine._staging
    for args in (ARGS, (START + 660, 60, START + 2400)):
        for query in (q, "avg by (host)(avg_over_time(gauge_metric[5m]))",
                      "max(max_over_time(gauge_metric[5m]))"):
            mesh_svc.query_range(query, *args)
            forget(mesh_svc.mesh_engine)
            poison(pool)
    mine = {id(b) for b in free_buffers(pool)}
    for b, (ts, vals) in zip(cached, bits):
        assert id(b.ts) not in mine and id(b.vals) not in mine
        assert not any(np.shares_memory(b.ts, p) or
                       np.shares_memory(b.vals, p)
                       for p in free_buffers(pool))
        assert (b.ts.tobytes(), b.vals.tobytes()) == (ts, vals)
    assert_same(want, exec_svc.query_range(q, *ARGS).result)
    assert len(cached) == built


# --- (g) the counter and the tags -------------------------------------------

def test_first_build_fresh_second_reused_and_a_hit_moves_neither(stores):
    _, mesh_svc = services(store(stores, "gauge"))
    q = "avg by (host)(avg_over_time(gauge_metric[5m]))"

    def traced(args):
        f0, r0 = buffer_bytes()
        with tracing.start_trace() as trace:
            mesh_svc.query_range(q, *args)
        tags = {s.name: s.tags for s in trace.spans}
        f1, r1 = buffer_bytes()
        return tags, f1 - f0, r1 - r0

    tags, fresh, reused = traced(ARGS)
    shape = tags["batch-stack"]["shape"]
    placed = shape[0] * shape[1] * (4 + 8)          # ts i32 + vals f64 (x64)
    mask = shape[0] * shape[1]
    assert (fresh, reused) == (placed + mask, 0)
    assert tags["batch-stack"]["reused_bytes"] == 0
    assert tags["mesh-pad"]["reused_bytes"] == 0
    tags, fresh, reused = traced(ARGS)              # a batch-cache hit
    assert "batch-stack" not in tags and "mesh-pad" not in tags
    assert (fresh, reused) == (0, 0)
    # another selector of the same series (another chunk range finds the
    # held batch): a miss that writes into the first build's arrays
    q = 'avg by (host)(avg_over_time(gauge_metric{host!=""}[5m]))'
    tags, fresh, reused = traced(ARGS)
    assert tags["batch-stack"]["shape"] == shape
    assert (fresh, reused) == (0, placed + mask)
    assert tags["batch-stack"]["reused_bytes"] == placed
    assert tags["mesh-pad"]["reused_bytes"] == mask


# --- (h) readers of the cached entry's layout -------------------------------

def test_the_cached_entry_keeps_the_header_and_the_placed_arrays(stores):
    import chip_smoke

    _, mesh_svc = services(store(stores, "histogram"))
    eng = mesh_svc.mesh_engine
    r = mesh_svc.query_range("sum(rate(http_req_latency[5m])) by (app)",
                             *ARGS)
    assert len(eng._batch_cache) == 1               # http/server.py, multiproc
    (entry,) = eng._batch_cache.values()
    version, head, keys, gids, out_keys, placed, is_counter = entry
    assert isinstance(head, BatchHeader)
    assert not hasattr(head, "ts") and not hasattr(head, "vals")
    assert head.is_histogram and head.buckets == len(head.les) > 1
    assert int(head.counts.sum()) == r.stats.samples_scanned > 0
    assert len(head.part_ids) == len(keys)
    assert chip_smoke.mesh_batches(mesh_svc) == [placed]
    assert all(hasattr(a, "devices") for a in placed)
    # nothing of the entry is a staging buffer
    mine = free_buffers(eng._staging)
    assert mine and not any(np.shares_memory(head.counts, b) or
                            np.shares_memory(gids, b) for b in mine)
    _, scalar_svc = services(store(stores, "gauge"))
    scalar_svc.query_range("gauge_metric", *ARGS)
    (entry,) = scalar_svc.mesh_engine._batch_cache.values()
    assert not entry[1].is_histogram and entry[1].buckets == 1
    assert entry[1].les is None


# --- (i) the lane decision makes no copy and is the masked form's ----------

def masked_form(vals) -> bool:
    """``_device_correction_ok`` as the parent had it where the device
    dtype is f32: a boolean-mask copy of the finite values and its
    ``abs``."""
    finite = vals[np.isfinite(vals)]
    return finite.size == 0 or float(np.abs(finite).max()) < F32_SAFE_MAX


def _histogram_values(big=None):
    vals = np.random.default_rng(9).random((4, 8, 3)) * 1000.0
    vals[:, 5:] = np.nan
    vals[1] = np.nan
    if big is not None:
        vals[3, 2, 1] = big
    return vals


NAN, INF = np.nan, np.inf
GATE = {
    "nan-padding": np.array([[1.0, 2.5, NAN, NAN], [3.0, NAN, NAN, NAN]]),
    "nan-padding-beside-big": np.array([[1.0, 3.0e9, NAN, NAN]]),
    "all-nan": np.full((4, 8), NAN),
    "empty": np.empty((0, 8)),
    "empty-histogram": np.empty((4, 0, 3)),
    "inf-beside-small": np.array([[NAN, INF, -INF, 5.0]]),
    "plus-inf-beside-small": np.array([[INF, 5.0, 7.0]]),
    "minus-inf-beside-small": np.array([[-INF, 5.0, 7.0]]),
    "inf-beside-big": np.array([[INF, 5.0, F32_SAFE_MAX + 1.0]]),
    "minus-inf-beside-big": np.array([[-INF, 5.0], [NAN, 2.0 ** 21]]),
    "inf-beside-big-negative": np.array([[INF, -INF, -3.0e9, 1.0]]),
    "only-inf": np.array([[INF, -INF, NAN]]),
    "exactly-2**20": np.array([[0.0, F32_SAFE_MAX]]),
    "exactly-minus-2**20": np.array([[-F32_SAFE_MAX, 0.0]]),
    "just-under-2**20": np.array([[np.nextafter(F32_SAFE_MAX, 0.0), 1.0]]),
    "large-negative": np.array([[-3.0e9, 1.0, NAN]]),
    "small-negative": np.array([[-1000.5, -1.0, NAN]]),
    "zeros": np.zeros((3, 4)),
    "histogram": _histogram_values(),
    "histogram-one-big-bucket": _histogram_values(big=5.0e7),
    "histogram-one-inf-bucket": _histogram_values(big=INF),
    "not-contiguous": _histogram_values(big=-2.0 ** 20).transpose(0, 2, 1),
    "float32": np.array([[1.0, 2.0 ** 20, NAN]], np.float32),
}


@pytest.mark.parametrize("device", ["float32", "float64"],
                         ids=["f32", "x64"])
@pytest.mark.parametrize("name", GATE)
def test_the_lane_decision_is_the_masked_forms_boolean(name, device,
                                                       device_dtype):
    """Beside ``test_mesh_sharded.py::TestPrecisionGate``: the largest
    finite magnitude from two reductions that skip NaN, the masked form
    where an infinity hides it — the same boolean for every input, so the
    lane a batch takes is the parent's. Under x64 the device corrects
    whatever it is given."""
    device_dtype(device)
    vals = GATE[name]
    kept = vals.copy()
    got = _device_correction_ok(vals)
    assert isinstance(got, bool)
    assert got == (masked_form(vals) if device == "float32" else True)
    assert np.array_equal(vals, kept, equal_nan=True)


def test_the_lane_decision_allocates_no_array_of_the_batchs_size(
        device_dtype):
    """No boolean-mask copy, no ``abs``: peak extra memory of a decision
    over 4 MB of finite values and NaN padding is numpy's 64 KiB iteration
    buffer, whatever the batch's size, where the masked form makes two
    arrays of the finite values' size and a mask."""
    import tracemalloc

    device_dtype("float32")
    vals = np.random.default_rng(1).random((64, 128, 64)) * 1000.0
    vals[:, 100:] = np.nan

    def peak(fn):
        tracemalloc.start()
        try:
            assert fn(vals)
            return tracemalloc.get_traced_memory()[1]
        finally:
            tracemalloc.stop()

    assert peak(masked_form) > vals.nbytes
    assert peak(_device_correction_ok) < 128 << 10 < vals.nbytes // 16
