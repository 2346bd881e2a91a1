"""Distributed (shard × time mesh) query tests on the virtual 8-device CPU
mesh: the device programs, called in the order the mesh engine calls them,
must match the single-device kernel exactly.

Counterpart of the reference's multi-jvm distributed query tests
(``coordinator/src/multi-jvm/...``) — here distribution is an SPMD program, so
"multi-node" correctness is exercised by sharding over virtual devices.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec
from mesh_oracle import pad_for_mesh

from filodb_tpu.parallel import dist_query as dq
from filodb_tpu.parallel.dist_query import (
    COUNTER_FNS,
    SPLIT_FNS,
    make_distributed_range_agg,
    make_mesh_bounds,
    make_mesh_eval_delta,
    make_mesh_eval_simple,
    make_mesh_group_reduce,
    make_mesh_prepare,
)
from filodb_tpu.query.engine import kernels
from filodb_tpu.query.engine.aggregations import aggregate
from filodb_tpu.query.engine.batch import TS_PAD


def make_series(P=12, S=200, seed=0, resets=True):
    rng = np.random.default_rng(seed)
    ts = np.full((P, S), TS_PAD, np.int32)
    vals = np.zeros((P, S), np.float64)
    counts = np.zeros(P, np.int32)
    for p in range(P):
        n = int(rng.integers(S // 2, S))
        t = np.cumsum(rng.integers(5_000, 15_000, n))
        v = np.cumsum(rng.integers(0, 20, n)).astype(float)
        if resets and n > 50:
            r = int(rng.integers(20, n - 10))
            v[r:] -= v[r]
        ts[p, :n] = t
        vals[p, :n] = v
        counts[p] = n
    return ts, vals, counts


@pytest.fixture(scope="module")
def mesh():
    devs = np.array(jax.devices()[:8]).reshape(4, 2)
    return Mesh(devs, ("shard", "time"))


def on_mesh(mesh, fn, agg, num_groups, ts, vals, counts, gids, steps,
            window):
    """``agg(fn(x[w])) by (g)`` through the programs the engine runs for
    ``fn``: prepare → bounds → eval → group reduce where it has a split
    form, the one masked-scan program where it has none."""
    ts_p, vals_p, valid, gid_p = (jnp.asarray(a) for a in pad_for_mesh(
        ts, vals, counts, gids, mesh))
    steps, window = jnp.asarray(steps), jnp.asarray(window)
    if fn not in SPLIT_FNS:
        return np.asarray(make_distributed_range_agg(
            mesh, fn, num_groups, agg)(ts_p, vals_p, valid, gid_p, steps,
                                       window))
    lo, hi = make_mesh_bounds(mesh)(ts_p, steps, window)
    if fn in COUNTER_FNS:
        cv = make_mesh_prepare(mesh, "counter")(vals_p, valid)
        ev = make_mesh_eval_delta(mesh, fn)(ts_p, vals_p, valid, lo, hi,
                                            steps, window, cv=cv)
    else:
        cs, cn, cs2 = make_mesh_prepare(mesh, "prefix")(vals_p, valid)
        ev = make_mesh_eval_simple(mesh, fn)(ts_p, vals_p, valid, cs, cn,
                                             cs2, lo, hi, steps, window)
    return np.asarray(make_mesh_group_reduce(mesh, num_groups, agg)(ev,
                                                                    gid_p))


def single_device(fn, agg, num_groups, ts, vals, counts, gids, steps, window):
    per_series = kernels.range_eval(
        fn, jnp.asarray(ts), jnp.asarray(vals), jnp.asarray(counts),
        jnp.asarray(steps), jnp.asarray(window))
    return np.asarray(aggregate(agg, per_series, jnp.asarray(gids),
                                num_groups))


class TestDistributedSumRate:
    def test_matches_single_device(self, mesh):
        P, S = 12, 200
        ts, vals, counts = make_series(P, S)
        gids = np.arange(P, dtype=np.int32) % 3
        steps = np.arange(600_000, 1_500_000, 60_000, dtype=np.int32)
        args = (ts, vals, counts, gids, steps, np.int32(300_000))
        np.testing.assert_allclose(
            on_mesh(mesh, "rate", "sum", 3, *args),
            single_device("rate", "sum", 3, *args),
            rtol=1e-9, atol=1e-12, equal_nan=True)

    def test_boundary_resets_handled(self, mesh):
        # counters that reset exactly around time-block boundaries
        P, S = 4, 160
        ts = np.full((P, S), TS_PAD, np.int32)
        vals = np.zeros((P, S), np.float64)
        counts = np.full(P, S, np.int32)
        for p in range(P):
            t = np.arange(S, dtype=np.int64) * 10_000 + 10_000
            v = np.cumsum(np.ones(S)) * (p + 1)
            # reset at the exact S/2 boundary (where the time axis splits)
            v[S // 2:] -= v[S // 2]
            ts[p] = t
            vals[p] = v
        gids = np.zeros(P, np.int32)
        steps = np.array([900_000, 1_200_000], dtype=np.int32)
        args = (ts, vals, counts, gids, steps, np.int32(600_000))
        np.testing.assert_allclose(
            on_mesh(mesh, "rate", "sum", 1, *args),
            single_device("rate", "sum", 1, *args),
            rtol=1e-9, equal_nan=True)

    def test_one_time_block_empty(self, mesh):
        """Few samples, all in the first time block, the first of them long
        after the window opens: the second block's partials are empty, and
        the extrapolation depends on the true global first and last sample —
        a combine that let the empty block's sentinels or zeros in would
        diverge here."""
        P_, S = 8, 128
        ts = np.full((P_, S), TS_PAD, np.int32)
        vals = np.zeros((P_, S), np.float64)
        counts = np.full(P_, 40, np.int32)
        rng = np.random.default_rng(33)
        for p in range(P_):
            ts[p, :40] = 900_000 + p * 1000 + np.arange(40) * 10_000
            vals[p, :40] = np.cumsum(rng.integers(1, 10, 40)).astype(float)
        gids = np.zeros(P_, np.int32)
        steps = np.array([1_400_000, 1_500_000], dtype=np.int32)
        args = (ts, vals, counts, gids, steps, np.int32(900_000))
        np.testing.assert_allclose(
            on_mesh(mesh, "rate", "sum", 1, *args),
            single_device("rate", "sum", 1, *args),
            rtol=1e-9, equal_nan=True)

    def test_empty_groups_nan(self, mesh):
        P, S = 4, 64
        ts, vals, counts = make_series(P, S, seed=5)
        gids = np.zeros(P, np.int32)
        steps = np.array([10], dtype=np.int32)  # before any data
        out = on_mesh(mesh, "rate", "sum", 2, ts, vals, counts, gids, steps,
                      np.int32(5))
        assert np.isnan(out).all()


class TestDistributedRangeAggFamily:
    @pytest.mark.parametrize("fn,agg", [
        ("sum_over_time", "sum"), ("count_over_time", "sum"),
        ("avg_over_time", "avg"), ("min_over_time", "min"),
        ("max_over_time", "max"), ("last_over_time", "sum"),
    ])
    def test_matches_single_device(self, mesh, fn, agg):
        P_, S = 8, 128
        ts, vals, counts = make_series(P_, S, seed=11, resets=False)
        gids = np.arange(P_, dtype=np.int32) % 2
        steps = np.arange(400_000, 1_000_000, 60_000, dtype=np.int32)
        args = (ts, vals, counts, gids, steps, np.int32(300_000))
        np.testing.assert_allclose(
            on_mesh(mesh, fn, agg, 2, *args),
            single_device(fn, agg, 2, *args),
            rtol=1e-9, atol=1e-12, equal_nan=True, err_msg=f"{fn}/{agg}")

    def test_scan_program_refuses_a_split_fn(self, mesh):
        with pytest.raises(ValueError, match="masked-scan"):
            make_distributed_range_agg(mesh, "avg_over_time", 2, "avg")


# ---- window bounds: the count form and the search form ----------------------

FORMS = ("count", "search")
WINDOW = 300_000


def _padded(rows, S):
    ts = np.full((len(rows), S), TS_PAD, np.int32)
    for i, r in enumerate(rows):
        ts[i, :len(r)] = r
    return ts


def _random_rows(S, seed, P=9):
    rng = np.random.default_rng(seed)
    return [np.cumsum(rng.integers(5_000, 15_000, int(rng.integers(1, S))))
            for _ in range(P)]


def _tick(n):  # a sample every 10 s from 10 s on
    return (np.arange(n, dtype=np.int64) + 1) * 10_000


# name -> (ts [P, S] int32 with TS_PAD after each row's samples, steps)
BOUNDS_CASES = {
    "random-lengths": (_padded(_random_rows(64, 1), 64),
                       np.arange(100_000, 700_000, 45_000)),
    "all-padding-row": (_padded([[], _tick(20), []], 32),
                        np.arange(50_000, 400_000, 50_000)),
    "full-row": (_padded([_tick(32), _tick(32) + 3], 32),
                 np.arange(50_000, 400_000, 25_000)),
    "duplicate-timestamps": (
        _padded([np.repeat(_tick(8), 4), np.repeat(_tick(3), 9)], 32),
        np.array([5_000, 10_000, 30_000, 55_000, 80_000, 500_000])),
    "steps-before-first-and-after-last": (
        _padded([_tick(20) + 1_000_000, _tick(5) + 2_000_000], 32),
        np.array([0, 999_999, 1_000_000, 1_500_000, 3_000_000,
                  2**31 - 2])),
    "window-start-negative": (_padded(_random_rows(32, 2), 32),
                              np.array([0, 10_000, 150_000, 299_999,
                                        300_000])),
    "step-equals-a-timestamp": (
        _padded([_tick(30), _tick(17)], 32),
        np.concatenate([_tick(30)[::3], _tick(30)[::3] + WINDOW])),
    "S-not-a-power-of-two": (_padded(_random_rows(37, 3), 37),
                             np.arange(60_000, 400_000, 20_000)),
}


def _np_bounds(ts, steps, window):
    """Row by row, what the device programs must return."""
    lo = np.stack([np.searchsorted(r, steps - window, side="right")
                   for r in ts])
    hi = np.stack([np.searchsorted(r, steps, side="right") for r in ts])
    return lo.astype(np.int32), hi.astype(np.int32)


@pytest.fixture
def force_form(monkeypatch):
    def force(form):
        monkeypatch.setattr(dq, "bounds_form", lambda *_: form)
    return force


class TestWindowBounds:
    @pytest.mark.parametrize("case", list(BOUNDS_CASES))
    @pytest.mark.parametrize("form", FORMS)
    def test_each_form_equals_numpy_searchsorted(self, force_form, form,
                                                 case):
        ts, steps = BOUNDS_CASES[case]
        steps = steps.astype(np.int32)
        force_form(form)
        lo, hi = jax.jit(lambda *a: dq._window_bounds(*a, mesh=None))(
            jnp.asarray(ts), jnp.asarray(steps), jnp.int32(WINDOW))
        want_lo, want_hi = _np_bounds(ts, steps, np.int32(WINDOW))
        assert lo.dtype == hi.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(lo), want_lo)
        np.testing.assert_array_equal(np.asarray(hi), want_hi)

    @pytest.mark.parametrize("shape", [(1, 1), (4, 1), (2, 2)],
                             ids=["1x1", "4x1", "2x2"])
    @pytest.mark.parametrize("form", FORMS)
    def test_mesh_bounds_are_each_time_blocks_own(self, force_form, form,
                                                  shape):
        """Through ``make_mesh_bounds``: every time block searches its own
        [P_l, S_l] slice, ascending with its padding last, and its K
        columns sit at [d·K, (d+1)·K) of the global result."""
        ds, dt = shape
        mesh = Mesh(np.array(jax.devices()[:ds * dt]).reshape(ds, dt),
                    ("shard", "time"))
        S, K = 48, 12
        rows = _random_rows(S, 4, P=5) + [[], _tick(S), np.repeat(_tick(6), 5)]
        ts = _padded(rows, S)
        steps = np.arange(20_000, 20_000 + K * 35_000, 35_000, np.int32)
        force_form(form)
        lo, hi = make_mesh_bounds(mesh)(jnp.asarray(ts), jnp.asarray(steps),
                                        jnp.int32(WINDOW))
        assert lo.shape == hi.shape == (len(rows), dt * K)
        S_l = S // dt
        for d in range(dt):
            want_lo, want_hi = _np_bounds(ts[:, d * S_l:(d + 1) * S_l],
                                          steps, np.int32(WINDOW))
            cols = slice(d * K, (d + 1) * K)
            np.testing.assert_array_equal(np.asarray(lo)[:, cols], want_lo)
            np.testing.assert_array_equal(np.asarray(hi)[:, cols], want_hi)

    def test_the_rule_keeps_the_search_on_the_cpu(self):
        """The tier-1 meshes are CPU meshes, where the gather loop is the
        fast form; that a mesh of the chip's devices counts is asserted
        where such a mesh can be described (``test_chip_compile.py``)."""
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2),
                    ("shard", "time"))
        assert dq.bounds_form(mesh) == "search"

    def test_count_form_never_holds_the_whole_compare(self, force_form):
        """At P 2,048, S 1,024, K 256 the [P, K, S] compare of both edges is
        1.07 G elements (1 GB as predicates, 4 GB as the int32 a sum
        widens them to); the count form's temporaries stay a small multiple
        of ``ts`` (8 MB) even on the CPU's compiler, which fuses nothing
        here: one pass of ``_COUNT_CHUNK`` steps at a time."""
        P_, S, K = 2048, 1024, 256
        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                    ("shard", "time"))

        def sds(shape, spec):
            return jax.ShapeDtypeStruct(
                shape, jnp.int32, sharding=NamedSharding(mesh, spec))

        force_form("count")
        compiled = make_mesh_bounds(mesh).lower(
            sds((P_, S), PartitionSpec("shard", "time")),
            sds((K,), PartitionSpec()), sds((), PartitionSpec())).compile()
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp <= (dq._COUNT_CHUNK + 4) * P_ * S * 4
