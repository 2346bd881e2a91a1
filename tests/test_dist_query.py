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
from jax.sharding import Mesh
from mesh_oracle import pad_for_mesh

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
