"""Distributed (shard × time mesh) query tests on the virtual 8-device CPU
mesh: the sharded sum(rate()) must match the single-device kernel exactly.

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

from filodb_tpu.parallel.dist_query import make_distributed_sum_rate
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


class TestDistributedSumRate:
    def test_matches_single_device(self, mesh):
        P, S = 12, 200
        ts, vals, counts = make_series(P, S)
        gids = np.arange(P, dtype=np.int32) % 3
        steps = np.arange(600_000, 1_500_000, 60_000, dtype=np.int32)
        window = np.int32(300_000)

        # single-device reference
        rate = np.asarray(kernels.range_eval(
            "rate", jnp.asarray(ts), jnp.asarray(vals), jnp.asarray(counts),
            jnp.asarray(steps), jnp.asarray(window)))
        expect = np.asarray(aggregate("sum", jnp.asarray(rate),
                                      jnp.asarray(gids), 3))

        # distributed
        ts_p, vals_p, valid, gid_p = pad_for_mesh(ts, vals, counts, gids, mesh)
        fn = make_distributed_sum_rate(mesh, 3)
        out = np.asarray(fn(jnp.asarray(ts_p), jnp.asarray(vals_p),
                            jnp.asarray(valid), jnp.asarray(gid_p),
                            jnp.asarray(steps), jnp.asarray(window)))
        np.testing.assert_allclose(out, expect, rtol=1e-9, atol=1e-12,
                                   equal_nan=True)

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
        window = np.int32(600_000)

        rate = np.asarray(kernels.range_eval(
            "rate", jnp.asarray(ts), jnp.asarray(vals), jnp.asarray(counts),
            jnp.asarray(steps), jnp.asarray(window)))
        expect = np.asarray(aggregate("sum", jnp.asarray(rate),
                                      jnp.asarray(gids), 1))
        ts_p, vals_p, valid, gid_p = pad_for_mesh(ts, vals, counts, gids, mesh)
        fn = make_distributed_sum_rate(mesh, 1)
        out = np.asarray(fn(jnp.asarray(ts_p), jnp.asarray(vals_p),
                            jnp.asarray(valid), jnp.asarray(gid_p),
                            jnp.asarray(steps), jnp.asarray(window)))
        np.testing.assert_allclose(out, expect, rtol=1e-9, equal_nan=True)

    def test_empty_groups_nan(self, mesh):
        P, S = 4, 64
        ts, vals, counts = make_series(P, S, seed=5)
        gids = np.zeros(P, np.int32)
        steps = np.array([10], dtype=np.int32)  # before any data
        window = np.int32(5)
        ts_p, vals_p, valid, gid_p = pad_for_mesh(ts, vals, counts, gids, mesh)
        fn = make_distributed_sum_rate(mesh, 2)
        out = np.asarray(fn(jnp.asarray(ts_p), jnp.asarray(vals_p),
                            jnp.asarray(valid), jnp.asarray(gid_p),
                            jnp.asarray(steps), jnp.asarray(window)))
        assert np.isnan(out).all()


class TestDistributedRangeAggFamily:
    @pytest.mark.parametrize("fn,agg", [
        ("sum_over_time", "sum"), ("count_over_time", "sum"),
        ("avg_over_time", "avg"), ("min_over_time", "min"),
        ("max_over_time", "max"), ("last_over_time", "sum"),
    ])
    def test_matches_single_device(self, mesh, fn, agg):
        from filodb_tpu.parallel.dist_query import make_distributed_range_agg

        P_, S = 8, 128
        ts, vals, counts = make_series(P_, S, seed=11, resets=False)
        gids = np.arange(P_, dtype=np.int32) % 2
        steps = np.arange(400_000, 1_000_000, 60_000, dtype=np.int32)
        window = np.int32(300_000)
        per_series = np.asarray(kernels.range_eval(
            fn, jnp.asarray(ts), jnp.asarray(vals), jnp.asarray(counts),
            jnp.asarray(steps), jnp.asarray(window)))
        expect = np.asarray(aggregate(agg, jnp.asarray(per_series),
                                      jnp.asarray(gids), 2))
        ts_p, vals_p, valid, gid_p = pad_for_mesh(ts, vals, counts, gids,
                                                  mesh)
        f = make_distributed_range_agg(mesh, fn, 2, agg)
        out = np.asarray(f(jnp.asarray(ts_p), jnp.asarray(vals_p),
                           jnp.asarray(valid), jnp.asarray(gid_p),
                           jnp.asarray(steps), jnp.asarray(window)))
        np.testing.assert_allclose(out, expect, rtol=1e-9, atol=1e-12,
                                   equal_nan=True, err_msg=f"{fn}/{agg}")


class TestRingVariant:
    def test_ring_matches_gather(self, mesh):
        from filodb_tpu.parallel.dist_query import (
            make_distributed_sum_rate_ring,
        )

        P_, S = 12, 200
        ts, vals, counts = make_series(P_, S, seed=21)
        gids = np.arange(P_, dtype=np.int32) % 3
        steps = np.arange(600_000, 1_500_000, 60_000, dtype=np.int32)
        window = np.int32(300_000)
        ts_p, vals_p, valid, gid_p = pad_for_mesh(ts, vals, counts, gids,
                                                  mesh)
        gather_fn = make_distributed_sum_rate(mesh, 3)
        ring_fn = make_distributed_sum_rate_ring(mesh, 3)
        a = np.asarray(gather_fn(jnp.asarray(ts_p), jnp.asarray(vals_p),
                                 jnp.asarray(valid), jnp.asarray(gid_p),
                                 jnp.asarray(steps), jnp.asarray(window)))
        b = np.asarray(ring_fn(jnp.asarray(ts_p), jnp.asarray(vals_p),
                               jnp.asarray(valid), jnp.asarray(gid_p),
                               jnp.asarray(steps), jnp.asarray(window)))
        np.testing.assert_allclose(b, a, rtol=1e-9, atol=1e-12,
                                   equal_nan=True)

    def test_ring_extrapolation_sensitive(self, mesh):
        """First sample arrives late (time-block 0 empty for some series):
        extrapolation depends on the true global t_first — a zero-polluted
        ring combine would diverge here."""
        from filodb_tpu.parallel.dist_query import (
            make_distributed_sum_rate_ring,
        )

        P_, S = 8, 128
        ts = np.full((P_, S), TS_PAD, np.int32)
        vals = np.zeros((P_, S), np.float64)
        counts = np.zeros(P_, np.int32)
        rng = np.random.default_rng(33)
        for p in range(P_):
            n = 40  # few samples, all landing in the SECOND time block
            t0 = 900_000 + p * 1000
            ts[p, :n] = t0 + np.arange(n) * 10_000
            vals[p, :n] = np.cumsum(rng.integers(1, 10, n)).astype(float)
            counts[p] = n
        gids = np.zeros(P_, np.int32)
        steps = np.array([1_400_000, 1_500_000], dtype=np.int32)
        window = np.int32(900_000)  # window start long before first sample
        rate = np.asarray(kernels.range_eval(
            "rate", jnp.asarray(ts), jnp.asarray(vals), jnp.asarray(counts),
            jnp.asarray(steps), jnp.asarray(window)))
        expect = np.asarray(aggregate("sum", jnp.asarray(rate),
                                      jnp.asarray(gids), 1))
        ts_p, vals_p, valid, gid_p = pad_for_mesh(ts, vals, counts, gids,
                                                  mesh)
        ring_fn = make_distributed_sum_rate_ring(mesh, 1)
        out = np.asarray(ring_fn(jnp.asarray(ts_p), jnp.asarray(vals_p),
                                 jnp.asarray(valid), jnp.asarray(gid_p),
                                 jnp.asarray(steps), jnp.asarray(window)))
        np.testing.assert_allclose(out, expect, rtol=1e-9, equal_nan=True)
