"""The chip's compiler, asked about every device program of the served path.

The TPU compiler is installed here and compiles for a chip that is described
and not attached (``v5e:2x2``). Interpret mode and the CPU backend accept
programs it refuses — block shapes off the (8, 128) tiling, scalar stores to
VMEM, more memory than a chip has — so each program ``chip_smoke.py`` runs is
compiled here at the smoke's real bucket sizes, in the dtypes a server
computes in (f32/int32, x64 off), for one chip and for the 2×2 and 4×1 meshes
a four-chip host can build. Nothing runs: a pass says the program compiles
and fits, not that it is right or fast.

Rules this file keeps (on-chip-measurement guide, section 2): the topology is
described inside a fixture, never at import and never in ``conftest.py``; the
fixture is not ``autouse`` and skips where no topology can be described; all
of it is one file, compiled in the test's own process (only one process may
load the TPU library, and it keeps it until it exits).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# chip_smoke.REAL: 100,000 counter and 10,000 gauge series, padded to the
# engine's power-of-two buckets. A range query reaches the engine as
# result-cache extents of 32 steps of 60 s, and a batch holds an extent plus
# its window: 222 samples of a counter under rate[5m] (bucket 256), up to 552
# of a gauge under max_over_time[1h] (bucket 1024). 100 apps → 128 groups.
P_COUNTER, S_COUNTER, P_GAUGE, S_GAUGE, K, G = 131072, 256, 16384, 1024, 32, 128
# the exec-tree join of the smoke: one app's 1,000 series over the two shards
# of its shard-key group
P_LEAF = 512
HBM_BYTES = 16 * 10**9  # one v5e chip
# what the one-chip smoke keeps on the device beside a running program: eight
# counter batches and four gauge batches (ts i32 + vals f32 + valid bool) and
# the prepare cache's four corrected-value tensors
RESIDENT_BYTES = (8 * P_COUNTER * S_COUNTER + 4 * P_GAUGE * S_GAUGE) * 9 \
    + 4 * P_COUNTER * S_COUNTER * 4

MESH_SHAPES = {"1x1": (1, 1), "2x2": (2, 2), "4x1": (4, 1)}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever says "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def f32():
    """The suite's conftest turns x64 on; a server runs with it off. Such a
    compile is written to the persistent cache but cannot be read back
    without a chip, so the cache is off around it."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_x64", False)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()
    jax.config.update("jax_enable_x64", True)


def _mesh(topo, shape_name):
    ds, dt = MESH_SHAPES[shape_name]
    return Mesh(np.array(topo.devices[: ds * dt]).reshape(ds, dt),
                ("shard", "time"))


def _check(compiled, n_devices, collectives=()):
    ma = compiled.memory_analysis()
    need = ma.output_size_in_bytes + ma.temp_size_in_bytes \
        + RESIDENT_BYTES // n_devices
    assert need < HBM_BYTES, (need, ma)
    text = compiled.as_text()
    for c in collectives:
        assert c in text, f"no {c} in the compiled program"
    return text


def _mesh_programs(mesh, p, s):
    """name → (jitted program, args, kwargs, collectives expected on this
    mesh), with the shardings ``MeshQueryEngine`` gives each operand."""
    from filodb_tpu.parallel import dist_query as dq

    ds, dt = mesh.shape["shard"], mesh.shape["time"]

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    st = P("shard", "time")
    ts = sds((p, s), jnp.int32, st)
    vals = sds((p, s), jnp.float32, st)
    valid = sds((p, s), jnp.bool_, st)
    gid = sds((p,), jnp.int32, P("shard"))
    steps = sds((K,), jnp.int32, P())
    win = sds((), jnp.int32, P())
    bound = sds((p, dt * K), jnp.int32, st)
    prefix = sds((p, s + dt), jnp.float32, st)
    series = sds((p, K), jnp.float32, P("shard", None))
    gather = ("all-gather",) if dt > 1 else ()
    reduce_ = ("all-reduce",) if ds > 1 else ()
    return {
        "prepare_counter": (dq.make_mesh_prepare(mesh, "counter"),
                            (vals, valid), {}, ()),
        "prepare_prefix": (dq.make_mesh_prepare(mesh, "prefix"),
                           (vals, valid), {}, ()),
        "bounds": (dq.make_mesh_bounds(mesh), (ts, steps, win), {}, ()),
        "eval_delta_rate": (
            dq.make_mesh_eval_delta(mesh, "rate", counter=True),
            (ts, vals, valid, bound, bound, steps, win), {"cv": vals},
            gather),
        "eval_simple_sum": (
            dq.make_mesh_eval_simple(mesh, "sum_over_time"),
            (ts, vals, valid, prefix, prefix, prefix, bound, bound, steps,
             win), {}, gather),
        "group_reduce_sum": (dq.make_mesh_group_reduce(mesh, G, "sum"),
                             (series, gid), {}, reduce_),
        "group_reduce_max": (dq.make_mesh_group_reduce(mesh, G, "max"),
                             (series, gid), {}, reduce_),
        "fused_max_over_time": (
            dq.make_distributed_range_agg(mesh, "max_over_time", G, "max"),
            (ts, vals, valid, gid, steps, win), {}, gather + reduce_),
    }


SPLIT_PROGRAMS = ("prepare_counter", "prepare_prefix", "bounds",
                  "eval_delta_rate", "eval_simple_sum", "group_reduce_sum",
                  "group_reduce_max")


@pytest.mark.parametrize("shape", list(MESH_SHAPES))
@pytest.mark.parametrize("program", SPLIT_PROGRAMS)
def test_split_pipeline_program_compiles(topo, f32, program, shape):
    mesh = _mesh(topo, shape)
    fn, args, kwargs, collectives = _mesh_programs(mesh, P_COUNTER, S_COUNTER)[program]
    _check(fn.lower(*args, **kwargs).compile(), mesh.devices.size,
           collectives)


@pytest.mark.parametrize("shape", list(MESH_SHAPES))
def test_fused_max_over_time_compiles(topo, f32, shape):
    mesh = _mesh(topo, shape)
    fn, args, kwargs, collectives = _mesh_programs(
        mesh, P_GAUGE, S_GAUGE)["fused_max_over_time"]
    _check(fn.lower(*args, **kwargs).compile(), mesh.devices.size,
           collectives)


@pytest.mark.parametrize("shape", list(MESH_SHAPES))
def test_bounds_count_on_the_chip_without_the_compare(topo, f32, shape):
    """On a mesh of the chip's devices the bounds program is the count form,
    and the chip's compiler fuses its compare into the row reduce: at the
    gauge bucket with eight grids in a chunk (K 256) the temporaries in HBM
    stay under ``ts`` itself, where the [P, K, S] compare of both edges would
    be 512 of it, and no ``while`` of dependent gathers is left."""
    from filodb_tpu.parallel import dist_query as dq

    mesh = _mesh(topo, shape)
    k = 8 * K
    assert dq.bounds_form(mesh) == "count"

    def sds(shp, spec):
        return jax.ShapeDtypeStruct(shp, jnp.int32,
                                    sharding=NamedSharding(mesh, spec))

    compiled = dq.make_mesh_bounds(mesh).lower(
        sds((P_GAUGE, S_GAUGE), P("shard", "time")), sds((k,), P()),
        sds((), P())).compile()
    text = _check(compiled, mesh.devices.size)
    assert "gather(" not in text
    ts_bytes = P_GAUGE * S_GAUGE * 4 // mesh.devices.size
    assert compiled.memory_analysis().temp_size_in_bytes < ts_bytes


def _one_chip_sds(topo):
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one)


def test_exec_tree_leaf_compiles(topo, f32):
    """``kernels.range_eval("rate")`` + ``aggregate("sum")``: the program of
    every plan the mesh does not lower, and of ``__graft_entry__.entry()``."""
    from filodb_tpu.query.engine import kernels
    from filodb_tpu.query.engine.aggregations import aggregate

    sds = _one_chip_sds(topo)

    def leaf(ts, vals, counts, gids, steps, window):
        rate = kernels.range_eval("rate", ts, vals, counts, steps, window,
                                  counter=True)
        return aggregate("sum", rate, gids, G)

    _check(jax.jit(leaf).lower(
        sds((P_LEAF, S_COUNTER), jnp.int32),
        sds((P_LEAF, S_COUNTER), jnp.float32),
        sds((P_LEAF,), jnp.int32), sds((P_LEAF,), jnp.int32),
        sds((K,), jnp.int32), sds((), jnp.int32)).compile(), 1)


def test_device_page_assemble_compiles(topo, f32):
    """``device_batch._assemble`` + the mask-aware rate: the device-page
    leaf (``StoreConfig.device_pages``, off by default) at ``bench.py``'s
    microbench shape."""
    from filodb_tpu.memory.device_pages import BLOCK, WORDS_PER_BLOCK_MAX
    from filodb_tpu.query.engine.device_batch import _assemble
    from filodb_tpu.query.engine.kernels import range_eval_masked

    sds = _one_chip_sds(topo)
    p, nb = 512, 32
    i32 = sds((p, nb), jnp.int32)
    words = sds((p, nb, WORDS_PER_BLOCK_MAX), jnp.uint32)
    packed = (i32, i32, i32, words, sds((p, nb), jnp.uint32), i32, i32,
              words, i32)

    def leaf(arrs, span, steps, window):
        ts, vals, valid = _assemble(*arrs, span)
        assert ts.shape == (p, nb * BLOCK)
        return range_eval_masked("rate", ts, vals, valid, steps, window,
                                 counter=True)

    _check(jax.jit(leaf).lower(packed, sds((), jnp.int32),
                               sds((K,), jnp.int32),
                               sds((), jnp.int32)).compile(), 1)


@pytest.mark.parametrize("kernel", ["decode_ts_page_pallas",
                                    "decode_f32_page_pallas"])
def test_pallas_decode_kernel_compiles(topo, f32, kernel):
    from filodb_tpu.memory import device_pages as dp

    sds = _one_chip_sds(topo)
    nb = 4096
    scalars = sds((nb,), jnp.int32)
    words = sds((nb, dp.WORDS_PER_BLOCK_MAX), jnp.uint32)
    if kernel == "decode_ts_page_pallas":
        lowered = jax.jit(dp.decode_ts_page_pallas).lower(
            scalars, scalars, words)
    else:
        lowered = jax.jit(dp.decode_f32_page_pallas).lower(
            sds((nb,), jnp.uint32), scalars, scalars, words)
    text = _check(lowered.compile(), 1)
    assert "tpu_custom_call" in text, "the Pallas kernel is not in there"
