"""``chip_smoke.py``'s phases at a tiny size on the CPU.

The driver runs the script itself on a TPU; here every phase function is
called directly (never through ``main()``, which refuses the CPU) so that a
broken path, argument or check is found without chip time. The per-series
shape stays the real one (720 samples at 10 s); only series counts shrink.
"""

import json
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
from filodb_tpu import startup  # noqa: E402

TINY = chip_smoke.Size(counter_series=96, gauge_series=24, apps=8)
SEED = 7


@pytest.fixture(scope="module")
def watch():
    return chip_smoke.CompileWatch()


@pytest.fixture(scope="module")
def store():
    return chip_smoke.phase_load(TINY, SEED)


@pytest.fixture(scope="module")
def service(store):
    ms, num_shards, spread, _, _ = store
    return chip_smoke.default_service(ms, num_shards, spread)


def test_front_door_reads_back_every_line():
    out = chip_smoke.phase_front_door(n_series=6, n_samples=12)
    assert out["lines"] == 72 and out["read_back"] == "all"
    assert out["native_shards"]


def test_device_page_kernels_decode_exactly():
    out = chip_smoke.phase_device_pages(n_values=300, interpret=True)
    assert out["blocks"] == 3 and out["ts_exact"] and out["f32_exact"]


def test_load_goes_through_the_native_lane(store):
    report = store[4]
    assert report["series"] == 120 and report["samples"] == 120 * 720
    assert report["have_native"] and report["native_shards"]
    assert report["cut_from_real_size"]
    assert sum(report["series_per_shard"]) == 120
    # spread 1 and eight namespaces: no shard of the default four is empty
    assert all(report["series_per_shard"])


def test_service_is_the_default_configuration(service):
    from filodb_tpu.config import DEFAULTS
    from filodb_tpu.parallel.mesh_engine import MeshQueryEngine

    assert DEFAULTS["datasets"]["timeseries"]["engine"] == "mesh"
    assert isinstance(service.mesh_engine, MeshQueryEngine)
    assert service.result_cache is not None


@pytest.mark.parametrize("name", [q[0] for q in chip_smoke.mesh_queries()])
def test_mesh_query_agrees_with_plain_reference(name, store, service, watch,
                                                capsys):
    query = [q for q in chip_smoke.mesh_queries() if q[0] == name]
    answers = chip_smoke.phase_queries(service, store[3], TINY, query, watch,
                                       on_mesh=True)
    keys, vals = answers[name]
    assert vals.shape[1] == 120 and len(keys) == vals.shape[0]
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "query" and line["mesh_hits"] > 0
    assert not any(line["mesh_fallbacks"].values())
    # the repeat is answered by the result cache and builds nothing
    assert line["again_result_cache_hits"] > 0
    assert line["again_build"]["programs_built"] == 0
    want = {"split_sum_rate": "split", "post_topk": "split",
            "fused_max_max": "fused"}[name]
    assert line["mesh_dispatch"][want] > 0


def test_exec_tree_query_agrees_with_plain_reference(store, service, watch,
                                                     capsys):
    chip_smoke.phase_queries(service, store[3], TINY,
                             chip_smoke.exec_tree_queries(TINY), watch,
                             on_mesh=False)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["mesh_hits"] == 0 and line["series_evaluated"] == 24


def test_a_wrong_answer_fails_the_phase(store, service, watch):
    data = dict(store[3])
    m = data[chip_smoke.COUNTER]
    data[chip_smoke.COUNTER] = chip_smoke.Metric(
        m.name, m.schema, m.keys, m.app, m.ts, m.vals * 1.001)
    with pytest.raises(AssertionError):
        chip_smoke.phase_queries(service, data, TINY,
                                 chip_smoke.mesh_queries()[:1], watch,
                                 on_mesh=True)


def test_proof_names_where_the_batch_lives(store, watch):
    ms, num_shards, spread, data, _ = store
    svc = chip_smoke.default_service(ms, num_shards, spread)
    with pytest.raises(AssertionError):
        chip_smoke.phase_proof(svc, "cpu")  # nothing placed yet
    chip_smoke.phase_queries(svc, data, TINY, chip_smoke.mesh_queries()[:1],
                             watch, on_mesh=True)
    out = chip_smoke.phase_proof(svc, "cpu")
    assert out["batch_platforms"] == ["cpu"] and out["placed_batch_bytes"] > 0
    with pytest.raises(AssertionError):
        chip_smoke.phase_proof(svc, "tpu")


@pytest.mark.parametrize("shape", [(4, 1), (2, 2)])
def test_sharded_mesh_agrees_and_places_a_quarter_per_device(shape, store,
                                                             watch):
    from jax.sharding import Mesh

    ms, num_shards, spread, data, _ = store

    def answers(devices, mesh_shape):
        mesh = Mesh(np.array(devices).reshape(mesh_shape), ("shard", "time"))
        svc = chip_smoke.default_service(ms, num_shards, spread, mesh=mesh)
        return svc, chip_smoke.phase_queries(
            svc, data, TINY, chip_smoke.mesh_queries(), watch, on_mesh=True)

    _, base = answers(jax.devices()[:1], (1, 1))
    svc, got = answers(jax.devices()[:4], shape)
    chip_smoke._same_answers(got, base, f"{shape} vs 1x1")
    out = chip_smoke.phase_placement(svc)
    assert out["group_reduce_all_reduce"]
    assert out["window_eval_all_gather"] or shape[1] == 1
    sizes = out["bytes_per_device_of_largest"]
    assert len(sizes) == 4 and len(set(sizes)) == 1


def test_reference_rate_on_a_counter_with_known_slope():
    # +10 every 10 s: rate 1/s in every window that extrapolates to its edges
    ts = (chip_smoke.T0_SEC * 1000 + 3_000
          + np.arange(60, dtype=np.int64)[None, :] * 10_000)
    vals = 1000.0 + 10.0 * np.arange(60, dtype=np.float64)[None, :]
    steps = (chip_smoke.T0_SEC + np.array([120, 300, 590])) * 1000
    np.testing.assert_allclose(
        chip_smoke.ref_rate(ts, vals, steps, 60_000), 1.0, rtol=1e-12)
    # a restart inside the window: what the counter lost is added back
    vals2 = vals.copy()
    vals2[0, 20:] -= vals2[0, 19]
    np.testing.assert_allclose(
        chip_smoke.ref_rate(ts, vals2, steps[1:2], 300_000), 1.0, rtol=1e-12)
    # a counter that starts at zero is not extrapolated below zero: the
    # 3 s before the first sample are left out, 297 of 300 s remain
    vals3 = 10.0 * np.arange(60, dtype=np.float64)[None, :]
    np.testing.assert_allclose(
        chip_smoke.ref_rate(ts, vals3, steps[1:2], 300_000), 0.99,
        rtol=1e-12)
    # one sample is not a rate
    assert np.isnan(chip_smoke.ref_rate(ts[:, :1], vals[:, :1],
                                        steps[:1], 60_000)).all()


def test_main_refuses_a_platform_that_is_not_a_tpu(capsys):
    assert chip_smoke.main([]) == 1
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["ok"] is False and last["device"]["platform"] == "cpu"


def test_main_cannot_exit_zero_after_a_failed_phase(monkeypatch, capsys,
                                                    tmp_path):
    monkeypatch.setattr(startup, "device_info", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    monkeypatch.setattr(startup, "configure_jax", lambda: str(tmp_path))

    def boom(*a, **k):
        raise RuntimeError("phase failed")

    monkeypatch.setattr(chip_smoke, "run_one_chip", boom)
    assert chip_smoke.main([]) == 1
    captured = capsys.readouterr()
    last = json.loads(captured.out.strip().splitlines()[-1])
    assert last == {"ok": False, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert "phase failed" in captured.err


def test_four_chip_option_needs_four_chips(monkeypatch, capsys):
    monkeypatch.setattr(startup, "device_info", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    assert chip_smoke.main(["--chips", "4"]) == 1
    assert '"ok": false' in capsys.readouterr().out


@pytest.fixture
def jax_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_compile_cache_goes_where_the_environment_says(monkeypatch, tmp_path,
                                                       jax_cache_config):
    # JAX reads the variable itself at import; here the code must not
    # override it with a directory of its own
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert startup.configure_jax() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    assert jax.config.jax_persistent_cache_min_compile_time_secs < 1.0


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(
        monkeypatch, jax_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert startup.configure_jax() == os.path.join(repo, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == \
        os.path.join(repo, ".jax_cache")
    assert startup.configure_jax() == startup.DEFAULT_CACHE_DIR
