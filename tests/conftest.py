"""Test harness config.

Tests run on the CPU, on a virtual 8-device mesh so multi-chip sharding
logic is exercised without TPU hardware, and in float64: Prometheus
semantics are defined on float64 and tests verify parity at full precision.
(The dtype a server computes in, f32/int32, is covered by
``test_f32_mode.py``; the chip's compiler by ``test_chip_compile.py``.)
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_ENABLE_X64"] = "1"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _cold_cost_models():
    """Every test starts with a cold cost model: learned-routing state is
    process-global (query/cost_model.py), and a model warmed by one test
    must never flip a decision site's arm in another — static behavior is
    the contract while cold. Tests that exercise warm routing seed their
    own observations after this reset."""
    from filodb_tpu.query import cost_model
    cost_model.reset_models()
    yield
    cost_model.reset_models()
