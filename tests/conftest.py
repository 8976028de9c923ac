"""Test harness config.

Distributed tests run on a virtual 8-device CPU mesh — the JAX idiom for a
fake cluster (SURVEY §4: the analog of the reference's localhost multi-process
NCCL tests is `xla_force_host_platform_device_count`)."""

import os

# Must be set before jax import.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# The suite is chipless: force the CPU platform whatever the environment
# names, so a test run never takes (or waits for) an attached chip.
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed_everything():
    import paddle_tpu as paddle
    paddle.seed(1234)
    np.random.seed(1234)
    yield


@pytest.fixture(autouse=True)
def _reset_mesh():
    """No test hands the next one on its worker a hybrid mesh: a file that
    sets one (``set_hybrid_mesh``, ``fleet.init``, or a collective's
    implicit world mesh) would otherwise decide how later files compile."""
    yield
    from paddle_tpu.distributed.topology import set_hybrid_mesh
    set_hybrid_mesh(None)


@pytest.fixture
def mesh8():
    """Fresh 8-device mesh helper; tests parametrize axis shapes."""
    assert jax.device_count() == 8, \
        f"expected 8 virtual devices, got {jax.device_count()}"
    return jax.devices()
