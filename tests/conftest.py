"""Test harness config: an 8-device virtual CPU mesh.

The tests run on the CPU backend (the tier-1 command sets
``JAX_PLATFORMS=cpu``; the config update below says the same for a bare
``pytest``), split into 8 virtual devices so that mesh and sharding code
runs. Matmul precision is pinned to 'highest' because exact-value tests
cannot live with the default low-precision (bf16-pass) matmuls.
"""
import os

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle

    paddle.seed(1234)
    np.random.seed(1234)
    yield


@pytest.fixture
def mesh8():
    """2x2x2 dp/sdp/mp mesh over the 8 virtual CPU devices."""
    from paddle_tpu.distributed.topology import HybridCommunicateGroup

    return HybridCommunicateGroup(dp_degree=2, mp_degree=2, pp_degree=1, sharding_degree=2).mesh
