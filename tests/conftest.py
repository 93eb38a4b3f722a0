import os

# Tests run on CPU (the 512-device override is strictly dryrun.py's, per
# the assignment).  The host platform is split into 4 virtual devices so
# the distribution tests can build real 2x2 / 1x4 / 4x1 meshes and run
# shard_map + ppermute collectives for the 2D (dp x tp) sharding mode;
# everything else still executes on device 0 as before.
#
# XLA:CPU is held to AVX so that it never contracts a multiply and an add
# into a fused multiply-add: v5e rounds every product on its own (its
# bundles hold separate vmul and vadd ops), and the kernels' tests hold
# the interpreter to that bit for bit, whatever the shape of the walk.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags += " --xla_force_host_platform_device_count=4"
if "xla_cpu_max_isa" not in _flags:
    _flags += " --xla_cpu_max_isa=AVX"
os.environ["XLA_FLAGS"] = _flags.strip()

import jax
import numpy as np
import pytest

jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def assert_trees_close(a, b, atol=1e-5, rtol=1e-5):
    import jax

    for (ka, la), (kb, lb) in zip(
        jax.tree_util.tree_flatten_with_path(a)[0],
        jax.tree_util.tree_flatten_with_path(b)[0],
    ):
        np.testing.assert_allclose(
            np.asarray(la), np.asarray(lb), atol=atol, rtol=rtol,
            err_msg=f"mismatch at {ka}",
        )
