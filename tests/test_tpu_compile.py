"""The paper-width Deformable-DETR MSDA plans compile for a TPU v5e.

For one described (not attached) v5e chip, compiles the forward and the
gradient of the encoder (87,296 queries) and decoder (300 queries) plans
the DETR training step commits — full width, bf16, Pallas kernels lowered
by Mosaic (``interpret=False``) — and those of the
``deformable-detr-coco800`` training configuration, BEVFormer-base's
forward plans and its cross-attention's gradient, and the gradient of
the mixed-slab-dtype and regathering kernels at a small pyramid.
Nothing runs: this catches what the chip's compiler would refuse
(tiling, VMEM / SMEM overflow, unsupported ops) and a program that does
not fit the chip's HBM, without a chip.
"""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.core import deformable_transformer as dt
from repro.kernels import plan as plan_mod

V5E_HBM_BYTES = 16 * 2**30
ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-device compile cannot be read back from the
    persistent cache without a chip: keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _v5e_plan(name: str):
    """The committed DETR plan ``name``, for the chip: the spec the model
    builds, with the v5e VMEM budget (the planner sees the CPU here)."""
    cfg = get_config("deformable-detr")
    spec = dt.msda_plans(cfg, dtype=cfg.dtype, train=True)[name].spec
    spec = dataclasses.replace(
        spec, vmem_budget=plan_mod.default_vmem_budget("TPU v5 lite"))
    return plan_mod.msda_plan(spec, backend="pallas", interpret=False)


def _compile(plan, direction, dtype, one_chip):
    """AOT-compile ``plan``'s forward or gradient for the described chip,
    value and attention weights in ``dtype``; it must hold the Pallas
    kernels and fit the chip's HBM."""
    s = plan.spec
    L, P, H, D = s.num_levels, s.num_points, s.num_heads, s.head_dim
    shape = lambda *dims, dt: jax.ShapeDtypeStruct(dims, dt,  # noqa: E731
                                                   sharding=one_chip)
    args = (shape(1, s.total_pixels, H, D, dt=dtype),
            shape(1, s.num_queries, H, L, P, 2, dt=jnp.float32),
            shape(1, s.num_queries, H, L, P, dt=dtype))
    fn = plan
    if direction == "grad":
        fn = jax.grad(lambda v, l, a: jnp.sum(plan(v, l, a).astype(
            jnp.float32)), argnums=(0, 1, 2))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    total = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
             + ma.output_size_in_bytes)
    assert total <= V5E_HBM_BYTES, total


@pytest.mark.parametrize("direction", ["fwd", "grad"])
@pytest.mark.parametrize("name", ["encoder", "decoder"])
def test_detr_plan_compiles_for_v5e(name, direction, one_chip,
                                    no_persistent_cache):
    plan = _v5e_plan(name)
    assert plan.backend == "pallas" and plan.tuning.interpret is False
    _compile(plan, direction, jnp.bfloat16, one_chip)


def _coco800_plan(name: str):
    """The plan ``name`` of the ``deformable-detr-coco800`` training
    cell, for the chip: the spec the model builds for float32 pyramids
    in training, with the v5e VMEM budget."""
    sys.path.insert(0, ROOT)
    from chipbench import catalog

    with open(os.path.join(ROOT, "chipbench", "configs",
                           "deformable-detr-coco800.json")) as f:
        cfg = json.load(f)
    mcfg = catalog.family(cfg).program_config(cfg)
    spec = dt.msda_plans(mcfg, dtype="float32", train=True)[name].spec
    spec = dataclasses.replace(
        spec, vmem_budget=plan_mod.default_vmem_budget("TPU v5 lite"))
    return plan_mod.msda_plan(spec, backend="pallas", interpret=False)


@pytest.mark.parametrize("direction", ["fwd", "grad"])
@pytest.mark.parametrize("name", ["encoder", "decoder"])
def test_coco800_train_plan_compiles_for_v5e(name, direction, one_chip,
                                             no_persistent_cache):
    plan = _coco800_plan(name)
    assert plan.launches_per_call() == {"fwd": 1, "bwd": 1}
    _compile(plan, direction, jnp.float32, one_chip)


def _bevformer_plans():
    """BEVFormer-base's forward plans at the widths of ``bevformer-base``:
    the largest camera's SCA call (9,664 packed queries, 4 levels x 8
    points: the gather's rolled loop), TSA's and the decoder's (one
    200x200 level, 4 points: the unrolled body)."""
    from repro.models import bevformer as bf

    with open(os.path.join(os.path.dirname(__file__), "..", "chipbench",
                           "configs", "bevformer-base.json")) as f:
        cfg = bf.BEVFormerConfig.from_dict(json.load(f), cam_bounds=(9664,))
    return {n: p.spec for n, p in bf.msda_plans(cfg).items()}


@pytest.mark.parametrize("name", ["sca.cam0", "tsa", "decoder"])
def test_bevformer_plan_compiles_for_v5e(name, one_chip, no_persistent_cache):
    spec = dataclasses.replace(
        _bevformer_plans()[name],
        vmem_budget=plan_mod.default_vmem_budget("TPU v5 lite"))
    plan = plan_mod.msda_plan(spec, backend="pallas", interpret=False)
    assert plan.launches_per_call() == {"fwd": 1, "bwd": 0}
    s = plan.spec
    L, P, H, D = s.num_levels, s.num_points, s.num_heads, s.head_dim
    shape = lambda *dims, dt: jax.ShapeDtypeStruct(dims, dt,  # noqa: E731
                                                   sharding=one_chip)
    compiled = jax.jit(plan).lower(
        shape(1, s.total_pixels, H, D, dt=jnp.float32),
        shape(1, s.num_queries, H, L, P, 2, dt=jnp.float32),
        shape(1, s.num_queries, H, L, P, dt=jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_bevformer_sca_grad_compiles_for_v5e(one_chip, no_persistent_cache):
    """The largest camera's cross-attention in training: saved corners
    of 4 levels x 8 points, and the scatter over the packed pyramid."""
    spec = dataclasses.replace(
        _bevformer_plans()["sca.cam0"], train=True,
        vmem_budget=plan_mod.default_vmem_budget("TPU v5 lite"))
    plan = plan_mod.msda_plan(spec, backend="pallas", interpret=False)
    assert plan.launches_per_call() == {"fwd": 1, "bwd": 1}
    _compile(plan, "grad", jnp.float32, one_chip)


# the kernel variants a plan can commit beside the cells' own, at a small
# 3-level pyramid: none is refused on a TPU, so each must compile there
SMALL_LEVELS = ((32, 32), (16, 16), (8, 8))
VARIANTS = {
    "fused-pyramid": {},
    "mixed-fp32-bf16-slabs": dict(
        slab_dtypes=("float32", "bfloat16", "bfloat16")),
    "regather": dict(save_sampled=False),
}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_kernel_variant_compiles_for_v5e(variant, one_chip,
                                         no_persistent_cache):
    from repro.kernels import ops

    L, P, H, D, Q = len(SMALL_LEVELS), 4, 8, 32, 256
    S = sum(h * w for h, w in SMALL_LEVELS)
    params = ops.MSDAParams(**{
        **dict(spatial_shapes=SMALL_LEVELS, block_q=64,
               interpret=False, save_sampled=True,
               vmem_limit=plan_mod.default_vmem_budget("TPU v5 lite")),
        **VARIANTS[variant]})
    op = ops.build_kernel_op(params)
    shape = lambda *dims, dt: jax.ShapeDtypeStruct(dims, dt,  # noqa: E731
                                                   sharding=one_chip)
    args = (shape(1, S, H, D, dt=jnp.bfloat16),
            shape(1, Q, H, L, P, 2, dt=jnp.float32),
            shape(1, Q, H, L, P, dt=jnp.bfloat16))
    grad = jax.grad(lambda v, l, a: jnp.sum(op(v, l, a).astype(jnp.float32)),
                    argnums=(0, 1, 2))
    compiled = jax.jit(grad).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
