"""Plan-layer contracts of the one launch per direction.

What the planner promises, independent of kernel numerics:

* ``launches_per_call()`` reports the static Pallas schedule by
  direction — one launch over the packed pyramid, with ``bwd`` zeroed
  on inference plans and no launch for the non-Pallas backends;
* ``describe()`` carries the launch schedule so an operator reads the
  launch bill from the plan dump alone;
* each ``plan(...)`` call feeds the ``msda.launches`` observability
  gauge by exactly its schedule (``execution_telemetry()``);
* every MSDA call of every benchmark configuration plans for a v5e
  without a race, with one launch per direction and a block the SMEM
  table chunks and the VMEM budget hold;
* a spec whose packed pyramid does not fit its budget is refused at
  plan time.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.kernels import plan as plan_mod
from repro.kernels.plan import MsdaSpec, msda_plan

SHAPES = ((14, 14), (10, 10), (7, 7))
ROOT = os.path.join(os.path.dirname(__file__), "..")
V5E = "TPU v5 lite"


@pytest.fixture(autouse=True)
def _isolated(tmp_path, monkeypatch):
    """Fresh plan cache + private autotune winner cache per test."""
    monkeypatch.setenv("REPRO_MSDA_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    plan_mod.clear_plans()
    plan_mod.reset_autotune_stats()
    yield
    plan_mod.clear_plans()


def _spec(*, budget=0, train=True):
    return MsdaSpec(
        spatial_shapes=SHAPES, num_heads=2, head_dim=8, num_points=2,
        num_queries=32, train=train, vmem_budget=budget)


def _io(spec):
    S = spec.total_pixels
    L = spec.num_levels
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    value = jax.random.normal(k1, (1, S, 2, 8), jnp.float32)
    loc = jax.random.uniform(k2, (1, 32, 2, L, 2, 2))
    attn = jax.nn.softmax(
        jax.random.normal(k3, (1, 32, 2, L * 2)), axis=-1
    ).reshape(1, 32, 2, L, 2)
    return value, loc, attn


# --------------------------------------------------------------------------
# launches_per_call(): the static schedule, by direction
# --------------------------------------------------------------------------


def test_inference_plans_carry_no_backward_launches():
    plan = msda_plan(_spec(train=False), backend="pallas")
    assert plan.launches_per_call() == {"fwd": 1, "bwd": 0}


def test_non_pallas_plans_report_zero_launches():
    plan = msda_plan(_spec(), backend="cpu")
    assert plan.launches_per_call() == {"fwd": 0, "bwd": 0}


# --------------------------------------------------------------------------
# describe(): the launch bill
# --------------------------------------------------------------------------


@pytest.mark.parametrize("train,line", [
    (True, "launches/call: fwd=1 bwd=1"),
    (False, "launches/call: fwd=1 bwd=0"),
], ids=["on", "infer"])
def test_describe_carries_the_launch_bill(train, line):
    assert line in msda_plan(_spec(train=train), backend="pallas").describe()


# --------------------------------------------------------------------------
# observability: every plan call feeds the launch gauge by its schedule
# --------------------------------------------------------------------------


def test_launch_gauge_advances_by_the_schedule():
    spec = _spec()
    plan = msda_plan(spec, backend="pallas")
    value, loc, attn = _io(spec)
    before = plan_mod.execution_telemetry()["launches"]
    plan(value, loc, attn)
    plan(value, loc, attn)
    after = plan_mod.execution_telemetry()["launches"]
    assert after["fwd"] - before["fwd"] == 2  # 2 calls x 1 launch
    assert after["bwd"] - before["bwd"] == 2
    assert after["plan_calls"] - before["plan_calls"] == 2


# --------------------------------------------------------------------------
# every MSDA call of every benchmark configuration plans for a v5e
# --------------------------------------------------------------------------

CALLS = ([("deformable-detr", n) for n in ("encoder", "decoder")]
         + [("deformable-detr-coco800", n) for n in ("encoder", "decoder")]
         + [("bevformer-base", f"sca.cam{c}") for c in range(6)]
         + [("bevformer-base", n) for n in ("tsa", "decoder")])


@pytest.fixture(scope="module")
def config_specs():
    """{(configuration, call): spec} as the program's plan entry points
    build them for each configuration file of the benchmark (read, not
    edited), in inference mode."""
    import sys

    sys.path.insert(0, ROOT)
    from chipbench import catalog
    from repro.core import deformable_transformer as dt
    from repro.models import bevformer as bf

    out = {}
    for name in sorted({c for c, _ in CALLS}):
        with open(os.path.join(ROOT, "chipbench", "configs",
                               f"{name}.json")) as f:
            cfg = json.load(f)
        mcfg = catalog.family(cfg).program_config(cfg)
        plans = (bf.msda_plans(mcfg) if cfg["family"] == "bevformer"
                 else dt.msda_plans(mcfg))
        out.update({(name, n): p.spec for n, p in plans.items()})
    plan_mod.clear_plans()
    return out


@pytest.mark.parametrize("train", [False, True], ids=["infer", "train"])
@pytest.mark.parametrize("config,call", CALLS,
                         ids=[f"{c}.{n}" for c, n in CALLS])
def test_every_config_call_plans_one_launch_for_v5e(config_specs, config,
                                                    call, train):
    spec = dataclasses.replace(
        config_specs[(config, call)], train=train,
        vmem_budget=plan_mod.default_vmem_budget(V5E))
    plan = msda_plan(spec, backend="pallas", tune="heuristic")
    assert plan.tuning.source == "heuristic"
    assert plan_mod.autotune_stats()["raced"] == 0
    assert plan.launches_per_call() == {"fwd": 1, "bwd": int(train)}
    L = spec.num_levels
    bq = plan.block_q[0]
    assert plan.block_q == (bq,) * L
    assert bq <= ops.smem_block_cap(spec.num_points, levels=L,
                                    heads=spec.heads_per_launch, train=train)
    assert all(r["vmem_frac"] <= 1.0 for r in plan.level_report())


# --------------------------------------------------------------------------
# a pyramid the budget cannot hold is refused at plan time
# --------------------------------------------------------------------------


@pytest.mark.parametrize("train", [False, True], ids=["infer", "train"])
def test_plan_refuses_a_pyramid_over_budget(train):
    need = ops.min_launch_bytes(SHAPES, 2, 8, train=train, heads=2)
    assert msda_plan(_spec(budget=need, train=train), backend="pallas")
    with pytest.raises(ValueError, match=f"{need} bytes.*{need - 1} bytes"):
        msda_plan(_spec(budget=need - 1, train=train), backend="pallas")
