"""The packed-pyramid MSDA launch: launch count, raced grad_reduce and
the occupancy model.

* a plan executes exactly ONE Pallas launch per direction over the
  packed pyramid (asserted by counting ``pallas_call`` equations in the
  traced jaxpr), whatever the pyramid's depth,
* the ring-vs-psum grad_reduce race persists per mesh topology,
* the occupancy model counts a train plan's saved-corner block.
"""
import jax
import jax.numpy as jnp
from jax.extend import core as jex_core
import pytest

from repro.kernels import ops
from repro.kernels import plan as pm
from repro.kernels.plan import MsdaSpec, msda_plan

LEVELS = ((10, 6), (5, 3))
B, Q, H, D, P = 2, 21, 2, 8, 3


@pytest.fixture(autouse=True)
def _fresh_plan_cache():
    pm.clear_plans()
    yield
    pm.clear_plans()


def _inputs(seed=0, levels=LEVELS, b=B, q=Q, h=H, d=D, p=P):
    S = sum(hh * ww for hh, ww in levels)
    L = len(levels)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    value = jax.random.normal(ks[0], (b, S, h, d), jnp.float32)
    loc = jax.random.uniform(ks[1], (b, q, h, L, p, 2), minval=-0.2, maxval=1.2)
    attn = jax.nn.softmax(
        jax.random.normal(ks[2], (b, q, h, L, p)).reshape(b, q, h, -1)
    ).reshape(b, q, h, L, p)
    return value, loc, attn


def _spec(*, train=False, levels=LEVELS, q=Q, **kw):
    return MsdaSpec(spatial_shapes=levels, num_heads=H, head_dim=D,
                    num_points=P, num_queries=q, dtype="float32",
                    train=train, **kw)


def count_pallas_calls(fn, *args) -> int:
    """Number of ``pallas_call`` equations anywhere in fn's jaxpr."""
    def walk(jaxpr):
        n = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                n += 1
            for v in eqn.params.values():
                for sub in _jaxprs_of(v):
                    n += walk(sub)
        return n

    def _jaxprs_of(v):
        if isinstance(v, jex_core.ClosedJaxpr):
            return [v.jaxpr]
        if hasattr(v, "jaxpr") and isinstance(getattr(v, "jaxpr", None), jex_core.Jaxpr):
            return [v.jaxpr]
        if isinstance(v, jex_core.Jaxpr):
            return [v]
        if isinstance(v, (list, tuple)):
            return [j for item in v for j in _jaxprs_of(item)]
        return []

    return walk(jax.make_jaxpr(fn)(*args).jaxpr)


# --------------------------------------------------------------------------
# acceptance: exactly one Pallas launch per direction
# --------------------------------------------------------------------------


def test_fused_single_launch_per_direction():
    """One launch per direction whatever the pyramid's depth."""
    for levels in (LEVELS, ((10, 6),), ((6, 6), (4, 4), (3, 3), (2, 2))):
        value, loc, attn = _inputs(levels=levels)
        plan = msda_plan(_spec(train=True, levels=levels), backend="pallas")
        assert count_pallas_calls(lambda v, l, a: plan(v, l, a),
                                  value, loc, attn) == 1, levels

        # fwd + bwd under grad: one launch per direction = 2 total
        def loss(v, l, a):
            return jnp.sum(plan(v, l, a) ** 2)

        assert count_pallas_calls(jax.grad(loss, argnums=(0, 1, 2)),
                                  value, loc, attn) == 2, levels


# --------------------------------------------------------------------------
# satellite: raced grad_reduce (ring vs psum) per mesh topology
# --------------------------------------------------------------------------


def test_grad_reduce_race_persists_per_topology(tmp_path, monkeypatch):
    from repro.launch import mesh as mesh_lib

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    mesh = mesh_lib.make_mesh_2d(2, 2)
    monkeypatch.setenv("REPRO_MSDA_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    spec = MsdaSpec(spatial_shapes=((8, 8), (4, 4)), num_heads=2, head_dim=8,
                    num_points=2, num_queries=16, train=True)
    pm.reset_autotune_stats()
    plan = msda_plan(spec, backend="ref", tune="autotune", mesh=mesh,
                     sharding="2d", query_parallel=True)
    assert plan.sharding_mode == "query2d"
    assert plan.grad_reduce in ("ring", "psum")  # timing decides
    assert pm.autotune_stats()["raced"] >= 1
    winner = pm.get_autotune_winner(
        spec, "ref", mesh_suffix=pm.mesh_winner_suffix(mesh, True))
    assert winner is not None and winner["grad_reduce"] == plan.grad_reduce

    # a fresh build resolves the reduction from the cache: zero races
    pm.clear_plans()
    pm.reset_autotune_stats()
    plan2 = msda_plan(spec, backend="ref", tune="autotune", mesh=mesh,
                      sharding="2d", query_parallel=True)
    assert pm.autotune_stats()["raced"] == 0
    assert plan2.grad_reduce == plan.grad_reduce

    # heuristic tune / inference plans never race: 'auto' stays ring
    pm.clear_plans()
    heur = msda_plan(spec, backend="ref", mesh=mesh, sharding="2d",
                     query_parallel=True)
    assert heur.grad_reduce == "ring"


# --------------------------------------------------------------------------
# satellite: train-mode saved-corner block in the occupancy model
# --------------------------------------------------------------------------


def test_train_occupancy_counts_saved_corner_block():
    # per-query bytes must grow by the double-buffered (4P, lanes)
    # slab-dtype corner rows, the weight grads and phase 1's fp32 corners
    base = ops.per_query_bytes(P, D)
    train = ops.per_query_bytes(P, D, train=True, slab_itemsize=4)
    lanes = ops.lane_width(1, D)
    rows = ops.msda_fwd.weight_layout(1, P, D)[0]
    assert base == 2 * (1 + rows) * lanes * 4
    assert train == max(base + 2 * 4 * P * lanes * 4,
                        2 * lanes * 4 + 2 * 4 * P * lanes * 4 + 2 * 4 * P * 4
                        + 4 * P * lanes * 4)
    # and the planner therefore never gives a train plan MORE queries
    # per step than the equivalent inference plan
    shapes = ((64, 64), (32, 32))
    kw = dict(num_points=4, head_dim=32, num_queries=8192,
              vmem_budget=8 * 2**20)
    bq_train = ops.plan_blocks(shapes, train=True, **kw)
    bq_infer = ops.plan_blocks(shapes, train=False, **kw)
    assert bq_train <= bq_infer
