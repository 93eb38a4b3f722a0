"""Fused whole-pyramid MSDA kernels: parity, launch count, planner rung.

The tentpole contract (ISSUE 5):

* a fused plan executes exactly ONE Pallas launch per direction
  (asserted by counting ``pallas_call`` equations in the traced jaxpr,
  with the per-level path as the negative control),
* fused output and FULL VJP match the per-level path **bitwise** in
  fp32 (padded/border sampling locations included),
* the fusion rung is a planned, autotuned, persisted property: 'auto'
  follows the VMEM fitting model, the autotuned winner survives a
  ``PlanStore`` save/restore with zero timing runs and identical
  ``describe()``.

Also here: the satellite races — per-level one-hot routing and the
ring-vs-psum grad_reduce — and the train-mode saved-corner occupancy
fix.
"""
import json

import jax
import jax.numpy as jnp
from jax.extend import core as jex_core
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import plan as pm
from repro.kernels.plan import MsdaSpec, msda_plan
from repro.kernels.ref import msda_ref

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
    # straddle the border: masked (zero-weight) corners must fuse too
    loc = jax.random.uniform(ks[1], (b, q, h, L, p, 2), minval=-0.2, maxval=1.2)
    attn = jax.nn.softmax(
        jax.random.normal(ks[2], (b, q, h, L, p)).reshape(b, q, h, -1)
    ).reshape(b, q, h, L, p)
    return value, loc, attn


def _spec(fuse, *, train=False, levels=LEVELS, q=Q, **kw):
    return MsdaSpec(spatial_shapes=levels, num_heads=H, head_dim=D,
                    num_points=P, num_queries=q, dtype="float32",
                    train=train, fuse_levels=fuse, **kw)


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
# bitwise parity: fused == per-level in fp32, fwd + full VJP
# --------------------------------------------------------------------------


def test_fused_fwd_bitwise_matches_per_level():
    value, loc, attn = _inputs()
    out_f = msda_plan(_spec("on"), backend="pallas")(value, loc, attn)
    out_p = msda_plan(_spec("off"), backend="pallas")(value, loc, attn)
    np.testing.assert_array_equal(np.asarray(out_f), np.asarray(out_p))
    # and both are the right answer
    ref = msda_ref(value, LEVELS, loc, attn)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("train", [False, True], ids=["regather", "saved"])
def test_fused_vjp_bitwise_matches_per_level(train):
    """Full VJP (value, loc, attn) — border locations included, both the
    saved-corner train path and the regather inference path."""
    value, loc, attn = _inputs(seed=1)
    pf = msda_plan(_spec("on", train=train), backend="pallas")
    pp = msda_plan(_spec("off", train=train), backend="pallas")
    gf = jax.grad(lambda v, l, a: jnp.sum(pf(v, l, a) ** 2), argnums=(0, 1, 2))(
        value, loc, attn)
    gp = jax.grad(lambda v, l, a: jnp.sum(pp(v, l, a) ** 2), argnums=(0, 1, 2))(
        value, loc, attn)
    for name, a, b in zip(("value", "loc", "attn"), gf, gp):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=f"grad_{name}")


def test_fused_onehot_routing_matches():
    """Per-level MXU one-hot routing survives inside the fused loop."""
    value, loc, attn = _inputs(seed=2)
    out_f = msda_plan(_spec("on", onehot_small_levels=True),
                      backend="pallas")(value, loc, attn)
    ref = msda_ref(value, LEVELS, loc, attn)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(ref), atol=2e-5)
    # mixed routing: level 0 VPU, level 1 MXU (hand-pinned via params)
    params = ops.MSDAParams(
        spatial_shapes=LEVELS, block_q=(24, 24), interpret=True,
        save_sampled=False,
        onehot_levels=(False, True), fuse_levels=True)
    out_m = ops.build_kernel_op(params)(value, loc, attn)
    np.testing.assert_allclose(np.asarray(out_m), np.asarray(ref), atol=2e-5)


def test_fused_unfused_gather_scatter_ablations_match():
    value, loc, attn = _inputs(seed=3)
    base = msda_plan(_spec("on", train=True), backend="pallas")
    abl = msda_plan(_spec("on", train=True, fuse_gather=False,
                          fuse_scatter=False), backend="pallas")
    np.testing.assert_allclose(np.asarray(base(value, loc, attn)),
                               np.asarray(abl(value, loc, attn)), atol=1e-5)
    g1 = jax.grad(lambda v: jnp.sum(base(v, loc, attn) ** 2))(value)
    g2 = jax.grad(lambda v: jnp.sum(abl(v, loc, attn) ** 2))(value)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-5)


# --------------------------------------------------------------------------
# acceptance: exactly one Pallas launch per direction
# --------------------------------------------------------------------------


def test_fused_single_launch_per_direction():
    value, loc, attn = _inputs()
    L = len(LEVELS)
    pf = msda_plan(_spec("on", train=True), backend="pallas")
    pp = msda_plan(_spec("off", train=True), backend="pallas")

    # forward: one launch fused, L launches per-level (negative control)
    assert count_pallas_calls(lambda v, l, a: pf(v, l, a),
                              value, loc, attn) == 1
    assert count_pallas_calls(lambda v, l, a: pp(v, l, a),
                              value, loc, attn) == L

    # fwd + bwd under grad: one launch per direction = 2 total
    def loss(plan):
        return lambda v, l, a: jnp.sum(plan(v, l, a) ** 2)

    assert count_pallas_calls(jax.grad(loss(pf), argnums=(0, 1, 2)),
                              value, loc, attn) == 2
    assert count_pallas_calls(jax.grad(loss(pp), argnums=(0, 1, 2)),
                              value, loc, attn) == 2 * L


# --------------------------------------------------------------------------
# the fusion rung: planned, reported, persisted
# --------------------------------------------------------------------------


def test_fusion_rung_follows_vmem_fitting_model():
    # tiny budget: the packed pyramid + grad slab cannot fit -> per-level
    tight = msda_plan(_spec("auto", train=True, levels=((256, 256), (128, 128)),
                            q=4096, vmem_budget=2 * 2**20), backend="pallas")
    assert not tight.fused
    # roomy budget at DETR-ish scale: fused
    roomy = msda_plan(_spec("auto", train=True, vmem_budget=64 * 2**20),
                      backend="pallas")
    assert roomy.fused
    assert "fuse=pyramid" in roomy.describe()
    assert "fuse=per-level" in tight.describe()
    assert all(r["fused"] for r in roomy.level_report())
    # fused plans share ONE block_q across levels
    assert len(set(roomy.block_q)) == 1


def test_fusion_rung_ignored_by_non_fusable_backends():
    for backend in ("ref", "cpu"):
        plan = msda_plan(_spec("on"), backend=backend)
        assert not plan.fused  # truthful: those backends launch no kernels
        out = plan(*_inputs())
        assert out.shape == (B, Q, H * D)


def test_single_level_auto_stays_per_level():
    plan = msda_plan(_spec("auto", levels=((8, 8),)), backend="pallas")
    assert not plan.fused


def test_fuse_winner_persists_and_reloads(tmp_path, monkeypatch):
    """The autotuned fuse_levels winner lands in the winner cache and a
    fresh plan build resolves it with zero timing runs."""
    monkeypatch.setenv("REPRO_MSDA_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    spec = _spec("auto", train=True, levels=((6, 6), (3, 3)), q=16)
    pm.reset_autotune_stats()
    plan = msda_plan(spec, backend="pallas", tune="autotune")
    assert plan.tuning.source == "autotune"
    assert pm.autotune_stats()["raced"] == 1
    entry = next(iter(json.load(open(tmp_path / "at.json")).values()))
    assert entry["fuse_levels"] == plan.fused

    pm.clear_plans()
    pm.reset_autotune_stats()
    plan2 = msda_plan(spec, backend="pallas", tune="autotune")
    stats = pm.autotune_stats()
    assert stats["raced"] == 0 and stats["cache_hits"] >= 1
    assert plan2.tuning.source == "autotune-cache"
    assert plan2.fused == plan.fused
    assert plan2.block_q == plan.block_q


def test_pinned_on_survives_schema_less_winner(tmp_path, monkeypatch):
    """A hand-seeded winner WITHOUT the fuse_levels field (pre-fusion /
    hand-authored schema) must not un-fuse a spec pinned 'on'."""
    monkeypatch.setenv("REPRO_MSDA_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    spec = _spec("on", levels=((6, 6), (3, 3)), q=16)
    assert pm.seed_autotune_winner(
        spec, "pallas",
        {"block_q": [16, 16], "slab_dtypes": ["float32", "float32"]})
    plan = msda_plan(spec, backend="pallas", tune="autotune")
    assert plan.tuning.source == "autotune-cache"
    assert plan.fused  # the 'on' pin wins over the field-less entry


def test_fuse_winner_survives_plan_store_roundtrip(tmp_path, monkeypatch):
    """Acceptance: the autotuned fuse_levels winner survives a PlanStore
    save/restore with zero timing runs and identical describe()."""
    from repro.serving.persistence import PlanStore, _norm_describe

    monkeypatch.setenv("REPRO_MSDA_AUTOTUNE_CACHE", str(tmp_path / "at1.json"))
    spec = _spec("auto", train=True, levels=((6, 6), (3, 3)), q=16)
    plan = msda_plan(spec, backend="pallas", tune="autotune")
    store = PlanStore(str(tmp_path / "plans.json"))
    assert store.save_plans([plan]) == 1

    # "restart": fresh plan cache, fresh (empty) winner cache
    pm.clear_plans()
    pm.reset_autotune_stats()
    monkeypatch.setenv("REPRO_MSDA_AUTOTUNE_CACHE", str(tmp_path / "at2.json"))
    report = store.restore()
    assert not report.skipped and not report.describe_mismatches
    assert pm.autotune_stats()["raced"] == 0
    [restored] = report.plans
    assert restored.fused == plan.fused
    assert restored.tuning.source == "autotune-cache"
    assert _norm_describe(restored.describe()) == _norm_describe(plan.describe())


# --------------------------------------------------------------------------
# satellite: autotuned one-hot threshold (replaces the static heuristic)
# --------------------------------------------------------------------------


def test_onehot_race_persists_per_level_flips(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_MSDA_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    spec = _spec("off", levels=((6, 6), (3, 3)), q=16,
                 onehot_small_levels=True)
    pm.reset_autotune_stats()
    plan = msda_plan(spec, backend="pallas", tune="autotune")
    assert plan.tuning.source == "autotune"
    assert len(plan.tuning.onehot_levels) == 2
    entry = next(iter(json.load(open(tmp_path / "at.json")).values()))
    # the raced routing is persisted per level, whichever way it went
    assert entry["onehot_levels"] == [bool(x) for x in plan.tuning.onehot_levels]

    pm.clear_plans()
    pm.reset_autotune_stats()
    plan2 = msda_plan(spec, backend="pallas", tune="autotune")
    assert pm.autotune_stats()["raced"] == 0
    assert plan2.tuning.onehot_levels == plan.tuning.onehot_levels
    # the raced plan still computes the right answer
    value, loc, attn = _inputs(levels=((6, 6), (3, 3)), q=16)
    np.testing.assert_allclose(
        np.asarray(plan2(value, loc, attn)),
        np.asarray(msda_ref(value, ((6, 6), (3, 3)), loc, attn)), atol=2e-5)


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
    assert all(t <= i for t, i in zip(bq_train, bq_infer))
    fused_t = ops.plan_blocks(shapes, train=True, fused=True, **kw)
    fused_i = ops.plan_blocks(shapes, train=False, fused=True, **kw)
    assert len(set(fused_t)) == 1 and len(set(fused_i)) == 1
    assert fused_t[0] <= fused_i[0]
