"""Sharding rules + distributed MSDA (shard_map on a debug mesh)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, PartitionSpec as P

from repro.configs.base import get_config, reduced
from repro.core import msda as msda_mod
from repro.kernels.ref import msda_ref
from repro.launch import mesh as mesh_lib
from repro.sharding import rules
from repro.train import state as train_state


def test_param_specs_cover_all_archs():
    mesh = mesh_lib.make_debug_mesh()
    for arch in ("llama3-8b", "dbrx-132b", "grok-1-314b", "xlstm-350m",
                 "recurrentgemma-2b", "whisper-large-v3", "phi-3-vision-4.2b"):
        cfg = get_config(arch)
        shapes = jax.eval_shape(lambda c=cfg: train_state.init_model(jax.random.PRNGKey(0), c))
        moe_e = cfg.moe.num_experts if cfg.moe else 0
        specs = rules.param_specs(shapes, mesh, moe_experts=moe_e)
        for (path, leaf), (_, spec) in zip(
            jax.tree_util.tree_flatten_with_path(shapes)[0],
            jax.tree_util.tree_flatten_with_path(specs)[0],
        ):
            assert len(spec) <= leaf.ndim, (arch, path, spec, leaf.shape)


def test_resolve_axes_multi_pod():
    mesh = jax.make_mesh((1, 1, 1), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)
    assert rules.resolve_axis("dp", mesh) == ("pod", "data")
    assert rules.resolve_axis("tp", mesh) == "model"
    mesh1 = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    assert rules.resolve_axis("dp", mesh1) == "data"


def test_hint_degrades_nondivisible():
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    with rules.use_mesh(mesh):
        x = jnp.ones((3, 5))
        y = rules.hint(x, "dp", "tp")  # 3 % 1 == 0 fine on 1-dev mesh
        assert y.shape == x.shape


def test_ep_vs_tp_moe_rule():
    mesh = mesh_lib.make_debug_mesh()  # model axis size 1 -> divisible
    cfg = get_config("grok-1-314b")
    shapes = jax.eval_shape(lambda: train_state.init_model(jax.random.PRNGKey(0), cfg))
    specs = rules.param_specs(shapes, mesh, moe_experts=8)
    # just structural sanity on a 1-dev mesh; the divisibility branch is
    # exercised against the production mesh in the dry-run
    leaves = jax.tree_util.tree_flatten_with_path(specs)[0]
    assert any("experts_wi" in str(p) for p, _ in leaves)


@pytest.mark.parametrize("query_parallel", [False, True])
def test_distributed_msda_matches_ref(query_parallel):
    levels = ((8, 8), (4, 4))
    B, Q, H, D, Pn = 2, 16, 2, 8, 2
    S = sum(h * w for h, w in levels)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    value = jax.random.normal(ks[0], (B, S, H, D))
    loc = jax.random.uniform(ks[1], (B, Q, H, len(levels), Pn, 2))
    attn = jax.nn.softmax(
        jax.random.normal(ks[2], (B, Q, H, len(levels), Pn)).reshape(B, Q, H, -1)
    ).reshape(B, Q, H, len(levels), Pn)
    ref = msda_ref(value, levels, loc, attn)
    mesh = mesh_lib.make_debug_mesh()
    with rules.use_mesh(mesh):
        out = msda_mod.distributed_msda(
            value, levels, loc, attn, mesh=mesh,
            query_parallel=query_parallel, backend="ref",
        )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_distributed_msda_grad_value_reduction():
    """query_parallel mode: grad wrt (replicated) value must equal the
    single-device grad — shard_map's transpose inserts the psum that
    realises the paper's staggered-scatter as partials+reduce."""
    levels = ((6, 6),)
    B, Q, H, D, Pn = 1, 8, 1, 8, 2
    S = 36
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    value = jax.random.normal(ks[0], (B, S, H, D))
    loc = jax.random.uniform(ks[1], (B, Q, H, 1, Pn, 2))
    attn = jax.nn.softmax(jax.random.normal(ks[2], (B, Q, H, 1, Pn)), axis=-1)
    mesh = mesh_lib.make_debug_mesh()

    def loss_dist(v):
        return jnp.sum(
            msda_mod.distributed_msda(
                v, levels, loc, attn, mesh=mesh, query_parallel=True, backend="ref"
            )
        )

    def loss_ref(v):
        return jnp.sum(msda_ref(v, levels, loc, attn))

    with rules.use_mesh(mesh):
        g1 = jax.grad(loss_dist)(value)
    g2 = jax.grad(loss_ref)(value)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-5)


# --------------------------------------------------------------------------
# 2D (dp x tp) query sharding + ring-reduced grad_value slabs
# (conftest splits the host into 4 virtual CPU devices so these meshes
# and their collectives — ppermute rings, psums — actually execute)
# --------------------------------------------------------------------------

from repro.kernels import msda_bwd
from repro.kernels import plan as pm


def _mesh(dp, tp):
    if len(jax.devices()) < dp * tp:
        pytest.skip(f"needs {dp * tp} devices")
    return mesh_lib.make_mesh_2d(dp, tp)


_LEVELS = ((8, 8), (4, 4))


@pytest.fixture(scope="module")
def prob():
    """One small MSDA problem: B=2, Q=16 (divides every mesh under test)."""
    B, Q, H, D, Pn = 2, 16, 2, 8, 2
    S = sum(h * w for h, w in _LEVELS)
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    value = jax.random.normal(ks[0], (B, S, H, D))
    loc = jax.random.uniform(ks[1], (B, Q, H, len(_LEVELS), Pn, 2))
    attn = jax.nn.softmax(
        jax.random.normal(ks[2], (B, Q, H, len(_LEVELS), Pn)).reshape(B, Q, H, -1)
    ).reshape(B, Q, H, len(_LEVELS), Pn)
    spec = pm.MsdaSpec(spatial_shapes=_LEVELS, num_heads=H, head_dim=D,
                       num_points=Pn, num_queries=Q, train=True)
    return value, loc, attn, spec


def test_ring_allreduce_equals_psum():
    """The ppermute ring is an all-reduce: every device ends with the
    full sum, bitwise equal to psum on a 2-wide axis (fp add is
    commutative; the ring order is a rotation of the device order)."""
    mesh = _mesh(2, 2)
    x = jnp.arange(2 * 37 * 3, dtype=jnp.float32).reshape(2, 37, 3) * 0.37
    def ring(v):
        return msda_bwd.ring_allreduce(v, "model", 2, axis=1)

    def psum(v):
        return jax.lax.psum(v, "model")

    kw = dict(mesh=mesh, in_specs=P(None, None, None),
              out_specs=P(None, None, None), check_vma=False)
    # chunk axis 37 does not divide the axis size: exercises the padding
    out_ring = jax.shard_map(ring, **kw)(x)
    out_psum = jax.shard_map(psum, **kw)(x)
    assert np.array_equal(np.asarray(out_ring), np.asarray(out_psum))


def test_query2d_plan_matches_ref_fwd_and_vjp(prob):
    """Acceptance: on a 2x2 mesh a 2D-sharded plan's forward and VJP
    match the unsharded reference within conformance tolerances."""
    value, loc, attn, spec = prob
    mesh = _mesh(2, 2)
    plan = pm.msda_plan(spec, backend="ref", mesh=mesh, sharding="2d")
    assert plan.sharding_mode == "query2d"
    assert plan.grad_reduce == "ring"
    assert plan.local_spec.num_queries == spec.num_queries // 4

    ref = msda_ref(value, _LEVELS, loc, attn)
    out = plan(value, loc, attn)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    g = jax.grad(lambda v, l, a: jnp.sum(plan(v, l, a) ** 2), argnums=(0, 1, 2))(
        value, loc, attn)
    gref = jax.grad(
        lambda v, l, a: jnp.sum(msda_ref(v, _LEVELS, l, a) ** 2), argnums=(0, 1, 2)
    )(value, loc, attn)
    for got, want in zip(g, gref):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("sharding,mode", [("2d", "query2d"), ("1d", "query")])
def test_ring_grad_value_equals_allreduce_bitwise(prob, sharding, mode):
    """Acceptance: the ring-reduced grad_value equals the all-reduce
    result BITWISE in fp32.  grad_reduce='psum' builds the identical
    backward with the tp-axis ring swapped for a psum, so the paths
    differ only in the collective under test; on a 2-wide tp axis the
    ring's rotated summation order is a commutation of psum's."""
    value, loc, attn, spec = prob
    mesh = _mesh(2, 2)
    kw = dict(backend="ref", mesh=mesh, sharding=sharding, query_parallel=True)
    p_ring = pm.msda_plan(spec, grad_reduce="ring", **kw)
    p_psum = pm.msda_plan(spec, grad_reduce="psum", **kw)
    assert p_ring.sharding_mode == p_psum.sharding_mode == mode
    assert (p_ring.grad_reduce, p_psum.grad_reduce) == ("ring", "psum")
    g_ring = jax.grad(lambda v: jnp.sum(p_ring(v, loc, attn) ** 2))(value)
    g_psum = jax.grad(lambda v: jnp.sum(p_psum(v, loc, attn) ** 2))(value)
    assert g_ring.dtype == jnp.float32
    assert np.array_equal(np.asarray(g_ring), np.asarray(g_psum))


def test_2d_falls_back_when_tp_does_not_divide(prob):
    """Nondivisible Q (or H) must fall back down the ladder — and the
    fallback plan must still compute the right answer, not idle shards
    silently."""
    del prob
    mesh = _mesh(2, 2)
    # Q=10: not divisible by dp*tp=4, divisible by tp=2 -> 1D query mode
    spec10 = pm.MsdaSpec(spatial_shapes=_LEVELS, num_heads=2, head_dim=8,
                         num_points=2, num_queries=10)
    assert pm.resolve_sharding(spec10, mesh, True, "2d")[0] == "query"
    # Q=9, H=3: neither queries nor heads divide tp=2 -> batch-only
    spec9 = pm.MsdaSpec(spatial_shapes=_LEVELS, num_heads=3, head_dim=8,
                        num_points=2, num_queries=9)
    assert pm.resolve_sharding(spec9, mesh, True, "2d")[0] == "batch"

    # the Q=10 fallback executes correctly end to end
    B, Q, H, D, Pn = 2, 10, 2, 8, 2
    S = sum(h * w for h, w in _LEVELS)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    value = jax.random.normal(ks[0], (B, S, H, D))
    loc = jax.random.uniform(ks[1], (B, Q, H, len(_LEVELS), Pn, 2))
    attn = jax.nn.softmax(
        jax.random.normal(ks[2], (B, Q, H, len(_LEVELS), Pn)).reshape(B, Q, H, -1)
    ).reshape(B, Q, H, len(_LEVELS), Pn)
    plan = pm.msda_plan(spec10, backend="ref", mesh=mesh, sharding="2d")
    assert plan.sharding_mode == "query"
    ref = msda_ref(value, _LEVELS, loc, attn)
    np.testing.assert_allclose(np.asarray(plan(value, loc, attn)),
                               np.asarray(ref), atol=1e-5)


def test_degenerate_meshes_resolve_to_1d(prob):
    """1xN and Nx1 meshes have one trivial axis: a 2D request resolves
    to the equivalent 1D rung instead of pretending to be 2D."""
    _, _, _, spec = prob
    m14 = _mesh(1, 4)
    m41 = _mesh(4, 1)
    # 1x4: dp is trivial -> plain query-parallel over tp
    assert pm.resolve_sharding(spec, m14, True, "2d")[0] == "query"
    # 4x1: tp is trivial -> batch-only dp sharding
    assert pm.resolve_sharding(spec, m41, True, "2d")[0] == "batch"


def test_describe_reports_sharding_mode_and_mesh_axes(prob):
    """Satellite: describe() states the resolved mode, the mesh
    topology, which axes shard Q, and the grad_value reduction — the
    truthful output docs/sharding.md quotes."""
    value, loc, attn, spec = prob
    del value, loc, attn
    mesh = _mesh(2, 2)
    text = pm.msda_plan(spec, backend="ref", mesh=mesh, sharding="2d").describe()
    assert "sharding=query2d" in text
    assert "mesh: data2xmodel2" in text
    assert "Q->data+model" in text
    assert "grad_value=ring" in text
    assert "per-shard: Q=4" in text
    rep = pm.msda_plan(spec, backend="ref", mesh=mesh, sharding="2d").sharding_report()
    assert rep["mode"] == "query2d"
    assert rep["query_axes"] == ("data", "model")
    assert rep["grad_reduce"] == "ring"
    # the 1D head-mode report stays truthful too
    nq = pm.msda_plan(dataclasses_replace_q(spec, 10), backend="ref", mesh=mesh)
    assert f"sharding={nq.sharding_mode}" in nq.describe()


def dataclasses_replace_q(spec, q):
    import dataclasses

    return dataclasses.replace(spec, num_queries=q)


def test_autotune_races_1d_vs_2d_and_persists(prob, tmp_path, monkeypatch):
    """Tentpole: under tune='autotune' + sharding='auto' the sharding
    mode is part of the autotune space — raced once, persisted in the
    winner cache ({"block_q","slab_dtypes","sharding"} schema), and a
    fresh plan build resolves from the cache with ZERO timing runs."""
    value, loc, attn, spec = prob
    del value, loc, attn
    mesh = _mesh(2, 2)
    monkeypatch.setenv("REPRO_MSDA_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    pm.clear_plans()
    pm.reset_autotune_stats()
    plan = pm.msda_plan(spec, backend="ref", tune="autotune", mesh=mesh,
                        query_parallel=True)
    assert plan.sharding_mode in ("query", "query2d")  # timing decides
    # >= 1: the grad_reduce (ring-vs-psum) race rides along for train specs
    assert pm.autotune_stats()["raced"] >= 1
    winner = pm.get_autotune_winner(
        spec, "ref", mesh_suffix=pm.mesh_winner_suffix(mesh, True))
    assert winner is not None and winner["sharding"] in ("1d", "2d")

    pm.clear_plans()
    pm.reset_autotune_stats()
    plan2 = pm.msda_plan(spec, backend="ref", tune="autotune", mesh=mesh,
                         query_parallel=True)
    stats = pm.autotune_stats()
    assert stats["raced"] == 0 and stats["cache_hits"] >= 1
    assert plan2.sharding_mode == plan.sharding_mode
    pm.clear_plans()


def test_plan_store_roundtrip_restores_2d_zero_races(prob, tmp_path, monkeypatch):
    """Acceptance: a PlanStore round-trip restores the 2D mode with zero
    autotune timing runs and an identical describe()."""
    from repro.serving.persistence import PlanStore

    value, loc, attn, spec = prob
    del value, loc, attn
    mesh = _mesh(2, 2)
    monkeypatch.setenv("REPRO_MSDA_AUTOTUNE_CACHE", str(tmp_path / "at1.json"))
    pm.clear_plans()
    pm.reset_autotune_stats()
    plan = pm.msda_plan(spec, backend="cpu", tune="autotune", mesh=mesh,
                        sharding="2d", query_parallel=True)
    assert plan.sharding_mode == "query2d"
    store = PlanStore(str(tmp_path / "plans.json"))
    assert store.save_plans([plan]) == 1

    # "restart": fresh plan cache, fresh (empty) winner cache
    pm.clear_plans()
    pm.reset_autotune_stats()
    monkeypatch.setenv("REPRO_MSDA_AUTOTUNE_CACHE", str(tmp_path / "at2.json"))
    report = store.restore(mesh=mesh)
    assert not report.skipped and not report.describe_mismatches
    assert pm.autotune_stats()["raced"] == 0
    [restored] = report.plans
    assert restored.sharding_mode == "query2d"
    # the raced reduction (ring or psum — timing decides) is restored
    assert restored.grad_reduce == plan.grad_reduce in ("ring", "psum")
    assert persistence_norm(restored.describe()) == persistence_norm(plan.describe())
    pm.clear_plans()


def persistence_norm(text):
    from repro.serving.persistence import _norm_describe

    return _norm_describe(text)


def test_plan_store_sharded_entry_degrades_without_mesh(prob, tmp_path, monkeypatch):
    """A distributed entry restored by a process with no (or the wrong)
    mesh degrades to a skip — never a crash, never a silently-local
    plan."""
    from repro.serving.persistence import PlanStore

    value, loc, attn, spec = prob
    del value, loc, attn
    mesh = _mesh(2, 2)
    monkeypatch.setenv("REPRO_MSDA_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    plan = pm.msda_plan(spec, backend="ref", mesh=mesh, sharding="2d")
    store = PlanStore(str(tmp_path / "plans.json"))
    store.save_plans([plan])
    pm.clear_plans()
    report = store.restore()  # no mesh
    assert not report.plans
    assert len(report.skipped) == 1 and "mesh" in report.skipped[0]
    report = store.restore(mesh=_mesh(1, 4))  # wrong topology
    assert not report.plans
    assert len(report.skipped) == 1 and "mismatch" in report.skipped[0]
    pm.clear_plans()


def test_msda_attention_module():
    from repro.configs.base import MSDAConfig

    mc = MSDAConfig(levels=((8, 8), (4, 4)), num_points=2, num_heads=2, backend="ref")
    d = 32
    p = msda_mod.init_msda_attention(jax.random.PRNGKey(0), d, mc)
    B, Q = 2, 10
    S = sum(h * w for h, w in mc.levels)
    q = jax.random.normal(jax.random.PRNGKey(1), (B, Q, d))
    feats = jax.random.normal(jax.random.PRNGKey(2), (B, S, d))
    refs = jax.random.uniform(jax.random.PRNGKey(3), (B, Q, 2))
    out = msda_mod.msda_attention(p, mc, q, feats, refs)
    assert out.shape == (B, Q, d)
    assert jnp.isfinite(out).all()
    # pallas backend agrees with ref backend through the module
    out_pal = msda_mod.msda_attention(p, mc, q, feats, refs, backend="pallas")
    np.testing.assert_allclose(np.asarray(out_pal), np.asarray(out), atol=2e-5)


# --------------------------------------------------------------------------
# batch x query hybrid sharding ('batchquery'): the whole device set is
# re-racked as (batch_tile x query_fan) so mid-size batches on tp-less
# meshes shard BOTH axes instead of idling on the batch rung
# --------------------------------------------------------------------------


def test_hybrid_resolution_ladder(prob):
    _, _, _, spec = prob
    m41 = _mesh(4, 1)
    # forced: 4 devices re-racked as B->x2, Q->x2
    mode, local = pm.resolve_sharding(spec, m41, True, "hybrid")
    assert mode == "batchquery"
    assert local.num_queries == spec.num_queries // 2
    # auto on a tp-less mesh with query-parallel intent prefers hybrid
    assert pm.resolve_sharding(spec, m41, True, "auto")[0] == "batchquery"
    # the pinned 1d/2d ladders are untouched (degenerate-mesh contract)
    assert pm.resolve_sharding(spec, m41, True, "2d")[0] == "batch"
    assert pm.resolve_sharding(spec, m41, True, "1d")[0] == "batch"
    # no query-parallel intent -> hybrid never surprise-tiles Q
    assert pm.resolve_sharding(spec, m41, False, "auto")[0] == "batch"
    # hybrid needs Q divisible by the query fan; Q=9 falls down the ladder
    spec9 = dataclasses_replace_q(spec, 9)
    assert pm.resolve_sharding(spec9, m41, True, "hybrid")[0] != "batchquery"


def test_hybrid_plan_matches_ref_fwd_and_vjp(prob):
    value, loc, attn, spec = prob
    mesh = _mesh(4, 1)
    plan = pm.msda_plan(spec, backend="ref", mesh=mesh, sharding="hybrid")
    assert plan.sharding_mode == "batchquery"
    assert plan.batch_tile == 2
    assert plan.local_spec.num_queries == spec.num_queries // 2
    rep = plan.sharding_report()
    assert rep["mode"] == "batchquery" and rep["batch_tile"] == 2
    assert "B->x2" in plan.describe() and "Q->x2" in plan.describe()

    ref = msda_ref(value, _LEVELS, loc, attn)
    np.testing.assert_allclose(np.asarray(plan(value, loc, attn)),
                               np.asarray(ref), atol=1e-5)
    g = jax.grad(lambda v, l, a: jnp.sum(plan(v, l, a) ** 2), argnums=(0, 1, 2))(
        value, loc, attn)
    gref = jax.grad(
        lambda v, l, a: jnp.sum(msda_ref(v, _LEVELS, l, a) ** 2), argnums=(0, 1, 2)
    )(value, loc, attn)
    for got, want in zip(g, gref):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_autotune_races_hybrid_and_persists(prob, tmp_path, monkeypatch):
    """Satellite: on a tp-less mesh the auto race includes the hybrid
    rung; the winner persists ('hybrid' in the cache schema) and a fresh
    build resolves from the cache with zero timing runs."""
    _, _, _, spec = prob
    mesh = _mesh(4, 1)
    monkeypatch.setenv("REPRO_MSDA_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    pm.clear_plans()
    pm.reset_autotune_stats()
    plan = pm.msda_plan(spec, backend="ref", tune="autotune", mesh=mesh,
                        query_parallel=True)
    assert plan.sharding_mode in ("batch", "batchquery")  # timing decides
    assert pm.autotune_stats()["raced_mesh"] >= 1
    winner = pm.get_autotune_winner(
        spec, "ref", mesh_suffix=pm.mesh_winner_suffix(mesh, True))
    assert winner is not None and winner["sharding"] in ("1d", "hybrid")

    pm.clear_plans()
    pm.reset_autotune_stats()
    plan2 = pm.msda_plan(spec, backend="ref", tune="autotune", mesh=mesh,
                         query_parallel=True)
    stats = pm.autotune_stats()
    assert stats["raced"] == 0 and stats["cache_hits"] >= 1
    assert plan2.sharding_mode == plan.sharding_mode
    pm.clear_plans()


def test_plan_store_roundtrip_restores_hybrid(prob, tmp_path, monkeypatch):
    from repro.serving.persistence import PlanStore

    _, _, _, spec = prob
    mesh = _mesh(4, 1)
    monkeypatch.setenv("REPRO_MSDA_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    pm.clear_plans()
    plan = pm.msda_plan(spec, backend="ref", mesh=mesh, sharding="hybrid")
    store = PlanStore(str(tmp_path / "plans.json"))
    assert store.save_plans([plan]) == 1
    pm.clear_plans()
    report = store.restore(mesh=mesh)
    assert not report.skipped and not report.describe_mismatches
    [restored] = report.plans
    assert restored.sharding_mode == "batchquery"
    assert restored.batch_tile == 2
    assert persistence_norm(restored.describe()) == persistence_norm(plan.describe())
    pm.clear_plans()
