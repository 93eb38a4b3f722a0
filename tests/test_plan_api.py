"""Plan/execute API: spec -> plan -> execute, registry, caches, shim parity."""
import dataclasses
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, registry
from repro.kernels import plan as plan_mod
from repro.kernels.plan import MsdaSpec, msda_plan
from repro.kernels.ref import msda_ref

LEVELS = ((10, 6), (5, 3))


def _inputs(B=2, Q=21, H=2, D=8, P=3, levels=LEVELS, dtype=jnp.float32, seed=0):
    S = sum(h * w for h, w in levels)
    L = len(levels)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    value = jax.random.normal(ks[0], (B, S, H, D), jnp.float32).astype(dtype)
    loc = jax.random.uniform(ks[1], (B, Q, H, L, P, 2), minval=-0.2, maxval=1.2)
    attn = jax.nn.softmax(
        jax.random.normal(ks[2], (B, Q, H, L, P)).reshape(B, Q, H, -1)
    ).reshape(B, Q, H, L, P)
    return value, loc, attn


def _spec(value, loc, **kw):
    B, S, H, D = value.shape
    Q, P = loc.shape[1], loc.shape[4]
    return MsdaSpec(spatial_shapes=LEVELS, num_heads=H, head_dim=D,
                    num_points=P, num_queries=Q, dtype=str(value.dtype), **kw)


@pytest.fixture(autouse=True)
def _fresh_plan_cache():
    plan_mod.clear_plans()
    yield
    plan_mod.clear_plans()


# --------------------------------------------------------------------------
# shim vs plan equivalence
# --------------------------------------------------------------------------


def test_shim_bit_identical_to_plan_ref_backend():
    value, loc, attn = _inputs()
    out_shim = ops.msda(value, LEVELS, loc, attn, backend="ref")
    plan = msda_plan(_spec(value, loc), backend="ref")
    out_plan = plan(value, loc, attn)
    assert jnp.array_equal(out_shim, out_plan)  # bit-identical, same path


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_shim_matches_plan_pallas_interpret(dtype):
    value, loc, attn = _inputs(dtype=dtype)
    out_shim = ops.msda(value, LEVELS, loc, attn, backend="pallas")
    plan = msda_plan(_spec(value, loc), backend="pallas")
    out_plan = plan(value, loc, attn)
    assert out_plan.dtype == dtype
    np.testing.assert_array_equal(np.asarray(out_shim), np.asarray(out_plan))
    ref = msda_ref(value, LEVELS, loc, attn)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out_plan, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_plan_q_not_multiple_of_block_q():
    # Q=21 with forced block_q=8: padding path (qpad=24) must be exact
    value, loc, attn = _inputs(Q=21)
    plan = msda_plan(_spec(value, loc), backend="pallas", block_q=(8, 8))
    out = plan(value, loc, attn)
    ref = msda_ref(value, LEVELS, loc, attn)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_plan_grads_match_oracle_train_mode():
    value, loc, attn = _inputs()
    plan = msda_plan(_spec(value, loc, train=True), backend="pallas")
    g = jax.grad(lambda v, l, a: jnp.sum(plan(v, l, a) ** 2), argnums=(0, 1, 2))(
        value, loc, attn)
    gr = jax.grad(lambda v, l, a: jnp.sum(msda_ref(v, LEVELS, l, a) ** 2),
                  argnums=(0, 1, 2))(value, loc, attn)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4)


def test_plan_shape_validation():
    value, loc, attn = _inputs()
    plan = msda_plan(_spec(value, loc), backend="ref")
    with pytest.raises(ValueError, match="does not match plan spec"):
        plan(value[:, :-1], loc, attn)
    with pytest.raises(ValueError, match="!= spec Q"):
        plan(value, loc[:, :-1], attn)


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------


def test_registry_builtins_present():
    assert "ref" in registry.list_backends()
    assert "pallas" in registry.list_backends()


def test_registry_unknown_backend_errors():
    with pytest.raises(registry.UnknownBackendError, match="no-such-npu"):
        registry.get_backend("no-such-npu")
    value, loc, attn = _inputs()
    with pytest.raises(ValueError):
        msda_plan(_spec(value, loc), backend="no-such-npu")


def test_registry_register_and_execute_custom_backend():
    calls = []

    def builder(spec, tuning):
        calls.append(spec)

        def run(value, loc, attn):
            from repro.kernels.ref import msda_ref as oracle

            return oracle(value, spec.spatial_shapes, loc, attn)

        return run

    registry.register_backend("test-oracle", builder)
    try:
        value, loc, attn = _inputs()
        plan = msda_plan(_spec(value, loc), backend="test-oracle")
        assert plan.backend == "test-oracle"
        out = plan(value, loc, attn)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(msda_ref(value, LEVELS, loc, attn)), atol=1e-6)
        assert len(calls) == 1  # builder ran exactly once (at plan time)
        plan(value, loc, attn)
        assert len(calls) == 1
    finally:
        registry.unregister_backend("test-oracle")


def test_registry_duplicate_and_reserved_names():
    def builder(spec, tuning):
        return lambda *a: None

    registry.register_backend("dup-backend", builder)
    try:
        with pytest.raises(ValueError, match="already registered"):
            registry.register_backend("dup-backend", builder)
        registry.register_backend("dup-backend", builder, overwrite=True)
    finally:
        registry.unregister_backend("dup-backend")
    with pytest.raises(ValueError, match="reserved"):
        registry.register_backend("auto", builder)


# --------------------------------------------------------------------------
# plan cache behaviour
# --------------------------------------------------------------------------


def test_same_spec_returns_same_plan_object():
    value, loc, attn = _inputs()
    p1 = msda_plan(_spec(value, loc), backend="pallas")
    p2 = msda_plan(_spec(value, loc), backend="pallas")
    assert p1 is p2
    info = plan_mod.plan_cache_info()
    assert info["hits"] >= 1 and info["size"] == 1
    plan_mod.clear_plans()
    p3 = msda_plan(_spec(value, loc), backend="pallas")
    assert p3 is not p1


def test_plan_blocks_not_reinvoked_on_repeat_calls(monkeypatch):
    """Acceptance: repeated identical-spec calls never re-run block planning."""
    value, loc, attn = _inputs()
    counter = {"n": 0}
    real = ops.plan_blocks

    def counting(*a, **kw):
        counter["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(ops, "plan_blocks", counting)
    ops.msda(value, LEVELS, loc, attn, backend="pallas")
    assert counter["n"] == 1  # planned once
    ops.msda(value, LEVELS, loc, attn, backend="pallas")
    ops.msda(value, LEVELS, loc, attn, backend="pallas")
    assert counter["n"] == 1  # cache hits: no re-planning


def test_plan_cache_eviction_bounded():
    value, loc, attn = _inputs()
    old = plan_mod.plan_cache_info()["maxsize"]
    plan_mod.configure_plan_cache(2)
    try:
        for q in (8, 16, 24):
            v, l, a = _inputs(Q=q)
            msda_plan(_spec(v, l), backend="ref")
        assert plan_mod.plan_cache_info()["size"] == 2  # LRU evicted
    finally:
        plan_mod.configure_plan_cache(old)


def test_deprecated_tuning_kwargs_warn():
    value, loc, attn = _inputs()
    ops._WARNED_KWARGS.clear()
    with pytest.warns(DeprecationWarning, match="interpret"):
        ops.msda(value, LEVELS, loc, attn, backend="pallas", interpret=True)


# --------------------------------------------------------------------------
# spec: VMEM budget field (per-device default, overridable)
# --------------------------------------------------------------------------


def test_vmem_budget_defaults_per_device_kind():
    assert plan_mod.default_vmem_budget("TPU v3") == 16 * 2**20
    assert plan_mod.default_vmem_budget("TPU v5p") == 64 * 2**20
    # the interpreter keeps the v5e figure
    assert plan_mod.default_vmem_budget("cpu") == 100 * 2**20
    spec = MsdaSpec(spatial_shapes=LEVELS, num_heads=2, head_dim=8,
                    num_points=2, num_queries=64)
    assert spec.vmem_budget == plan_mod.default_vmem_budget()


def test_vmem_budget_drives_block_plan():
    big_level = ((64, 64),)
    mk = lambda budget: MsdaSpec(
        spatial_shapes=big_level, num_heads=2, head_dim=32, num_points=4,
        num_queries=4096, vmem_budget=budget)
    small = msda_plan(mk(4 * 2**20), backend="pallas").block_q
    large = msda_plan(mk(256 * 2**20), backend="pallas").block_q
    assert large[0] > small[0]  # more VMEM -> wider blocks (longer vectors)


# --------------------------------------------------------------------------
# inspectability
# --------------------------------------------------------------------------


def test_describe_reports_per_level_decisions():
    value, loc, attn = _inputs()
    plan = msda_plan(_spec(value, loc), backend="pallas")
    report = plan.level_report()
    assert len(report) == len(LEVELS)
    assert all(r["gather"] == "vpu" for r in report)
    text = plan.describe()
    assert "backend=pallas" in text and "block_q" in text and "vmem" in text
    for r in report:
        assert r["slab_bytes"] > 0 and r["block_q"] >= 8


@pytest.mark.parametrize("train", [False, True], ids=["on", "train"])
def test_describe_reports_each_launch_weight_block(train):
    """The forward launch's line: the VMEM weight block's rows per query
    and its double-buffered bytes."""
    from repro.kernels import msda_fwd

    value, loc, attn = _inputs()
    plan = msda_plan(_spec(value, loc, train=train), backend="pallas")
    s = plan.spec
    lanes = ops.lane_width(s.heads_per_launch, s.head_dim)
    launches = [tuple(range(len(LEVELS)))]
    report = plan.weight_report()
    assert [w["levels"] for w in report] == launches
    text = plan.describe()
    for w, levels in zip(report, launches):
        rows = msda_fwd.weight_layout(len(levels), s.num_points,
                                      s.head_dim)[0]
        bq = plan.block_q[levels[0]]
        assert (w["rows_per_query"], w["block_q"]) == (rows, bq)
        assert w["vmem_bytes"] == 2 * bq * rows * lanes * 4
        span = (f"{levels[0]}-{levels[-1]}" if len(levels) > 1
                else f"{levels[0]}")
        assert (f"weights lvl {span}: {rows} rows/query x block_q {bq} = "
                f"{w['vmem_bytes']} B VMEM (2 buffers)") in text
    assert msda_plan(_spec(value, loc), backend="ref").weight_report() == []


# --------------------------------------------------------------------------
# dtype policy: the second planned axis (slab dtype + widened accumulator)
# --------------------------------------------------------------------------


def test_autotune_inputs_honor_spec_dtype():
    """Regression: _autotune_inputs used to build fp32 operands regardless
    of spec.dtype, so autotune timed (and cached winners for) a different
    program than real bf16 calls execute."""
    for dt in ("float32", "bfloat16"):
        spec = MsdaSpec(spatial_shapes=LEVELS, num_heads=2, head_dim=8,
                        num_points=3, num_queries=16, dtype=dt)
        value, loc, attn = plan_mod._autotune_inputs(spec)
        assert str(value.dtype) == dt
        assert str(loc.dtype) == dt
        assert str(attn.dtype) == dt
        assert value.shape == (1, spec.total_pixels, 2, 8)
        assert loc.shape == (1, 16, 2, spec.num_levels, 3, 2)


def test_dtype_policy_resolution():
    assert plan_mod.resolve_dtype_policy("follow") == ("", "float32")
    assert plan_mod.resolve_dtype_policy("bfloat16") == ("bfloat16", "float32")
    assert plan_mod.resolve_dtype_policy("auto") == ("auto", "float32")
    with pytest.raises(ValueError, match="dtype policy"):
        plan_mod.resolve_dtype_policy("float8")


def test_bf16_slab_widens_blocks_and_is_reported():
    """bf16 slabs halve residency -> heuristic blocks can only widen; the
    committed variant must show up in describe()/level_report()."""
    big = ((64, 64),)
    mk = lambda sdt: MsdaSpec(spatial_shapes=big, num_heads=2, head_dim=32,
                              num_points=4, num_queries=4096,
                              vmem_budget=4 * 2**20, slab_dtype=sdt)
    p32 = msda_plan(mk("float32"), backend="pallas")
    p16 = msda_plan(mk("bfloat16"), backend="pallas")
    assert p16.block_q[0] >= p32.block_q[0]
    assert p16.level_report()[0]["slab_dtype"] == "bfloat16"
    assert p16.level_report()[0]["slab_bytes"] < p32.level_report()[0]["slab_bytes"]
    assert "bfloat16" in p16.describe() and "accum=float32" in p16.describe()


def test_spec_normalises_policy_dtypes():
    spec = MsdaSpec(spatial_shapes=LEVELS, num_heads=2, head_dim=8,
                    num_points=2, num_queries=16, slab_dtype=jnp.bfloat16,
                    accum_dtype="float32")
    assert spec.slab_dtype == "bfloat16" and spec.accum_dtype == "float32"
    assert spec.resolved_slab_dtype() == "bfloat16"
    auto = MsdaSpec(spatial_shapes=LEVELS, num_heads=2, head_dim=8,
                    num_points=2, num_queries=16, slab_dtype="auto")
    assert auto.resolved_slab_dtype() == "float32"  # heuristic fallback


# --------------------------------------------------------------------------
# autotune (slow: times real candidate executions)
# --------------------------------------------------------------------------


@pytest.mark.slow
def test_autotune_picks_candidate_and_persists(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_MSDA_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    value, loc, attn = _inputs(Q=32, levels=((6, 6),))
    spec = MsdaSpec(spatial_shapes=((6, 6),), num_heads=2, head_dim=8,
                    num_points=3, num_queries=32)
    plan = msda_plan(spec, backend="pallas", tune="autotune")
    assert plan.tuning.source == "autotune"
    assert (tmp_path / "tune.json").exists()
    out = plan(value, loc, attn)
    ref = msda_ref(value, ((6, 6),), loc, attn)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    # a fresh plan cache must hit the on-disk winner (no re-timing)
    plan_mod.clear_plans()
    plan2 = msda_plan(spec, backend="pallas", tune="autotune")
    assert plan2.tuning.source == "autotune-cache"
    assert plan2.block_q == plan.block_q


@pytest.mark.slow
def test_autotune_races_slab_dtypes_and_persists(tmp_path, monkeypatch):
    """Under slab_dtype='auto', autotune races fp32 vs bf16 per level and
    the winner (whichever side) round-trips through the on-disk cache."""
    import json

    monkeypatch.setenv("REPRO_MSDA_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    spec = MsdaSpec(spatial_shapes=((6, 6), (3, 3)), num_heads=2, head_dim=8,
                    num_points=3, num_queries=32, slab_dtype="auto")
    plan = msda_plan(spec, backend="pallas", tune="autotune")
    assert plan.tuning.source == "autotune"
    assert len(plan.tuning.slab_dtypes) == 2
    assert all(d in ("float32", "bfloat16") for d in plan.tuning.slab_dtypes)
    entry = next(iter(json.load(open(tmp_path / "tune.json")).values()))
    assert entry == {"block_q": list(plan.block_q),
                     "slab_dtypes": list(plan.tuning.slab_dtypes)}
    plan_mod.clear_plans()
    plan2 = msda_plan(spec, backend="pallas", tune="autotune")
    assert plan2.tuning.source == "autotune-cache"
    assert plan2.tuning.slab_dtypes == plan.tuning.slab_dtypes


def test_autotune_ref_backend_falls_back_to_heuristic(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_MSDA_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    value, loc, attn = _inputs()
    plan = msda_plan(_spec(value, loc), backend="ref", tune="autotune")
    assert plan.tuning.source == "heuristic"  # no blocks to tune in XLA


def test_unknown_tune_mode_errors():
    value, loc, attn = _inputs()
    with pytest.raises(ValueError, match="tune"):
        msda_plan(_spec(value, loc), tune="genetic")


# --------------------------------------------------------------------------
# no silent stand-ins for the device: budgets, peaks, fallbacks, autotune
# --------------------------------------------------------------------------


def test_vmem_budget_unknown_tpu_kind_raises():
    assert plan_mod.default_vmem_budget("TPU v5 lite") == 100 * 2**20
    with pytest.raises(ValueError, match="TPU v99"):
        plan_mod.default_vmem_budget("TPU v99")


def test_peaks_unknown_device_kind_raises():
    from repro.launch import mesh as mesh_lib

    assert mesh_lib.peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(ValueError, match="cpu"):
        mesh_lib.peaks("cpu")


def test_compiled_pallas_plan_has_no_oracle_rung():
    """Compiled kernels (a TPU) never degrade to the XLA oracle: a dense
    compiled plan is the bottom of its ladder.  Interpreted ones keep the
    oracle rung, and a pruned compiled plan still demotes to dense."""
    spec = MsdaSpec(spatial_shapes=LEVELS, num_heads=2, head_dim=8,
                    num_points=2, num_queries=64)
    compiled = msda_plan(spec, backend="pallas", interpret=False)
    assert compiled.fallback() is None and compiled.fallback_chain() == ()
    interpreted = msda_plan(spec, backend="pallas", interpret=True)
    assert [p.backend for p in interpreted.fallback_chain()] == ["ref"]
    pruned = msda_plan(dataclasses.replace(spec, sparsity="topk"),
                       backend="pallas", interpret=False)
    chain = pruned.fallback_chain()
    assert [p.backend for p in chain] == ["pallas"]
    assert chain[0].tuning.sparsity == "dense"
    assert chain[0].tuning.interpret is False


def test_autotune_raises_when_no_candidate_builds(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_MSDA_AUTOTUNE_CACHE", str(tmp_path / "t.json"))

    def broken(spec, tuning):
        raise RuntimeError("Mosaic failed to compile TPU kernel: nope")

    registry.register_backend("broken-kernels", broken)
    try:
        spec = MsdaSpec(spatial_shapes=LEVELS, num_heads=2, head_dim=8,
                        num_points=2, num_queries=64)
        with pytest.raises(RuntimeError, match="Mosaic failed"):
            msda_plan(spec, backend="broken-kernels", tune="autotune")
    finally:
        registry.unregister_backend("broken-kernels")


def test_compilation_cache_dir_rule(monkeypatch, tmp_path):
    from repro.serving import persistence

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    # the checkout root: where pytest.ini sits
    assert os.path.isfile(os.path.join(persistence.CHECKOUT_ROOT,
                                       "pytest.ini"))
    assert persistence.compilation_cache_dir() == os.path.join(
        persistence.CHECKOUT_ROOT, ".jax_cache")
    assert persistence.compilation_cache_dir(str(tmp_path)) == str(tmp_path)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    # the environment wins over a launcher's --compile-cache
    assert persistence.compilation_cache_dir("/elsewhere") == str(
        tmp_path / "env")
