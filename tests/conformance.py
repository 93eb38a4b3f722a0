"""Cross-backend x dtype-policy conformance suite for MSDA.

Every backend returned by ``registry.list_backends()`` is parametrized
against the ``"ref"`` oracle for forward and VJP parity, under every
dtype policy and every geometry form of the benchmark cells' MSDA calls
— so any future ``register_backend(...)`` call is automatically covered
the moment it lands (collection re-reads the registry).  CI shards the
matrix via env vars:

* ``REPRO_CONFORMANCE_BACKENDS`` — comma list restricting the backends
  (e.g. ``"ref,cpu"`` for the Pallas-free CPU lane),
* ``REPRO_CONFORMANCE_POLICIES`` — comma list restricting the dtype
  policies (``"float32"`` / ``"bfloat16"``),
* ``REPRO_CONFORMANCE_SPARSITY`` — comma list restricting the sparsity
  variants (``"off"`` / ``"topk"``).

Tolerance tiers (documented, per dtype policy):

* ``float32`` policy on the ``"ref"`` backend: **bit-identical** — the
  plan executes the oracle itself, so any difference is a planning bug.
* ``float32`` policy elsewhere: ``2e-5`` fwd / ``5e-4`` VJP — fp32
  reassociation only (the kernels' gather order vs the oracle's).
* ``bfloat16`` policy (bf16 slab, fp32 accumulation): ``3e-2`` fwd /
  ``1e-1`` VJP against the *fp32* oracle — one bf16 rounding of the
  value slab (8-bit mantissa => ~4e-3 relative per element, amplified
  by the P*L-term reduction); accumulation error does NOT grow with Q
  because the accumulator stays fp32.

Sparsity tier (``sparsity="topk"`` — lossy BY DESIGN): the pruned plan
is conformance-checked against the *masked-renormalised* oracle
(``msda_sparse.topk_mask_weights`` + ``msda_ref``), NOT the dense one,
at the **float32** tolerances regardless of slab policy — the pruned
executor computes in fp32 end to end.  ``sparsity="off"`` and
``"auto"`` resolved without an autotune race must stay **bitwise**
equal to the dense plan on every backend x policy (lossy modes are
never picked untimed).

Also here: a mutation self-test of the oracle comparison (one perturbed
pixel of the packed slab must trip it), finite-difference gradcheck of
the backward path on small geometries, including sampling locations at and outside the [0, 1]
border where bilinear corner weights zero out — plus the pruned plan
with well-separated attention weights (so eps-perturbations cannot
flip the top-k selection AD differentiates through frozen).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import plan as plan_mod
from repro.kernels import registry
from repro.kernels.plan import MsdaSpec, msda_plan
from repro.kernels.ref import msda_ref

LEVELS = ((10, 6), (5, 3))
B, Q, H, D, P = 2, 21, 2, 8, 3

# the geometry forms of the benchmark cells' MSDA calls, in their head
# layout (8 heads of 32), as (levels, points, queries): DETR's encoder
# (5 levels x 4 points), BEVFormer's SCA (4 x 8), TSA and the decoders
# (1 x 4), and an odd form whose query count is no multiple of the block
FORM_H, FORM_D = 8, 32
FORMS = {
    "L5P4": (((8, 8), (4, 4), (3, 3), (2, 2), (1, 1)), 4, 16),
    "L4P8": (((6, 10), (3, 5), (2, 3), (1, 2)), 8, 16),
    "L1P4": (((10, 10),), 4, 16),
    "L3P2-odd": (((10, 6), (5, 3), (3, 2)), 2, 21),
}

# documented per-policy tolerance tiers (see module docstring)
FWD_TOL = {"float32": 2e-5, "bfloat16": 3e-2}
VJP_TOL = {"float32": 5e-4, "bfloat16": 1e-1}


def _env_subset(env_var, names):
    env = os.environ.get(env_var)
    if not env:
        return tuple(names)
    keep = {s.strip() for s in env.split(",") if s.strip()}
    unknown = keep - set(names)
    if unknown:
        # a typo'd/renamed name must fail the lane, not skip-collect an
        # empty matrix and report a green job that tested nothing
        raise ValueError(
            f"{env_var} names {sorted(unknown)} not in {sorted(names)}")
    return tuple(n for n in names if n in keep)


BACKENDS = _env_subset("REPRO_CONFORMANCE_BACKENDS", registry.list_backends())
POLICIES = _env_subset("REPRO_CONFORMANCE_POLICIES", ("float32", "bfloat16"))
SPARSITIES = _env_subset("REPRO_CONFORMANCE_SPARSITY", ("off", "topk"))


@pytest.fixture(autouse=True)
def _fresh_plan_cache():
    plan_mod.clear_plans()
    yield
    plan_mod.clear_plans()


def _inputs(seed=0, levels=LEVELS, b=B, q=Q, h=H, d=D, p=P):
    S = sum(hh * ww for hh, ww in levels)
    L = len(levels)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    value = jax.random.normal(ks[0], (b, S, h, d), jnp.float32)
    # straddle the border on purpose: [-0.2, 1.2] exercises the masked
    # (zero-weight) corners every backend must reproduce
    loc = jax.random.uniform(ks[1], (b, q, h, L, p, 2), minval=-0.2, maxval=1.2)
    attn = jax.nn.softmax(
        jax.random.normal(ks[2], (b, q, h, L, p)).reshape(b, q, h, -1)
    ).reshape(b, q, h, L, p)
    return value, loc, attn


def _spec(policy, *, train=False, levels=LEVELS, q=Q, h=H, d=D, p=P,
          sparsity="off", sparsity_k=0, query_order="identity"):
    slab_dtype, accum_dtype = plan_mod.resolve_dtype_policy(policy)
    return MsdaSpec(spatial_shapes=levels, num_heads=h, head_dim=d,
                    num_points=p, num_queries=q, dtype="float32", train=train,
                    slab_dtype=slab_dtype, accum_dtype=accum_dtype,
                    sparsity=sparsity,
                    sparsity_k=sparsity_k, query_order=query_order)


def _form(form):
    """(levels, spec kwargs, operands) of a geometry form."""
    levels, p, q = FORMS[form]
    kw = dict(levels=levels, q=q, h=FORM_H, d=FORM_D, p=p)
    return levels, kw, _inputs(b=1, **kw)


def _check_fwd(backend, policy, form):
    levels, kw, (value, loc, attn) = _form(form)
    plan = msda_plan(_spec(policy, **kw), backend=backend)
    out = plan(value, loc, attn)
    ref = msda_ref(value, levels, loc, attn)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    if backend == "ref" and policy == "float32":
        # the plan runs the oracle itself: bit-identical or planning bug
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    else:
        tol = FWD_TOL[policy]
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=tol, rtol=tol)


# --------------------------------------------------------------------------
# fwd parity: every backend x dtype policy x geometry form vs the oracle
# --------------------------------------------------------------------------


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("backend", BACKENDS)
def test_fwd_matches_ref_oracle(backend, policy, form):
    _check_fwd(backend, policy, form)


# --------------------------------------------------------------------------
# mutation self-test: the oracle comparison must be able to fail
# --------------------------------------------------------------------------

_MUTATED = ("pallas", "float32", "L3P2-odd")


def test_mutated_packed_slab_breaks_parity(monkeypatch):
    """Perturb ONE real pixel of the packed slab (every lane of its row)
    and the fwd comparison must trip — proving the oracle comparison
    actually constrains the kernels' data path."""
    levels = FORMS[_MUTATED[2]][0]
    # level 0's padded width is W + 2 and its real image origin sits at
    # pixel (1, 1): this row is a real corner value, not a zero-pad row
    # whose masked weight would null the perturbation
    row = levels[0][1] + 2 + 1
    orig = ops._pack_pyramid

    def tampered(value_t, spatial_shapes, dtypes):
        slab = orig(value_t, spatial_shapes, dtypes)
        return slab.at[:, :, row, :].add(jnp.asarray(1.0, slab.dtype))

    monkeypatch.setattr(ops, "_pack_pyramid", tampered)
    with pytest.raises(AssertionError):
        _check_fwd(*_MUTATED)


def test_untampered_control_for_the_mutation():
    """The mutation test's case, untampered: green.  Pairs with the
    negative control so a failure there can only mean the perturbation
    (not the case itself) broke parity."""
    _check_fwd(*_MUTATED)


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("backend", BACKENDS)
def test_bf16_policy_commits_bf16_slabs(backend, policy):
    """The plan must *report* the committed dtype variant per level."""
    plan = msda_plan(_spec(policy), backend=backend)
    report = plan.level_report()
    assert len(report) == len(LEVELS)
    # the ref oracle ignores the slab policy (pure fp32 compute) and its
    # report must say so rather than echo an uncommitted policy
    want = "bfloat16" if policy == "bfloat16" and backend != "ref" else "float32"
    assert all(r["slab_dtype"] == want for r in report)
    assert f"accum={plan.spec.accum_dtype}" in plan.describe()
    assert plan.spec.accum_dtype == "float32"  # wide accumulation, always


# --------------------------------------------------------------------------
# VJP parity: grads of every backend vs the fp32 oracle's grads
# --------------------------------------------------------------------------


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("backend", BACKENDS)
def test_vjp_matches_ref_oracle(backend, policy, form):
    levels, kw, (value, loc, attn) = _form(form)
    plan = msda_plan(_spec(policy, train=True, **kw), backend=backend)

    g = jax.grad(lambda v, l, a: jnp.sum(plan(v, l, a) ** 2),
                 argnums=(0, 1, 2))(value, loc, attn)
    gr = jax.grad(lambda v, l, a: jnp.sum(msda_ref(v, levels, l, a) ** 2),
                  argnums=(0, 1, 2))(value, loc, attn)
    tol = VJP_TOL[policy]
    for got, want, name in zip(g, gr, ("value", "loc", "attn")):
        assert got.dtype == want.dtype, name  # grad dtype == operand dtype
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=tol, rtol=tol,
            err_msg=f"grad_{name} [{backend}/{policy}/{form}]")


# --------------------------------------------------------------------------
# finite-difference gradcheck (bwd path, small geometry, border cases)
# --------------------------------------------------------------------------

# x/y samples: outside (<0, >1), exactly at the border, and interior —
# chosen OFF the bilinear kinks (px = x*W - 0.5 never an integer for
# W, H in {4, 5}) so central differences see a smooth function
_BORDER_COORDS = (-0.12, 0.0, 0.31, 0.52, 0.77, 1.0, 1.09, 0.45)


@pytest.mark.parametrize("backend", [b for b in BACKENDS if b != "ref"])
def test_gradcheck_finite_difference_small_geometry(backend):
    levels = ((4, 5),)
    b, q, h, d, p = 1, 4, 1, 4, 2
    value, _, attn = _inputs(seed=3, levels=levels, b=b, q=q, h=h, d=d, p=p)
    coords = np.resize(np.asarray(_BORDER_COORDS, np.float32), q * p * 2)
    loc = jnp.asarray(coords.reshape(b, q, h, 1, p, 2))
    gout = jax.random.normal(jax.random.PRNGKey(7), (b, q, h * d), jnp.float32)

    plan = msda_plan(_spec("float32", train=True, levels=levels, q=q, h=h,
                           d=d, p=p), backend=backend)
    f = jax.jit(lambda v, l, a: jnp.vdot(plan(v, l, a), gout))
    grads = jax.jit(jax.grad(f, argnums=(0, 1, 2)))(value, loc, attn)

    def fd(operand_idx, arr, flat_idx, eps):
        base = [np.asarray(value, np.float64), np.asarray(loc, np.float64),
                np.asarray(attn, np.float64)]

        def at(delta):
            pert = [x.copy() for x in base]
            pert[operand_idx].flat[flat_idx] += delta
            return float(f(*[jnp.asarray(x, jnp.float32) for x in pert]))

        return (at(eps) - at(-eps)) / (2 * eps)

    # loc: every coordinate (the nonlinear argument — border masks live
    # here); fp32 central differences at eps=1e-3 resolve ~1e-3 abs
    g_loc = np.asarray(grads[1], np.float64)
    for i in range(g_loc.size):
        approx = fd(1, loc, i, eps=1e-3)
        np.testing.assert_allclose(
            g_loc.flat[i], approx, atol=5e-3, rtol=5e-2,
            err_msg=f"grad_loc[{i}] (coord={np.asarray(loc).flat[i]:.2f})")

    # value / attn enter linearly: FD is exact up to fp noise; spot-check
    for operand_idx, arr, g in ((0, value, grads[0]), (2, attn, grads[2])):
        garr = np.asarray(g, np.float64)
        for i in range(0, garr.size, max(garr.size // 7, 1)):
            approx = fd(operand_idx, arr, i, eps=1e-2)
            np.testing.assert_allclose(garr.flat[i], approx, atol=2e-3,
                                       rtol=2e-2, err_msg=f"operand{operand_idx}[{i}]")


@pytest.mark.parametrize("backend", [b for b in BACKENDS if b != "ref"])
def test_grad_zero_far_outside_border(backend):
    """>1 pixel outside the map every corner weight masks to zero, so the
    op is locally constant: grad_loc == 0 and the output ignores attn
    mass placed there."""
    levels = ((4, 5),)
    b, q, h, d, p = 1, 3, 1, 4, 2
    value, _, attn = _inputs(seed=5, levels=levels, b=b, q=q, h=h, d=d, p=p)
    loc = jnp.full((b, q, h, 1, p, 2), 1.8)  # deep outside
    plan = msda_plan(_spec("float32", train=True, levels=levels, q=q, h=h,
                           d=d, p=p), backend=backend)
    out = plan(value, loc, attn)
    np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-6)
    g_loc = jax.grad(lambda l: jnp.sum(plan(value, l, attn) ** 2))(loc)
    np.testing.assert_allclose(np.asarray(g_loc), 0.0, atol=1e-6)


# --------------------------------------------------------------------------
# sparsity tier: dense fallback bitwise, pruned vs the masked oracle
# --------------------------------------------------------------------------


@pytest.mark.skipif("off" not in SPARSITIES, reason="sparsity=off lane off")
@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("backend", BACKENDS)
def test_sparsity_auto_unraced_is_bitwise_dense(backend, policy):
    """``sparsity="auto"``/``query_order="auto"`` WITHOUT an autotune
    race must resolve to the dense executor and identity order — lossy
    modes are never picked untimed — and match the explicit-off plan
    bitwise, forward and full VJP."""
    value, loc, attn = _inputs()
    base = msda_plan(_spec(policy, train=True), backend=backend)
    auto = msda_plan(_spec(policy, train=True, sparsity="auto",
                           query_order="auto"), backend=backend)
    assert auto.tuning.sparsity == "dense"
    assert auto.tuning.query_order == "identity"

    def vjp(plan):
        out = plan(value, loc, attn)
        g = jax.grad(lambda v, l, a: jnp.sum(plan(v, l, a) ** 2),
                     argnums=(0, 1, 2))(value, loc, attn)
        return (out,) + g

    for got, want, name in zip(vjp(auto), vjp(base),
                               ("out", "gvalue", "gloc", "gattn")):
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(want),
            err_msg=f"{name} [{backend}/{policy}]")


@pytest.mark.skipif("topk" not in SPARSITIES, reason="topk lane off")
@pytest.mark.parametrize("policy", POLICIES)
def test_pruned_matches_masked_renormalised_oracle(policy):
    """The pruned plan vs ``msda_ref`` over top-k-masked renormalised
    weights — fp32 tolerances regardless of slab policy (the pruned
    executor computes in fp32; the slab policy is dense-path tuning)."""
    from repro.kernels import msda_sparse

    value, loc, attn = _inputs()
    k = 4  # of L*P = 6 cells
    plan = msda_plan(_spec(policy, train=True, sparsity="topk",
                           sparsity_k=k), backend="cpu")
    assert plan.tuning.sparsity == "topk"
    masked = msda_sparse.topk_mask_weights(attn, k)
    ref = msda_ref(value, LEVELS, loc, masked)
    out = plan(value, loc, attn)
    tol = FWD_TOL["float32"]
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)

    g = jax.grad(lambda v, l, a: jnp.sum(plan(v, l, a) ** 2),
                 argnums=(0, 1, 2))(value, loc, attn)
    gr = jax.grad(
        lambda v, l, a: jnp.sum(
            msda_ref(v, LEVELS, l, msda_sparse.topk_mask_weights(a, k)) ** 2),
        argnums=(0, 1, 2))(value, loc, attn)
    tol = VJP_TOL["float32"]
    for got, want, name in zip(g, gr, ("value", "loc", "attn")):
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=tol, rtol=tol, err_msg=f"grad_{name} [pruned/{policy}]")


@pytest.mark.skipif("topk" not in SPARSITIES, reason="topk lane off")
def test_gradcheck_finite_difference_pruned():
    """FD gradcheck of the pruned plan.  Attention logits are spaced
    >= 2.0 apart per query, so the kept/dropped weight gap (~0.76)
    dwarfs the FD eps and no perturbation can flip the top-k selection
    that AD differentiates through frozen.  Gradients w.r.t. pruned-out
    cells must be zero on both sides; k=2 of 3 keeps the renormalised
    weights a genuine function of attn (k=1 would make them constant)."""
    levels = ((4, 5),)
    b, q, h, d, p = 1, 4, 1, 4, 3  # L*P = 3 cells, keep k=2
    value, _, _ = _inputs(seed=3, levels=levels, b=b, q=q, h=h, d=d, p=p)
    coords = np.resize(np.asarray(_BORDER_COORDS, np.float32), q * p * 2)
    loc = jnp.asarray(coords.reshape(b, q, h, 1, p, 2))
    # rotate which cells win so both kept/dropped index paths vary; the
    # kept-vs-dropped weight gap (softmax([3,1.5,0]) -> 0.175 vs 0.039)
    # stays an order of magnitude above the FD eps
    logits = np.asarray([[3.0, 1.5, 0.0], [0.0, 3.0, 1.5],
                         [1.5, 0.0, 3.0], [3.0, 0.0, 1.5]],
                        np.float32).reshape(b, q, h, 1, p)
    attn = jax.nn.softmax(jnp.asarray(logits).reshape(b, q, h, -1), axis=-1
                          ).reshape(b, q, h, 1, p)
    gout = jax.random.normal(jax.random.PRNGKey(7), (b, q, h * d), jnp.float32)

    plan = msda_plan(_spec("float32", train=True, levels=levels, q=q, h=h,
                           d=d, p=p, sparsity="topk", sparsity_k=2),
                     backend="cpu")
    f = jax.jit(lambda v, l, a: jnp.vdot(plan(v, l, a), gout))
    grads = jax.jit(jax.grad(f, argnums=(0, 1, 2)))(value, loc, attn)

    def fd(operand_idx, flat_idx, eps):
        base = [np.asarray(value, np.float64), np.asarray(loc, np.float64),
                np.asarray(attn, np.float64)]

        def at(delta):
            pert = [x.copy() for x in base]
            pert[operand_idx].flat[flat_idx] += delta
            return float(f(*[jnp.asarray(x, jnp.float32) for x in pert]))

        return (at(eps) - at(-eps)) / (2 * eps)

    g_loc = np.asarray(grads[1], np.float64)
    for i in range(g_loc.size):
        np.testing.assert_allclose(
            g_loc.flat[i], fd(1, i, eps=1e-3), atol=5e-3, rtol=5e-2,
            err_msg=f"grad_loc[{i}] (coord={np.asarray(loc).flat[i]:.2f})")

    g_attn = np.asarray(grads[2], np.float64)
    for i in range(g_attn.size):
        np.testing.assert_allclose(
            g_attn.flat[i], fd(2, i, eps=1e-2), atol=2e-3, rtol=2e-2,
            err_msg=f"grad_attn[{i}]")

    g_val = np.asarray(grads[0], np.float64)
    for i in range(0, g_val.size, max(g_val.size // 7, 1)):
        np.testing.assert_allclose(g_val.flat[i], fd(0, i, eps=1e-2),
                                   atol=2e-3, rtol=2e-2,
                                   err_msg=f"grad_value[{i}]")


# --------------------------------------------------------------------------
# registry auto-coverage: a freshly registered backend enters the matrix
# --------------------------------------------------------------------------


def test_new_backend_is_auto_covered():
    """list_backends() is the parametrization source, so a backend
    registered before collection lands in every test above; this guards
    the mechanism itself."""

    def builder(spec, tuning):
        return lambda v, l, a: msda_ref(v, spec.spatial_shapes, l, a)

    registry.register_backend("conformance-probe", builder)
    try:
        assert "conformance-probe" in registry.list_backends()
        assert set(BACKENDS) <= set(registry.list_backends())
    finally:
        registry.unregister_backend("conformance-probe")


# --------------------------------------------------------------------------
# degradation-ladder conformance: every fallback rung vs its primary
# --------------------------------------------------------------------------
# The serving resilience layer (``serving/resilience.py``) demotes a
# failing plan down ``MsdaPlan.fallback()`` — these tiers pin what a
# demotion costs numerically, per backend x policy (and, via BACKENDS,
# auto-cover any future ``register_backend`` the moment it lands):
#
# * same-backend rungs (sparse -> dense identity with a keep-everything
#   k) are **bitwise** — the rung reads the same slab bytes and
#   accumulates in the same dtype;
# * the terminal ``ref`` rung matches within the documented per-policy
#   forward tiers (FWD_TOL) — same budget as any backend-vs-oracle gap;
# * every rung is a heuristic build: zero autotune races, never
#   persisted as a winner, and the chain terminates at ``ref``.


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("backend", BACKENDS)
def test_fallback_ladder_rungs_are_consistent(backend, policy):
    value, loc, attn = _inputs()
    plan_mod.reset_autotune_stats()
    primary = msda_plan(_spec(policy), backend=backend, tune="heuristic")
    chain = primary.fallback_chain()
    if primary.backend == "ref":
        assert not chain and primary.fallback() is None
        return
    assert chain, f"{primary.rung_label()} has no fallback rung"
    assert chain[-1].backend == "ref", [r.rung_label() for r in chain]
    assert chain[-1].fallback() is None, "ladder does not terminate"
    prev, prev_out = primary, np.asarray(primary(value, loc, attn))
    for rung in chain:
        assert rung.tune == "heuristic", rung.describe()
        out = np.asarray(rung(value, loc, attn))
        if rung.backend == prev.backend:
            np.testing.assert_array_equal(
                out, prev_out,
                err_msg=f"{prev.rung_label()} -> {rung.rung_label()} "
                        f"must be bitwise (same backend, same slab bytes)")
        else:
            np.testing.assert_allclose(
                out, prev_out, rtol=0, atol=FWD_TOL[policy],
                err_msg=f"{prev.rung_label()} -> {rung.rung_label()}")
        prev, prev_out = rung, out
    # demotions must never race or persist winners
    assert plan_mod.autotune_stats()["raced"] == 0


@pytest.mark.parametrize("backend", BACKENDS)
def test_fallback_sparse_demotes_to_dense(backend):
    """A top-k plan's first rung drops sparsity (and Morton order) on
    the SAME backend.  With a keep-every-cell ``sparsity_k`` the prune
    is a no-op, so the demotion is numerically the dense plan — the
    fp32 tier bounds the renormalisation round-trip.  (The lossy gap of
    a truly pruned primary is covered by the masked-renormalised oracle
    tests above; a demotion never has to reproduce the loss.)"""
    L = len(LEVELS)
    spec = _spec("float32", sparsity="topk", sparsity_k=L * P)
    primary = msda_plan(spec, backend=backend, tune="heuristic")
    if primary.tuning.sparsity != "topk":
        pytest.skip(f"{backend} does not execute top-k plans")
    rung = primary.fallback()
    assert rung is not None and rung.backend == primary.backend
    assert rung.tuning.sparsity == "dense"
    assert rung.tuning.query_order == "identity"
    value, loc, attn = _inputs()
    np.testing.assert_allclose(
        np.asarray(rung(value, loc, attn)),
        np.asarray(primary(value, loc, attn)),
        rtol=0, atol=FWD_TOL["float32"],
        err_msg=f"{primary.rung_label()} -> {rung.rung_label()}")
