"""Cross-tier differential harness for partial fusion (ISSUE 9).

One oracle, every tier: for a random kernel configuration the
per-level executor (fuse off) is the reference, and EVERY fusion tier
of the same configuration — each strict prefix 1 <= k < L and the
whole-pyramid launch — must reproduce its forward output and full VJP
(value, loc, attn) **bitwise** in fp32.  No tolerances: the packed
super-slab rounds each level to its own committed dtype, so a fused
tier reads bit-identical level data and accumulates in the same order
per level.

The sweep varies everything the packing logic branches on:

* pyramid depth 1..5 with irregular level shapes,
* committed per-level slab dtypes — uniform fp32 AND mixed
  fp32/bfloat16 (each level rounded to its own dtype in the packed
  super-slab),
* sampling locations straddling the [0, 1] border (masked corners),
* both residual modes — train-style saved corners (``save_sampled``)
  and the inference regather path.

Each tier's launch geometry is asserted structurally by counting
``pallas_call`` equations in the traced jaxpr: a k-prefix tier runs
exactly ``L - k + 1`` launches per direction (``k == 0`` fused means
the whole pyramid: one launch).

A mutation NEGATIVE control proves the harness can fail: perturbing a
single packed corner weight in the super-slab must break bitwise
parity.  A differential suite whose oracle comparison cannot trip is
measuring nothing.

The deterministic seeded sweep below always runs.  When ``hypothesis``
is installed (CI's kernels lane), a property layer drives the same
oracle with minimised random cases on top.
"""
import jax
import jax.numpy as jnp
from jax.extend import core as jex_core
import numpy as np
import pytest

from repro.kernels import ops

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # local dev without the CI extras: seeded sweep only
    HAVE_HYPOTHESIS = False


# --------------------------------------------------------------------------
# case generation: geometry + dtype commitments + residual mode
# --------------------------------------------------------------------------


def _case_from_rng(rng):
    """One differential case drawn from a seeded ``numpy`` Generator —
    the same sampler backs the deterministic sweep and (via integer
    seeds) the hypothesis layer, so a CI-minimised failure replays
    locally as ``_case_from_rng(np.random.default_rng(seed))``."""
    L = int(rng.integers(1, 6))
    shapes = tuple(
        (int(rng.integers(2, 9)), int(rng.integers(2, 9))) for _ in range(L))
    mixed = bool(rng.integers(0, 2)) and L >= 2
    if mixed:
        dtypes = tuple(
            str(rng.choice(["float32", "bfloat16"])) for _ in range(L))
        # force an actual mix: a uniform draw would test the legacy path
        if len(set(dtypes)) == 1:
            flip = {"float32": "bfloat16", "bfloat16": "float32"}
            dtypes = (flip[dtypes[0]],) + dtypes[1:]
    else:
        dtypes = ()
    return {
        "shapes": shapes,
        "dtypes": dtypes,
        "B": int(rng.integers(1, 3)),
        "Q": int(rng.choice([8, 13, 16])),
        "H": int(rng.integers(1, 3)),
        "D": int(rng.choice([4, 8])),
        "P": int(rng.integers(1, 4)),
        "save_sampled": bool(rng.integers(0, 2)),
        "seed": int(rng.integers(0, 2**31)),
    }


def _inputs(case):
    shapes, L = case["shapes"], len(case["shapes"])
    B, Q, H, D, P = (case[k] for k in "BQHDP")
    S = sum(h * w for h, w in shapes)
    ks = jax.random.split(jax.random.PRNGKey(case["seed"]), 3)
    value = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    # straddle the border: masked (zero-weight) corners must pack too
    loc = jax.random.uniform(ks[1], (B, Q, H, L, P, 2),
                             minval=-0.2, maxval=1.2)
    attn = jax.nn.softmax(
        jax.random.normal(ks[2], (B, Q, H, L, P)).reshape(B, Q, H, -1)
    ).reshape(B, Q, H, L, P)
    return value, loc, attn


def _params(case, fused, prefix):
    L = len(case["shapes"])
    bq = -(-case["Q"] // 8) * 8
    return ops.MSDAParams(
        spatial_shapes=case["shapes"], block_q=(bq,) * L, interpret=True,
        fuse_levels=fused, fuse_prefix=prefix,
        save_sampled=case["save_sampled"],
        slab_dtypes=tuple(case["dtypes"]))


def _tiers(L):
    """(label, fused, prefix) for every tier of an L-level pyramid:
    per-level, each strict prefix, whole pyramid."""
    tiers = [("per-level", False, 0)]
    tiers += [(f"prefix:{k}", True, k) for k in range(1, L)]
    tiers.append(("full", True, 0))
    return tiers


def _run(case, fused, prefix):
    """(out, (gvalue, gloc, gattn)) for one tier of the case."""
    f = ops.build_kernel_op(_params(case, fused, prefix))
    value, loc, attn = _inputs(case)
    out = f(value, loc, attn)
    g = jax.grad(lambda v, l, a: jnp.sum(f(v, l, a) * 0.5),
                 argnums=(0, 1, 2))(value, loc, attn)
    return out, g


def _assert_tiers_bitwise(case):
    """The differential oracle: every tier bitwise-equals per-level."""
    ref_out, ref_g = _run(case, False, 0)
    assert not np.any(np.isnan(np.asarray(ref_out)))
    for label, fused, prefix in _tiers(len(case["shapes"]))[1:]:
        out, g = _run(case, fused, prefix)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(ref_out),
            err_msg=f"{label} fwd [{case}]")
        for name, a, b in zip(("value", "loc", "attn"), g, ref_g):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"{label} grad_{name} [{case}]")


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
        if hasattr(v, "jaxpr") and isinstance(getattr(v, "jaxpr", None),
                                              jex_core.Jaxpr):
            return [v.jaxpr]
        if isinstance(v, jex_core.Jaxpr):
            return [v]
        if isinstance(v, (list, tuple)):
            return [j for item in v for j in _jaxprs_of(item)]
        return []

    return walk(jax.make_jaxpr(fn)(*args).jaxpr)


# --------------------------------------------------------------------------
# deterministic seeded sweep — always runs, no optional deps
# --------------------------------------------------------------------------

_SWEEP_SEEDS = tuple(range(6))


@pytest.mark.parametrize("sweep_seed", _SWEEP_SEEDS)
def test_all_tiers_bitwise_equal_seeded(sweep_seed):
    _assert_tiers_bitwise(_case_from_rng(np.random.default_rng(sweep_seed)))


def test_sweep_covers_the_interesting_axes():
    """The seeded sweep is only a proof if its cases actually span the
    packing branches: at least one mixed-dtype case, one deep pyramid
    (a strict prefix with a multi-level tail), and both residual
    modes."""
    cases = [_case_from_rng(np.random.default_rng(s)) for s in _SWEEP_SEEDS]
    assert any(c["dtypes"] for c in cases)
    assert any(len(c["shapes"]) >= 3 for c in cases)
    assert any(c["save_sampled"] for c in cases)
    assert any(not c["save_sampled"] for c in cases)


def test_mixed_dtype_prefix_pinpoint():
    """The mixed-dtype packing's hardest case, pinned rather than
    drawn: a bf16 level INSIDE an fp32 prefix, strict tier,
    both residual modes."""
    for save in (False, True):
        _assert_tiers_bitwise({
            "shapes": ((6, 8), (4, 4), (2, 2)),
            "dtypes": ("float32", "bfloat16", "float32"),
            "B": 2, "Q": 16, "H": 2, "D": 8, "P": 3,
            "save_sampled": save, "seed": 17,
        })


# --------------------------------------------------------------------------
# launch geometry: L - k + 1 launches per direction, counted in the jaxpr
# --------------------------------------------------------------------------


@pytest.mark.parametrize("save_sampled", [False, True],
                         ids=["regather", "saved"])
def test_launches_per_tier(save_sampled):
    case = {
        "shapes": ((6, 8), (4, 4), (3, 3), (2, 2)),
        "dtypes": (), "B": 1, "Q": 8, "H": 1, "D": 4, "P": 2,
        "save_sampled": save_sampled, "seed": 5,
    }
    L = len(case["shapes"])
    value, loc, attn = _inputs(case)
    for label, fused, prefix in _tiers(L):
        f = ops.build_kernel_op(_params(case, fused, prefix))
        per_dir = L if not fused else (1 if prefix == 0 else L - prefix + 1)
        assert count_pallas_calls(f, value, loc, attn) == per_dir, label
        grad = jax.grad(lambda v, l, a: jnp.sum(f(v, l, a)),
                        argnums=(0, 1, 2))
        # the VJP trace holds the forward replay plus the backward
        # kernels: one scatter launch per forward launch
        assert count_pallas_calls(grad, value, loc, attn) == 2 * per_dir, label


# --------------------------------------------------------------------------
# mutation negative control: the oracle must be able to fail
# --------------------------------------------------------------------------


def test_mutated_packed_slab_breaks_parity(monkeypatch):
    """Perturb ONE packed corner weight (a single super-slab element)
    and the differential assertion must trip — proving the bitwise
    comparison actually constrains the fused data path."""
    case = {
        "shapes": ((6, 8), (4, 4), (2, 2)), "dtypes": (),
        "B": 2, "Q": 16, "H": 2, "D": 8, "P": 3,
        "save_sampled": False, "seed": 17,
    }
    orig = ops._pack_pyramid
    # level 0 is (6, 8): padded width 10, real image origin at pixel
    # (1, 1) — row 11 is a REAL corner value, not a zero-pad row whose
    # masked weight would null the perturbation
    row = 1 * (case["shapes"][0][1] + 2) + 1

    def tampered(value_t, spatial_shapes, dtypes):
        slab = orig(value_t, spatial_shapes, dtypes)
        return slab.at[0, 0, row, 0].add(jnp.asarray(1e-3, slab.dtype))

    monkeypatch.setattr(ops, "_pack_pyramid", tampered)
    with pytest.raises(AssertionError):
        _assert_tiers_bitwise(case)


def test_untampered_control_for_the_mutation():
    """Same case as the mutation test, untampered: green.  Pairs with
    the negative control so a failure there can only mean the
    perturbation (not the case itself) broke parity."""
    _assert_tiers_bitwise({
        "shapes": ((6, 8), (4, 4), (2, 2)), "dtypes": (),
        "B": 2, "Q": 16, "H": 2, "D": 8, "P": 3,
        "save_sampled": False, "seed": 17,
    })


# --------------------------------------------------------------------------
# hypothesis layer (CI): random cases through the same oracle
# --------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    @given(st.integers(0, 2**31 - 1))
    @settings(max_examples=8, deadline=None)
    def test_all_tiers_bitwise_equal_property(seed):
        _assert_tiers_bitwise(_case_from_rng(np.random.default_rng(seed)))
