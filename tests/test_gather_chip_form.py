"""The gather kernel's chip loop structure, run in the interpreter.

On a TPU ``msda_gather`` unrolls what fits its scalar budget: a whole
query step up to ``msda_fwd.UNROLLED_ROWS`` rows, else one rolled step
per sampling point with every (level, head) chain unrolled inside it.
The interpreter rolls the walk loop always, so the chip's form never
runs on a CPU unless forced.  These tests force it (``unroll=True``)
and hold it to the rolled form bitwise, to the other fusion tiers
bitwise, and to the float32 oracle — fused, prefix and per-level, with
and without the saved corners of the training forward, and for the
corner-major ablation walk.

A query step unrolled whole is held to the rolled form within rounding
only: XLA:CPU contracts ``acc + w * row`` into a fused multiply-add in
a loop body, but in a long straight-line body only in part, so the two
forms differ in the last bit of some outputs.  v5e has no such
contraction: its bundles hold separate ``vmul`` and ``vadd`` ops.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import msda_fwd, ops
from repro.kernels.ref import msda_ref

# head_dim 16 puts all 8 heads in one group: a query step gathers
# 3 * 8 * 3 * 4 = 288 rows fused, 96 per level: a rolled point loop in
# every tier.  At 2 points a per-level step (64 rows) is unrolled whole.
LEVELS = ((8, 6), (4, 3), (2, 2))
B, Q, H, D, P = 2, 19, 8, 16, 3
TIERS = {
    "fused": dict(fuse_levels=True),
    "prefix-2": dict(fuse_levels=True, fuse_prefix=2),
    "per-level": {},
}


def _inputs(seed=0, P=P):
    S = sum(h * w for h, w in LEVELS)
    L = len(LEVELS)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    value = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    # straddle the border: masked corners take the same walk
    loc = jax.random.uniform(ks[1], (B, Q, H, L, P, 2), minval=-0.2,
                             maxval=1.2)
    attn = jax.nn.softmax(
        jax.random.normal(ks[2], (B, Q, H, L * P)), axis=-1
    ).reshape(B, Q, H, L, P)
    return value, loc, attn


def _results(tier, train, fuse_gather=True, seed=0, P=P):
    """Forward, and for training plans the full VJP (whose weight grads
    read the forward's saved corners)."""
    params = ops.MSDAParams(
        spatial_shapes=LEVELS, block_q=(8,) * len(LEVELS), interpret=True,
        save_sampled=train, fuse_gather=fuse_gather, **TIERS[tier])
    op = ops.build_kernel_op(params)
    value, loc, attn = _inputs(seed, P)
    out = [op(value, loc, attn)]
    if train:
        out += jax.grad(lambda v, l, a: jnp.sum(op(v, l, a) ** 2),
                        argnums=(0, 1, 2))(value, loc, attn)
    return [np.asarray(x) for x in out]


@pytest.fixture
def chip_form(monkeypatch):
    """Run the gather kernel with the loop structure Mosaic compiles."""
    kernel = msda_fwd._gather_kernel

    def unrolled(*args, **kw):
        return kernel(*args, **{**kw, "unroll": True})

    def force():
        monkeypatch.setattr(msda_fwd, "_gather_kernel", unrolled)

    return force


def test_geometry_takes_both_chip_structures():
    G = msda_fwd.head_group(H, D)
    assert G * P * 4 > msda_fwd.UNROLLED_ROWS  # every tier rolled
    assert G * 2 * 4 <= msda_fwd.UNROLLED_ROWS  # 2 points, one level


@pytest.mark.parametrize("train", [False, True], ids=["infer", "train"])
@pytest.mark.parametrize("tier", sorted(TIERS))
def test_chip_form_matches_rolled_form_and_oracle(tier, train, chip_form):
    rolled = _results(tier, train)
    chip_form()
    chip = _results(tier, train)
    for name, a, b in zip(("out", "grad_value", "grad_loc", "grad_attn"),
                          chip, rolled):
        np.testing.assert_array_equal(a, b, err_msg=name)
    value, loc, attn = _inputs()
    ref = msda_ref(value, LEVELS, loc, attn)
    np.testing.assert_allclose(chip[0], np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("train", [False, True], ids=["infer", "train"])
@pytest.mark.parametrize("tier", ["fused", "prefix-2"])
def test_chip_form_tiers_bitwise(tier, train, chip_form):
    chip_form()
    fused = _results(tier, train, seed=1)
    per_level = _results("per-level", train, seed=1)
    for name, a, b in zip(("out", "grad_value", "grad_loc", "grad_attn"),
                          fused, per_level):
        np.testing.assert_array_equal(a, b, err_msg=name)


@pytest.mark.parametrize("train", [False, True], ids=["infer", "train"])
def test_chip_form_corner_major_walk(train, chip_form):
    rolled = _results("fused", train, fuse_gather=False, seed=2)
    chip_form()
    chip = _results("fused", train, fuse_gather=False, seed=2)
    for name, a, b in zip(("out", "grad_value", "grad_loc", "grad_attn"),
                          chip, rolled):
        np.testing.assert_array_equal(a, b, err_msg=name)
    value, loc, attn = _inputs(seed=2)
    ref = msda_ref(value, LEVELS, loc, attn)
    np.testing.assert_allclose(chip[0], np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("train", [False, True], ids=["infer", "train"])
def test_unrolled_query_step(train, chip_form):
    rolled = _results("per-level", train, seed=3, P=2)
    chip_form()
    chip = _results("per-level", train, seed=3, P=2)
    for name, a, b in zip(("out", "grad_value", "grad_loc", "grad_attn"),
                          chip, rolled):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6, err_msg=name)
    value, loc, attn = _inputs(seed=3, P=2)
    ref = msda_ref(value, LEVELS, loc, attn)
    np.testing.assert_allclose(chip[0], np.asarray(ref), atol=2e-5)
