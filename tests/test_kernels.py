"""MSDA Pallas kernel vs pure-jnp oracle: shape/dtype sweeps + grads.

Every Pallas kernel cell runs in interpret mode (the kernel body
executes in Python on CPU) against ``ref.msda_ref``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import msda_fwd, ops
from repro.kernels.ref import msda_grid_sample_baseline, msda_ref

CASES = [
    # (B, Q, H, D, P, levels)
    (1, 8, 1, 8, 1, ((4, 4),)),
    (2, 21, 2, 8, 3, ((10, 6), (5, 3))),
    (1, 40, 4, 16, 4, ((16, 16), (8, 8), (4, 4))),
    (3, 7, 2, 32, 2, ((9, 13),)),
    (1, 100, 8, 8, 4, ((12, 12), (6, 6))),
]


def _inputs(B, Q, H, D, P, levels, dtype=jnp.float32, seed=0):
    S = sum(h * w for h, w in levels)
    L = len(levels)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    value = jax.random.normal(ks[0], (B, S, H, D), jnp.float32).astype(dtype)
    loc = jax.random.uniform(ks[1], (B, Q, H, L, P, 2), minval=-0.3, maxval=1.3)
    attn = jax.nn.softmax(
        jax.random.normal(ks[2], (B, Q, H, L, P)).reshape(B, Q, H, -1)
    ).reshape(B, Q, H, L, P)
    gout = jax.random.normal(ks[3], (B, Q, H * D), jnp.float32)
    return value, loc, attn, gout


def _saved_corners(levels, value, loc, attn):
    """The training forward's (out, saved corners) straight from the
    gather kernel: (B, Q, H*D) and (B, NG, Qp*L*4P, G*D)."""
    B, S, H, D = value.shape
    Q, P = loc.shape[1], loc.shape[4]
    G = msda_fwd.head_group(H, D)
    p = ops.MSDAParams(spatial_shapes=levels, block_q=8, interpret=True,
                       save_sampled=True)
    idx, wrows, _ = ops._corner_tables(p, loc, attn, D)
    value_g = jnp.transpose(value.reshape(B, S, H // G, G * D), (0, 2, 1, 3))
    slab = ops._pack_pyramid(value_g, levels, p.level_dtypes(value.dtype))
    geoms, _ = ops._pyramid_geometry(p)
    out, saved = msda_fwd.msda_gather(
        slab, idx, wrows, levels=geoms, num_points=P, head_dim=D, block_q=8,
        save_dtype=jnp.float32, interpret=True)
    out = jnp.transpose(out[:, :, :Q], (0, 2, 1, 3)).reshape(B, Q, H * D)
    return np.asarray(out), np.asarray(saved)


def _check_saved_corners(levels, value, loc, saved):
    """Every corner a weight can reach (inside its level) holds that
    pixel's value, in its saved slot."""
    B, S, H, D = value.shape
    Q, L, P = loc.shape[1], loc.shape[3], loc.shape[4]
    G = msda_fwd.head_group(H, D)
    K = L * 4 * P
    value, loc = np.asarray(value), np.asarray(loc)
    offs = np.cumsum([0] + [h * w for h, w in levels])
    for l, (hh, ww) in enumerate(levels):
        x0 = np.floor(loc[:, :, :, l, :, 0] * ww - 0.5).astype(int)
        y0 = np.floor(loc[:, :, :, l, :, 1] * hh - 0.5).astype(int)
        for b, q, h, pt in np.ndindex(B, Q, H, P):
            for c in range(4):
                x, y = x0[b, q, h, pt] + (c & 1), y0[b, q, h, pt] + (c >> 1)
                if not (0 <= x < ww and 0 <= y < hh):
                    continue
                row = q * K + msda_fwd.saved_slot(l, c, pt, P)
                got = saved[b, h // G, row, (h % G) * D:(h % G + 1) * D]
                np.testing.assert_array_equal(
                    got, value[b, offs[l] + y * ww + x, h],
                    err_msg=f"corner {(b, q, h, l, pt, c)}")


@pytest.mark.parametrize("case", CASES, ids=[str(c[:5]) for c in CASES])
@pytest.mark.parametrize("train", [False, True], ids=["infer", "train"])
def test_fwd_matches_oracle(case, train):
    """Inference forward, and the training forward whose saved corners
    must be the pixels the oracle samples."""
    B, Q, H, D, P, levels = case
    value, loc, attn, _ = _inputs(B, Q, H, D, P, levels)
    ref = msda_ref(value, levels, loc, attn)
    if train:
        out, saved = _saved_corners(levels, value, loc, attn)
        _check_saved_corners(levels, value, loc, saved)
    else:
        out = ops.msda(value, levels, loc, attn, backend="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
def test_fwd_dtypes(dtype):
    B, Q, H, D, P, levels = 2, 16, 2, 8, 2, ((8, 8), (4, 4))
    value, loc, attn, _ = _inputs(B, Q, H, D, P, levels, dtype=dtype)
    ref = msda_ref(value, levels, loc, attn)
    out = ops.msda(value, levels, loc, attn, backend="pallas")
    assert out.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


@pytest.mark.parametrize("train", [False, True], ids=["regather", "saved"])
@pytest.mark.parametrize("case", CASES[:3], ids=[str(c[:5]) for c in CASES[:3]])
def test_grads_match_oracle(case, train):
    B, Q, H, D, P, levels = case
    value, loc, attn, gout = _inputs(B, Q, H, D, P, levels)

    def loss_ref(v, l, a):
        return jnp.vdot(msda_ref(v, levels, l, a), gout)

    def loss_pal(v, l, a):
        return jnp.vdot(
            ops.msda(v, levels, l, a, backend="pallas", train=train), gout
        )

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(value, loc, attn)
    g_pal = jax.grad(loss_pal, argnums=(0, 1, 2))(value, loc, attn)
    for name, gr, gp in zip(("value", "loc", "attn"), g_ref, g_pal):
        np.testing.assert_allclose(
            np.asarray(gp), np.asarray(gr), atol=5e-4, rtol=5e-4,
            err_msg=f"grad_{name}",
        )


def test_baseline_equals_oracle():
    B, Q, H, D, P, levels = 2, 33, 4, 8, 3, ((14, 9), (7, 5), (3, 3))
    value, loc, attn, _ = _inputs(B, Q, H, D, P, levels)
    a = msda_ref(value, levels, loc, attn)
    b = msda_grid_sample_baseline(value, levels, loc, attn)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_all_oob_is_zero():
    B, Q, H, D, P, levels = 1, 4, 1, 8, 2, ((6, 6),)
    value, _, attn, _ = _inputs(B, Q, H, D, P, levels)
    loc = jnp.full((B, Q, H, 1, P, 2), -3.0)  # far outside
    out = ops.msda(value, levels, loc, attn, backend="pallas")
    np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-6)


def test_pixel_center_exactness():
    """Sampling exactly at pixel centers returns the pixel values."""
    H_, W_ = 5, 7
    B, Q, Hh, D, P = 1, H_ * W_, 1, 4, 1
    levels = ((H_, W_),)
    value = jax.random.normal(jax.random.PRNGKey(0), (B, H_ * W_, Hh, D))
    ys, xs = jnp.meshgrid(jnp.arange(H_), jnp.arange(W_), indexing="ij")
    loc = jnp.stack([(xs.reshape(-1) + 0.5) / W_, (ys.reshape(-1) + 0.5) / H_], -1)
    loc = loc[None, :, None, None, None, :]
    attn = jnp.ones((B, Q, Hh, 1, P))
    out = ops.msda(value, levels, loc, attn, backend="pallas")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(value[:, :, 0, :]), atol=1e-5
    )


def test_plan_blocks_adaptive():
    """Adaptive block planning: smaller pyramids get wider blocks (paper
    Fig. 7)."""
    big = ops.plan_blocks(((256, 256), (16, 16)), 4, 32, 4096)
    small = ops.plan_blocks(((16, 16),), 4, 32, 4096)
    assert small > big


def test_block_q_invariance():
    """Output must not depend on the block size (pure tiling)."""
    B, Q, H, D, P, levels = 1, 24, 2, 8, 2, ((8, 8), (4, 4))
    value, loc, attn, _ = _inputs(B, Q, H, D, P, levels)
    o1 = ops.msda(value, levels, loc, attn, backend="pallas", block_q=(8, 8))
    o2 = ops.msda(value, levels, loc, attn, backend="pallas", block_q=(24, 24))
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)
