"""Pure-jnp oracles for multi-scale deformable attention (MSDA).

Two reference paths, mirroring the paper's evaluation targets:

* :func:`msda_ref` — the fused, vectorised oracle (semantics of the MMCV
  CUDA op / the vendor "CANN" kernel).  This is the correctness oracle
  every Pallas kernel is tested against, and the CPU fallback backend.
* :func:`msda_grid_sample_baseline` — the un-fused ``grid_sample``
  composition (MMCV's pure-PyTorch fallback, the paper's "Baseline"
  column in Table 2): one grid-sample per level, stack, weighted sum,
  materialising the ``(B, H*D, Q, L*P)`` intermediate.

Conventions (MMCV ``MultiScaleDeformableAttnFunction``):

* ``value``:              ``(B, S, H, D)`` with ``S = sum_l H_l * W_l``
* ``spatial_shapes``:     static tuple ``((H_0, W_0), ...)``
* ``sampling_locations``: ``(B, Q, H, L, P, 2)`` normalised to ``[0, 1]``,
  last axis ``(x, y)``
* ``attention_weights``:  ``(B, Q, H, L, P)`` (softmaxed over ``L*P``)
* returns                 ``(B, Q, H * D)``

Bilinear sampling follows ``F.grid_sample(align_corners=False,
padding_mode='zeros')``: pixel coords ``px = x * W - 0.5`` and
out-of-bounds corners contribute zero.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

Shapes = Tuple[Tuple[int, int], ...]


def level_sizes(spatial_shapes: Shapes) -> Tuple[int, ...]:
    return tuple(h * w for h, w in spatial_shapes)


def _bilinear_corners(loc_x, loc_y, H, W):
    """Corner indices + weights for grid_sample(align_corners=False).

    Returns (x0, y0, lx, ly) in fp32; callers derive the 4 corners.
    """
    px = loc_x * W - 0.5
    py = loc_y * H - 0.5
    x0 = jnp.floor(px)
    y0 = jnp.floor(py)
    lx = px - x0
    ly = py - y0
    return x0, y0, lx, ly


def _gather_2d(value_l, x, y, H, W):
    """Zero-padded gather: value_l (B,H,HW,D), x/y (B,Q,H,P) int corners."""
    inb = (x >= 0) & (x < W) & (y >= 0) & (y < H)
    xc = jnp.clip(x, 0, W - 1)
    yc = jnp.clip(y, 0, H - 1)
    flat = yc * W + xc  # (B,Q,Hh,P)
    # value_l: (B, Hh, HW, D) -> gather along HW per (B,Hh)
    # indices: (B,Q,Hh,P) -> (B,Hh,Q*P)
    B, Q, Hh, P = flat.shape
    idx = jnp.transpose(flat, (0, 2, 1, 3)).reshape(B, Hh, Q * P)
    out = jnp.take_along_axis(value_l, idx[..., None], axis=2)  # (B,Hh,Q*P,D)
    out = out.reshape(B, Hh, Q, P, -1)
    out = jnp.transpose(out, (0, 2, 1, 3, 4))  # (B,Q,Hh,P,D)
    return out * inb[..., None].astype(out.dtype)


def msda_ref(
    value: jax.Array,
    spatial_shapes: Shapes,
    sampling_locations: jax.Array,
    attention_weights: jax.Array,
    *,
    compute_dtype=jnp.float32,
) -> jax.Array:
    """Fused vectorised MSDA oracle. See module docstring for shapes."""
    B, S, H, D = value.shape
    _, Q, _, L, P, _ = sampling_locations.shape
    assert S == sum(level_sizes(spatial_shapes)), (S, spatial_shapes)
    assert attention_weights.shape == (B, Q, H, L, P)

    out_dtype = value.dtype
    value = value.astype(compute_dtype)
    loc = sampling_locations.astype(compute_dtype)
    attn = attention_weights.astype(compute_dtype)

    # (B, S, H, D) -> (B, H, S, D) once; split per level.
    value_t = jnp.transpose(value, (0, 2, 1, 3))
    out = jnp.zeros((B, Q, H, D), compute_dtype)
    offset = 0
    for l, (Hl, Wl) in enumerate(spatial_shapes):
        hw = Hl * Wl
        value_l = jax.lax.dynamic_slice_in_dim(value_t, offset, hw, axis=2)
        offset += hw
        loc_l = loc[:, :, :, l]  # (B,Q,H,P,2)
        x0f, y0f, lx, ly = _bilinear_corners(loc_l[..., 0], loc_l[..., 1], Hl, Wl)
        x0 = x0f.astype(jnp.int32)
        y0 = y0f.astype(jnp.int32)
        w00 = (1 - lx) * (1 - ly)
        w10 = lx * (1 - ly)
        w01 = (1 - lx) * ly
        w11 = lx * ly
        v00 = _gather_2d(value_l, x0, y0, Hl, Wl)
        v10 = _gather_2d(value_l, x0 + 1, y0, Hl, Wl)
        v01 = _gather_2d(value_l, x0, y0 + 1, Hl, Wl)
        v11 = _gather_2d(value_l, x0 + 1, y0 + 1, Hl, Wl)
        sampled = (
            v00 * w00[..., None]
            + v10 * w10[..., None]
            + v01 * w01[..., None]
            + v11 * w11[..., None]
        )  # (B,Q,H,P,D)
        # HIGHEST: an fp32 oracle on every backend (TPU's default fp32
        # matmul rounds its operands to bf16)
        out = out + jnp.einsum("bqhpd,bqhp->bqhd", sampled, attn[:, :, :, l],
                               precision=jax.lax.Precision.HIGHEST)
    return out.reshape(B, Q, H * D).astype(out_dtype)


# --------------------------------------------------------------------------
# grid_sample + the un-fused baseline
# --------------------------------------------------------------------------


def grid_sample(input_: jax.Array, grid: jax.Array) -> jax.Array:
    """``F.grid_sample(input, grid, align_corners=False, mode='bilinear',
    padding_mode='zeros')``.

    input_: (B, C, H, W); grid: (B, Hg, Wg, 2) in [-1, 1] (x, y).
    returns (B, C, Hg, Wg).
    """
    B, C, H, W = input_.shape
    gx = (grid[..., 0] + 1.0) * 0.5  # -> [0,1]
    gy = (grid[..., 1] + 1.0) * 0.5
    x0f, y0f, lx, ly = _bilinear_corners(gx, gy, H, W)
    x0 = x0f.astype(jnp.int32)
    y0 = y0f.astype(jnp.int32)

    def corner(xi, yi):
        inb = (xi >= 0) & (xi < W) & (yi >= 0) & (yi < H)
        xc = jnp.clip(xi, 0, W - 1)
        yc = jnp.clip(yi, 0, H - 1)
        flat = (yc * W + xc).reshape(B, -1)  # (B, Hg*Wg)
        v = jnp.take_along_axis(
            input_.reshape(B, C, H * W), flat[:, None, :], axis=2
        )  # (B, C, Hg*Wg)
        return v * inb.reshape(B, 1, -1).astype(v.dtype)

    v00 = corner(x0, y0)
    v10 = corner(x0 + 1, y0)
    v01 = corner(x0, y0 + 1)
    v11 = corner(x0 + 1, y0 + 1)
    w00 = ((1 - lx) * (1 - ly)).reshape(B, 1, -1)
    w10 = (lx * (1 - ly)).reshape(B, 1, -1)
    w01 = ((1 - lx) * ly).reshape(B, 1, -1)
    w11 = (lx * ly).reshape(B, 1, -1)
    out = v00 * w00 + v10 * w10 + v01 * w01 + v11 * w11
    Hg, Wg = grid.shape[1], grid.shape[2]
    return out.reshape(B, C, Hg, Wg)


def msda_grid_sample_baseline(
    value: jax.Array,
    spatial_shapes: Shapes,
    sampling_locations: jax.Array,
    attention_weights: jax.Array,
) -> jax.Array:
    """The paper's "Baseline": MMCV's pure grid-sample composition.

    Materialises per-level sampled tensors and a (B*H, D, Q, L*P)
    intermediate — the memory-traffic-heavy path the paper beats.
    """
    B, S, H, D = value.shape
    _, Q, _, L, P, _ = sampling_locations.shape
    dtype = jnp.float32
    value = value.astype(dtype)
    sizes = level_sizes(spatial_shapes)
    # split per level: list of (B, H*D? ...) -> (B*H, D, Hl, Wl)
    offs = 0
    sampled_all = []
    grids = 2.0 * sampling_locations.astype(dtype) - 1.0  # (B,Q,H,L,P,2)
    for l, (Hl, Wl) in enumerate(spatial_shapes):
        v_l = jax.lax.dynamic_slice_in_dim(value, offs, sizes[l], axis=1)
        offs += sizes[l]
        v_l = jnp.transpose(v_l, (0, 2, 3, 1)).reshape(B * H, D, Hl, Wl)
        g_l = jnp.transpose(grids[:, :, :, l], (0, 2, 1, 3, 4)).reshape(B * H, Q, P, 2)
        sampled = grid_sample(v_l, g_l)  # (B*H, D, Q, P)
        sampled_all.append(sampled)
    stacked = jnp.stack(sampled_all, axis=-2)  # (B*H, D, Q, L, P)
    stacked = stacked.reshape(B * H, D, Q, L * P)
    attn = jnp.transpose(attention_weights.astype(dtype), (0, 2, 1, 3, 4))
    attn = attn.reshape(B * H, 1, Q, L * P)
    out = (stacked * attn).sum(-1)  # (B*H, D, Q)
    out = out.reshape(B, H, D, Q)
    out = jnp.transpose(out, (0, 3, 1, 2)).reshape(B, Q, H * D)
    return out.astype(value.dtype)
