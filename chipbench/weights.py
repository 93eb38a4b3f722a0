"""Weights of the ``deformable-detr`` family
(``chipbench/families/deformable-detr.py``), made by the benchmark from
the seed, on the device, in one jitted call, in the program's parameter
layout.

The distributions are the registered model's initialisation: LeCun
normal matrices, unit norm scales, zero biases, embeddings at 0.02, and
Deformable DETR's MSDA initialisation (zero offset weights, offset
biases on a ring of radius 1..P per head, attention weights at 0.01 of
LeCun normal).  The run checks the layout against the program's own
parameter shapes before it uses them.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _lecun(key, shape):
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[-2])


def _norm(n, d):
    return {"bias": jnp.zeros((n, d), jnp.float32),
            "scale": jnp.ones((n, d), jnp.float32)}


def _ring(H, L, P):
    theta = jnp.arange(H, dtype=jnp.float32) * (2.0 * math.pi / H)
    grid = jnp.stack([jnp.cos(theta), jnp.sin(theta)], -1)
    grid = grid / jnp.abs(grid).max(-1, keepdims=True)
    grid = jnp.tile(grid[:, None, None], (1, L, P, 1))
    scale = (jnp.arange(P, dtype=jnp.float32) + 1.0)[None, None, :, None]
    return (grid * scale).reshape(-1)


def _msda(key, n, d, H, L, P):
    k = jax.random.split(key, 3)
    hlp = H * L * P
    return {
        "b_offsets": jnp.tile(_ring(H, L, P)[None], (n, 1)),
        "b_weights": jnp.zeros((n, hlp), jnp.float32),
        "out_proj": _lecun(k[0], (n, d, d)),
        "value_proj": _lecun(k[1], (n, d, d)),
        "w_offsets": jnp.zeros((n, d, 2 * hlp), jnp.float32),
        "w_weights": _lecun(k[2], (n, d, hlp)) * 0.01,
    }


def _mlp(key, n, d, ff):
    k = jax.random.split(key, 2)
    return {"wd": _lecun(k[0], (n, ff, d)), "wi": _lecun(k[1], (n, d, ff))}


def init(key, cfg: dict) -> dict:
    d, ff = cfg["d_model"], cfg["d_ff"]
    H, P, L = cfg["num_heads"], cfg["num_points"], len(cfg["levels"])
    ne, nd = cfg["encoder_layers"], cfg["decoder_layers"]
    C, nq = cfg["num_classes"], cfg["num_queries"]
    k = jax.random.split(key, 14)
    return {
        "box_head": {
            "l1": {"b": jnp.zeros((d,), jnp.float32), "w": _lecun(k[0], (d, d))},
            "l2": {"b": jnp.zeros((4,), jnp.float32), "w": _lecun(k[1], (d, 4))},
        },
        "class_head": {"b": jnp.zeros((C,), jnp.float32),
                       "w": _lecun(k[2], (d, C))},
        "dec_layers": {
            "mlp": _mlp(k[3], nd, d, ff),
            "msda": _msda(k[4], nd, d, H, L, P),
            "norm1": _norm(nd, d), "norm2": _norm(nd, d), "norm3": _norm(nd, d),
            "self_attn": {n: _lecun(kk, (nd, d, d)) for n, kk in
                          zip(("wk", "wo", "wq", "wv"), jax.random.split(k[5], 4))},
        },
        "enc_layers": {
            "mlp": _mlp(k[6], ne, d, ff),
            "msda": _msda(k[7], ne, d, H, L, P),
            "norm1": _norm(ne, d), "norm2": _norm(ne, d),
        },
        "final_norm": {"bias": jnp.zeros((d,), jnp.float32),
                       "scale": jnp.ones((d,), jnp.float32)},
        "level_emb": jax.random.normal(k[8], (L, d), jnp.float32) * 0.02,
        "query_emb": jax.random.normal(k[9], (nq, d), jnp.float32) * 0.02,
        "ref_head": {"w": _lecun(k[10], (d, 2))},
    }


def make(seed: int, cfg: dict, served: bool = False) -> dict:
    """Weights for ``seed``, made on the default device in one jitted
    call: float32 master weights for training, or with ``served`` the
    weights as inference serves them, every leaf of two or more axes in
    the configuration's dtype (what the training step computes with) and
    vectors in float32."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 1)
    dt = jnp.dtype(cfg["dtype"])

    def build(k):
        p = init(k, cfg)
        if served:
            p = jax.tree.map(lambda x: x.astype(dt) if x.ndim >= 2 else x, p)
        return p

    return jax.jit(build)(key)
