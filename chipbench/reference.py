"""Plain Deformable-DETR in float32: the yardstick that decides `correct`
for the ``deformable-detr`` family (``chipbench/families/deformable-detr.py``).

Written from the layer equations alone; it imports nothing of the
program.  Parameters come in the program's tree layout (dicts named as
the program names them, layers stacked on a leading axis), made by the
benchmark from the seed.

Model, as the registered configuration defines it:

* encoder layer (pre-norm): ``x += MSDA(LN1(x)); x += FFN(LN2(x))`` where
  every pyramid pixel is a query and its reference point is its own
  centre; ``x`` starts as the pyramid plus a per-level embedding;
* decoder layer (pre-norm): ``q += SelfAttn(LN1(q)); q += MSDA(LN2(q),
  memory); q += FFN(LN3(q))`` with 300 learned queries whose reference
  points are ``sigmoid(query_emb @ ref_head)``; then a final LN, a
  linear class head and a two-layer box MLP with a sigmoid;
* MSDA: value projection, sampling offsets normalised by each level's
  (W, H), attention weights softmaxed over levels x points, bilinear
  sampling as ``grid_sample(align_corners=False, padding_mode='zeros')``
  (pixel ``x * W - 0.5``; corners off the map read zero), output
  projection;
* FFN: ``gelu_tanh(x @ wi) @ wd``; self-attention: 8 heads of 32,
  no biases, softmax(q k^T / sqrt(32));
* loss: greedy bipartite matching on ``-log p(label) + 5 * L1(box)``
  (repeatedly the cheapest free pair), matched NLL + 5 * L1 over the
  valid targets, plus class 0 as background for every unmatched query,
  averaged over queries; the mean over the batch;
* optimiser: AdamW (b1 0.9, b2 0.95, eps 1e-8), global-norm clipping,
  weight decay on every leaf of two or more axes, warmup-cosine rate.

Departures from arXiv:2010.04159, all of them the registered
configuration's: GELU in place of ReLU, greedy in place of Hungarian
matching, no focal loss or GIoU term, no auxiliary decoder losses, no
iterative box refinement, pre-norm layers, and no positional encoding
beyond the level embedding.

``Precision`` says how operands are rounded before each matrix product
and each MSDA call: the reference keeps float32 (products at
``Precision.HIGHEST``); the control rounds them to float8 (e4m3), the
step below the configuration's bfloat16.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.95, 1e-8
MATCH_L1_WEIGHT = 5.0


@dataclass(frozen=True)
class Precision:
    """Operand rounding: ``None`` keeps float32.  A rounded operand passes
    its cotangent through unrounded (straight-through), so the control
    computes its forward pass in the lower precision and its gradients
    from that forward pass."""

    round_to: Optional[str] = None

    def __call__(self, x):
        x = x.astype(jnp.float32)
        if self.round_to is None:
            return x
        dt = jnp.dtype(self.round_to)
        lim = float(jnp.finfo(dt).max)
        low = jnp.clip(x, -lim, lim).astype(dt).astype(jnp.float32)
        return x + jax.lax.stop_gradient(low - x)


FLOAT32 = Precision()
FLOAT8 = Precision("float8_e4m3fn")


def mm(a, b, pr: Precision):
    return jnp.matmul(pr(a), pr(b), precision=HIGHEST)


def layer_norm(p, x, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def ffn(p, x, pr):
    return mm(gelu_tanh(mm(x, p["wi"], pr)), p["wd"], pr)


def level_ref_points(levels) -> jax.Array:
    """(x, y) centre of every pixel of every level, normalised: (S, 2)."""
    out = []
    for h, w in levels:
        ys = (jnp.arange(h, dtype=jnp.float32) + 0.5) / h
        xs = (jnp.arange(w, dtype=jnp.float32) + 0.5) / w
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        out.append(jnp.stack([gx, gy], -1).reshape(h * w, 2))
    return jnp.concatenate(out, axis=0)


def _sample_level(value_l, h, w, loc_l, attn_l):
    """Bilinear samples of one level, weighted and summed over points.

    value_l (B, Hh, h*w, D); loc_l (B, Q, Hh, P, 2); attn_l (B, Q, Hh, P)
    -> (B, Q, Hh, D).  Each corner is one row gather; a corner off the
    map reads zero.
    """
    B, Q, Hh, P = attn_l.shape
    px = loc_l[..., 0] * w - 0.5
    py = loc_l[..., 1] * h - 0.5
    x0 = jnp.floor(px)
    y0 = jnp.floor(py)
    lx, ly = px - x0, py - y0
    x0 = x0.astype(jnp.int32)
    y0 = y0.astype(jnp.int32)
    out = 0.0
    for dx, dy, wt in ((0, 0, (1 - lx) * (1 - ly)), (1, 0, lx * (1 - ly)),
                       (0, 1, (1 - lx) * ly), (1, 1, lx * ly)):
        xi, yi = x0 + dx, y0 + dy
        inside = (xi >= 0) & (xi < w) & (yi >= 0) & (yi < h)
        flat = jnp.clip(yi, 0, h - 1) * w + jnp.clip(xi, 0, w - 1)
        idx = jnp.transpose(flat, (0, 2, 1, 3)).reshape(B, Hh, Q * P)
        rows = jnp.take_along_axis(value_l, idx[..., None], axis=2)
        rows = jnp.transpose(rows.reshape(B, Hh, Q, P, -1), (0, 2, 1, 3, 4))
        cw = wt * inside * attn_l  # (B, Q, Hh, P)
        out = out + jnp.sum(rows * cw[..., None], axis=3)
    return out


def msda(value, levels, loc, attn, *, block_q: int = 4096):
    """Multi-scale deformable attention, in blocks of queries.

    value (B, S, Hh, D); loc (B, Q, Hh, L, P, 2) normalised (x, y);
    attn (B, Q, Hh, L, P) -> (B, Q, Hh * D).  Each block is
    rematerialised, so a VJP holds one block's corner rows at a time.
    """
    B, S, Hh, D = value.shape
    Q = loc.shape[1]
    vt = jnp.transpose(value, (0, 2, 1, 3))
    starts = [0]
    for h, w in levels:
        starts.append(starts[-1] + h * w)

    def block(args):
        loc_b, attn_b = args
        acc = 0.0
        for l, (h, w) in enumerate(levels):
            vl = vt[:, :, starts[l]:starts[l + 1]]
            acc = acc + _sample_level(vl, h, w, loc_b[:, :, :, l],
                                      attn_b[:, :, :, l])
        return acc

    bq = min(block_q, Q)
    nb = -(-Q // bq)
    pad = nb * bq - Q
    loc_p = jnp.pad(loc, ((0, 0), (0, pad)) + ((0, 0),) * 4)
    attn_p = jnp.pad(attn, ((0, 0), (0, pad)) + ((0, 0),) * 3)
    loc_p = jnp.moveaxis(loc_p.reshape(B, nb, bq, *loc.shape[2:]), 1, 0)
    attn_p = jnp.moveaxis(attn_p.reshape(B, nb, bq, *attn.shape[2:]), 1, 0)
    out = jax.lax.map(jax.checkpoint(block), (loc_p, attn_p))
    out = jnp.moveaxis(out, 0, 1).reshape(B, nb * bq, Hh, D)[:, :Q]
    return out.reshape(B, Q, Hh * D)


def msda_module(p, cfg, query, value_feats, refs, pr: Precision, block_q):
    levels = cfg["levels"]
    L, Hh, P = len(levels), cfg["num_heads"], cfg["num_points"]
    B, Q, d = query.shape
    D = d // Hh
    value = mm(value_feats, p["value_proj"], pr).reshape(B, -1, Hh, D)
    off = (mm(query, p["w_offsets"], pr) + p["b_offsets"]).reshape(
        B, Q, Hh, L, P, 2)
    wh = jnp.asarray([[w, h] for h, w in levels], jnp.float32)
    loc = refs[:, :, None, None, None, :] + off / wh[None, None, None, :, None, :]
    aw = mm(query, p["w_weights"], pr) + p["b_weights"]
    aw = jax.nn.softmax(aw.reshape(B, Q, Hh, L * P), axis=-1).reshape(
        B, Q, Hh, L, P)
    out = msda(pr(value), levels, loc, pr(aw), block_q=block_q)
    return mm(out, p["out_proj"], pr)


def self_attention(p, cfg, x, pr: Precision):
    B, T, d = x.shape
    Hh = cfg["num_heads"]
    hd = d // Hh
    q = mm(x, p["wq"], pr).reshape(B, T, Hh, hd)
    k = mm(x, p["wk"], pr).reshape(B, T, Hh, hd)
    v = mm(x, p["wv"], pr).reshape(B, T, Hh, hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", pr(q), pr(k), precision=HIGHEST)
    w = jax.nn.softmax(s / math.sqrt(hd), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", pr(w), pr(v), precision=HIGHEST)
    return mm(o.reshape(B, T, d), p["wo"], pr)


def encode(params, cfg, pyramid, pr: Precision, block_q: int):
    levels = cfg["levels"]
    eps = cfg["norm_eps"]
    emb = jnp.concatenate([
        jnp.broadcast_to(params["level_emb"][i], (h * w, cfg["d_model"]))
        for i, (h, w) in enumerate(levels)], axis=0)
    x = pyramid.astype(jnp.float32) + emb[None]
    refs = jnp.broadcast_to(level_ref_points(levels)[None],
                            (x.shape[0], x.shape[1], 2))

    @jax.checkpoint
    def layer(x, lp):
        h = layer_norm(lp["norm1"], x, eps)
        x = x + msda_module(lp["msda"], cfg, h, h, refs, pr, block_q)
        x = x + ffn(lp["mlp"], layer_norm(lp["norm2"], x, eps), pr)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["enc_layers"])
    return x


def decode(params, cfg, memory, pr: Precision, block_q: int):
    eps = cfg["norm_eps"]
    B = memory.shape[0]
    nq = cfg["num_queries"]
    q = jnp.broadcast_to(params["query_emb"][None], (B, nq, cfg["d_model"]))
    refs = jax.nn.sigmoid(mm(params["query_emb"], params["ref_head"]["w"], pr))
    refs = jnp.broadcast_to(refs[None], (B, nq, 2))

    def layer(q, lp):
        q = q + self_attention(lp["self_attn"], cfg,
                               layer_norm(lp["norm1"], q, eps), pr)
        q = q + msda_module(lp["msda"], cfg, layer_norm(lp["norm2"], q, eps),
                            memory, refs, pr, block_q)
        q = q + ffn(lp["mlp"], layer_norm(lp["norm3"], q, eps), pr)
        return q, None

    q, _ = jax.lax.scan(layer, q, params["dec_layers"])
    q = layer_norm(params["final_norm"], q, eps)
    logits = mm(q, params["class_head"]["w"], pr) + params["class_head"]["b"]
    bh = params["box_head"]
    b = gelu_tanh(mm(q, bh["l1"]["w"], pr) + bh["l1"]["b"])
    boxes = jax.nn.sigmoid(mm(b, bh["l2"]["w"], pr) + bh["l2"]["b"])
    return logits, boxes


def forward(params, cfg, pyramid, pr: Precision = FLOAT32,
            block_q: int = 4096):
    """pyramid (B, S, d) -> (class logits (B, 300, C), boxes (B, 300, 4))."""
    return decode(params, cfg, encode(params, cfg, pyramid, pr, block_q), pr,
                  block_q)


def greedy_match(cost):
    """cost (Q, T) -> for each target, a distinct query: repeatedly the
    cheapest free pair."""
    Q, T = cost.shape

    def body(_, state):
        c, assign = state
        flat = jnp.argmin(c)
        qi, ti = flat // T, flat % T
        return (c.at[qi, :].set(jnp.inf).at[:, ti].set(jnp.inf),
                assign.at[ti].set(qi))

    _, assign = jax.lax.fori_loop(0, T, body,
                                  (cost, jnp.zeros((T,), jnp.int32)))
    return assign


def detection_loss(logits, boxes, labels, gt_boxes):
    logp = jax.nn.log_softmax(logits, axis=-1)

    def one(lp, bx, lab, gbx):
        valid = lab >= 0
        lab_c = jnp.maximum(lab, 0)
        cost = -lp[:, lab_c] + MATCH_L1_WEIGHT * jnp.abs(
            bx[:, None, :] - gbx[None, :, :]).sum(-1)
        cost = jnp.where(valid[None, :], cost, jnp.inf)
        assign = jax.lax.stop_gradient(greedy_match(jax.lax.stop_gradient(cost)))
        nll = -lp[assign, lab_c] * valid
        l1 = jnp.abs(bx[assign] - gbx).sum(-1) * valid
        matched = jnp.zeros((lp.shape[0],), bool).at[assign].set(valid)
        bg = -lp[:, 0] * (~matched)
        denom = jnp.maximum(valid.sum(), 1)
        return (nll.sum() + MATCH_L1_WEIGHT * l1.sum()) / denom + bg.mean()

    return jax.vmap(one)(logp, boxes, labels, gt_boxes).mean()


def loss(params, cfg, batch, pr: Precision = FLOAT32, block_q: int = 4096):
    logits, boxes = forward(params, cfg, batch["pyramid"], pr, block_q)
    return detection_loss(logits, boxes, batch["labels"],
                          batch["boxes"].astype(jnp.float32))


def learning_rate(step, opt):
    step = jnp.asarray(step, jnp.float32)
    peak, warm, total = opt["peak_lr"], opt["warmup_steps"], opt["total_steps"]
    warm_lr = peak * step / max(warm, 1)
    prog = jnp.clip((step - warm) / max(total - warm, 1), 0.0, 1.0)
    cos = peak * (0.1 + 0.9 * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
    return jnp.where(step < warm, warm_lr, cos)


def adamw(params, grads, m, v, count, step, opt):
    """One AdamW update; returns (params, m, v, clipped grads)."""
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    count = count + 1
    c1 = 1.0 - ADAM_B1 ** count
    c2 = 1.0 - ADAM_B2 ** count
    lr = learning_rate(step, opt)
    g = jax.tree.map(lambda x: x * scale, grads)
    m = jax.tree.map(lambda a, b: ADAM_B1 * a + (1 - ADAM_B1) * b, m, g)
    v = jax.tree.map(lambda a, b: ADAM_B2 * a + (1 - ADAM_B2) * b * b, v, g)

    def upd(p, mm_, vv):
        wd = opt["weight_decay"] if p.ndim >= 2 else 0.0
        return p - lr * ((mm_ / c1) / (jnp.sqrt(vv / c2) + ADAM_EPS) + wd * p)

    return jax.tree.map(upd, params, m, v), m, v, g


def train_steps(params, cfg, batches: Sequence[dict], opt,
                pr: Precision = FLOAT32, block_q: int = 4096):
    """Run ``len(batches)`` reference steps from step 0.

    Returns (losses, the first step's gradients, final params).
    Un-jitted: the caller jits one step at a time so that a step's
    activations are freed before the next.
    """
    step_fn = jitted(_one_step, cfg, opt, pr, block_q)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    count = jnp.zeros((), jnp.float32)
    losses, first_grads = [], None
    for b in batches:
        params, m, v, count, lval, g = step_fn(params, m, v, count, b)
        losses.append(lval)
        if first_grads is None:
            first_grads = g
    return losses, first_grads, params


_JITTED = {}


def jitted(fn, cfg, opt, pr, block_q):
    """``fn`` jitted with its configuration bound, once per process and
    configuration (fn(*arrays, cfg, opt, pr, block_q))."""
    key = (fn.__name__, json.dumps(cfg, sort_keys=True),
           json.dumps(opt, sort_keys=True), pr, block_q)
    if key not in _JITTED:
        _JITTED[key] = jax.jit(lambda *a: fn(*a, cfg, opt, pr, block_q))
    return _JITTED[key]


def forward_fn(params, pyramid, cfg, opt, pr, block_q):
    del opt
    return forward(params, cfg, pyramid, pr, block_q)


def _one_step(params, m, v, count, batch, cfg, opt, pr, block_q):
    lval, grads = jax.value_and_grad(loss)(params, cfg, batch, pr, block_q)
    params, m, v, _ = adamw(params, grads, m, v, count, count, opt)
    return params, m, v, count + 1, lval, grads
