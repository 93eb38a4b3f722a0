"""Plain BEVFormer-base inference in float32: the yardstick that decides
`correct` for the program's ``repro.models.bevformer``.

Written from upstream's layer equations (arXiv:2203.17270,
``projects/mmdet3d_plugin/bevformer`` of
github.com/fundamentalvision/BEVFormer); it imports nothing of the
program.  It takes the configuration as a plain dict (the keys of
``chipbench/configs/bevformer-base.json``) and the parameters in the
program's tree layout, made from a seed.

What it does its own way:

* matrix products in float32 at ``Precision.HIGHEST``;
* bilinear sampling as ``grid_sample(align_corners=False,
  padding_mode='zeros')``: pixel ``x * W - 0.5``, a corner off the map
  reads zero (tested in floating point before any index is formed);
* the camera mask: every anchor of every pillar projected through each
  camera in float32, and only the hit (query, camera) pairs evaluated,
  camera by camera in blocks of queries, with no static bound; the
  cameras' outputs summed into the queries in camera order;
* the previous BEV rotated as torchvision's ``rotate`` does it: an affine
  grid over the normalised output, ``grid_sample`` with nearest
  neighbour (round half to even) and zero fill;
* its own BEV carried over the frames of a sequence.

``Precision`` says how operands are rounded before each matrix product
and each sampling: the reference keeps float32; the control rounds them
to float8 (e4m3), the step below the configuration's bfloat16.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-5


@dataclass(frozen=True)
class Precision:
    """Operand rounding: ``None`` keeps float32."""

    round_to: Optional[str] = None

    def __call__(self, x):
        x = x.astype(jnp.float32)
        if self.round_to is None:
            return x
        dt = jnp.dtype(self.round_to)
        lim = float(jnp.finfo(dt).max)
        return jnp.clip(x, -lim, lim).astype(dt).astype(jnp.float32)


FLOAT32 = Precision()
FLOAT8 = Precision("float8_e4m3fn")


def mm(a, b, pr: Precision):
    return jnp.matmul(pr(a), pr(b), precision=HIGHEST)


def linear(p, x, pr: Precision):
    return mm(x, p["w"], pr) + p["b"].astype(jnp.float32)


def layer_norm(p, x, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return ((x - mu) / jnp.sqrt(var + eps) * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32))


def relu(x):
    return jnp.maximum(x, 0.0)


def ffn(p, x, pr):
    return linear(p["l2"], relu(linear(p["l1"], x, pr)), pr)


# --------------------------------------------------------------------------
# deformable sampling
# --------------------------------------------------------------------------


def sample_level(value, h, w, loc, attn):
    """One level's bilinear samples, weighted and summed over points.

    value (h*w, Hh, D); loc (n, Hh, P, 2) normalised (x, y); attn
    (n, Hh, P) -> (n, Hh, D)."""
    px = loc[..., 0] * w - 0.5
    py = loc[..., 1] * h - 0.5
    x0, y0 = jnp.floor(px), jnp.floor(py)
    lx, ly = px - x0, py - y0
    heads = jnp.arange(value.shape[1])[None, :, None]
    out = 0.0
    for dx, dy, wt in ((0, 0, (1 - lx) * (1 - ly)), (1, 0, lx * (1 - ly)),
                       (0, 1, (1 - lx) * ly), (1, 1, lx * ly)):
        xi, yi = x0 + dx, y0 + dy
        inside = (xi >= 0) & (xi <= w - 1) & (yi >= 0) & (yi <= h - 1)
        row = (jnp.clip(yi, 0, h - 1) * w + jnp.clip(xi, 0, w - 1)).astype(jnp.int32)
        rows = value[row, heads]  # (n, Hh, P, D)
        out = out + jnp.sum(rows * (wt * inside * attn)[..., None], axis=2)
    return out


def deformable(value, levels, loc, attn, pr: Precision, block: int = 2048):
    """MSDA over blocks of queries.  value (S, Hh, D); loc (n, Hh, L, P, 2);
    attn (n, Hh, L, P) -> (n, Hh * D)."""
    n, Hh = attn.shape[:2]
    value, attn = pr(value), pr(attn)
    starts = np.cumsum([0] + [h * w for h, w in levels])

    def one(args):
        lb, ab = args
        return sum(sample_level(value[starts[l]:starts[l + 1]], h, w,
                                lb[:, :, l], ab[:, :, l])
                   for l, (h, w) in enumerate(levels))

    bq = min(block, n)
    nb = -(-n // bq)
    pad = nb * bq - n
    lb = jnp.pad(loc, ((0, pad),) + ((0, 0),) * 4).reshape(nb, bq, *loc.shape[1:])
    ab = jnp.pad(attn, ((0, pad),) + ((0, 0),) * 3).reshape(nb, bq, *attn.shape[1:])
    out = jax.lax.map(one, (lb, ab)).reshape(nb * bq, Hh, -1)[:n]
    return out.reshape(n, -1)


def msda_offsets(p, cfg, query, n_levels, P, pr):
    """Sampling offsets (n, Hh, L, P, 2), in level pixels, and softmaxed
    attention weights (n, Hh, L, P) of one MSDA module."""
    n, Hh = query.shape[0], cfg["num_heads"]
    off = linear(p["offsets"], query, pr).reshape(n, Hh, n_levels, P, 2)
    aw = linear(p["weights"], query, pr).reshape(n, Hh, n_levels * P)
    aw = jax.nn.softmax(aw, axis=-1).reshape(n, Hh, n_levels, P)
    return off, aw


# --------------------------------------------------------------------------
# geometry
# --------------------------------------------------------------------------


def anchors(cfg) -> np.ndarray:
    """(Z, H*W, 3) pillar anchors in metres, float32."""
    pc, n = cfg["pc_range"], cfg["num_points_in_pillar"]
    H, W = cfg["bev_h"], cfg["bev_w"]
    zlen = pc[5] - pc[2]
    zs = np.linspace(0.5, zlen - 0.5, n) / zlen
    i, j = np.meshgrid(np.arange(H), np.arange(W), indexing="ij")
    x = (j.reshape(-1) + 0.5) / W * (pc[3] - pc[0]) + pc[0]
    y = (i.reshape(-1) + 0.5) / H * (pc[4] - pc[1]) + pc[1]
    out = np.empty((n, H * W, 3))
    out[..., 0], out[..., 1] = x[None], y[None]
    out[..., 2] = (zs * zlen + pc[2])[:, None]
    return out.astype(np.float32)


def camera_points(cfg, lidar2img):
    """``(uv (N, Q, Z, 2), mask (N, Q, Z))`` of every anchor in every
    camera, in float32; mask: depth > 1e-5, image coordinates strictly
    inside (0, 1) of ``image_size``."""
    pts = jnp.asarray(anchors(cfg))  # (Z, Q, 3)
    hom = jnp.concatenate([pts, jnp.ones_like(pts[..., :1])], -1)
    m = jnp.asarray(lidar2img, jnp.float32)
    cam = sum(m[:, None, None, :, k] * hom[None, :, :, None, k] for k in range(4))
    depth = cam[..., 2]
    xy = cam[..., :2] / jnp.maximum(depth, EPS)[..., None]
    h, w = cfg["image_size"]
    u, v = xy[..., 0] / w, xy[..., 1] / h
    mask = (depth > EPS) & (v > 0.0) & (v < 1.0) & (u < 1.0) & (u > 0.0)
    uv = jnp.stack([u, v], -1)
    return jnp.transpose(uv, (0, 2, 1, 3)), jnp.transpose(mask, (0, 2, 1))


def rotate(cfg, bev, angle_deg):
    """torchvision ``rotate(img, angle, center=grid centre)``, nearest
    neighbour, zero fill, of a (H*W, d) BEV."""
    H, W = cfg["bev_h"], cfg["bev_w"]
    # torchvision's inverse affine matrix with no translation, scale 1,
    # no shear, about the centre: [[cos, sin, 0], [-sin, cos, 0]] of
    # -angle; its trigonometry in float32 (torchvision's is in float64
    # before the matrix is rounded to float32), as a program that takes
    # the angle on the device has it, so that both resolve a source on a
    # rounding tie alike
    a = -jnp.asarray(angle_deg, jnp.float32) * jnp.float32(math.pi / 180.0)
    c, s = jnp.cos(a), jnp.sin(a)
    theta = jnp.stack([jnp.stack([c, s, 0.0 * c]), jnp.stack([-s, c, 0.0 * c])])
    xs = jnp.linspace(-W * 0.5 + 0.5, W * 0.5 - 0.5, W, dtype=jnp.float32)
    ys = jnp.linspace(-H * 0.5 + 0.5, H * 0.5 - 0.5, H, dtype=jnp.float32)
    base = jnp.stack([jnp.broadcast_to(xs[None], (H, W)),
                      jnp.broadcast_to(ys[:, None], (H, W)),
                      jnp.ones((H, W), jnp.float32)], -1)
    scaled = theta.T / jnp.asarray([0.5 * W, 0.5 * H], jnp.float32)
    grid = base[..., 0:1] * scaled[0] + base[..., 1:2] * scaled[1] + base[..., 2:3] * scaled[2]
    ix = jnp.round(((grid[..., 0] + 1) * W - 1) / 2)
    iy = jnp.round(((grid[..., 1] + 1) * H - 1) / 2)
    ok = (ix >= 0) & (ix <= W - 1) & (iy >= 0) & (iy <= H - 1)
    img = bev.reshape(H, W, -1)
    got = img[jnp.clip(iy, 0, H - 1).astype(jnp.int32),
              jnp.clip(ix, 0, W - 1).astype(jnp.int32)]
    return jnp.where(ok[..., None], got, 0.0).reshape(H * W, -1)


def shift(cfg, can_bus):
    """The ego translation in BEV grids, (x, y), at the ego yaw."""
    pc = cfg["pc_range"]
    dx, dy = can_bus[0], can_bus[1]
    ego_angle = can_bus[-2] / math.pi * 180
    length = jnp.sqrt(dx ** 2 + dy ** 2)
    trans_angle = jnp.arctan2(dy, dx) / math.pi * 180
    bev_angle = ego_angle - trans_angle
    grid_y = (pc[4] - pc[1]) / cfg["bev_h"]
    grid_x = (pc[3] - pc[0]) / cfg["bev_w"]
    sy = length * jnp.cos(bev_angle / 180 * math.pi) / grid_y / cfg["bev_h"]
    sx = length * jnp.sin(bev_angle / 180 * math.pi) / grid_x / cfg["bev_w"]
    return jnp.stack([sx, sy])


def bev_grid(cfg):
    H, W = cfg["bev_h"], cfg["bev_w"]
    i, j = np.meshgrid(np.arange(H), np.arange(W), indexing="ij")
    return jnp.asarray(np.stack([(j.reshape(-1) + 0.5) / W,
                                 (i.reshape(-1) + 0.5) / H], -1), jnp.float32)


# --------------------------------------------------------------------------
# the model, piece by piece (jitted by ``Reference``)
# --------------------------------------------------------------------------


def temporal(params, cfg, can_bus, prev_bev, first: bool, pr):
    can_bus = jnp.asarray(can_bus, jnp.float32)
    if first:
        can_bus = can_bus.at[jnp.array([0, 1, 2, -1])].set(0.0)
    cb = params["can_bus"]
    h = relu(linear(cb["l2"], relu(linear(cb["l1"], can_bus, pr)), pr))
    q0 = params["bev_embedding"].astype(jnp.float32) + layer_norm(
        cb["norm"], h, cfg["norm_eps"])[None]
    H, W, half = cfg["bev_h"], cfg["bev_w"], cfg["d_model"] // 2
    pos = jnp.concatenate([
        jnp.broadcast_to(params["col_embed"].astype(jnp.float32)[None], (H, W, half)),
        jnp.broadcast_to(params["row_embed"].astype(jnp.float32)[:, None], (H, W, half)),
    ], -1).reshape(H * W, -1)
    refs = bev_grid(cfg) + shift(cfg, can_bus)[None]
    prev = None if first else rotate(cfg, prev_bev, can_bus[-1])
    return q0, pos, refs, prev


def tsa(p, cfg, query, pos, value, refs, pr):
    """Temporal self-attention with its residual; value (2, Q, d)."""
    Q, d = query.shape
    Hh, P = cfg["num_heads"], cfg["tsa_points"]
    x = jnp.concatenate([value[0], query + pos], -1)
    off = (mm(x, p["offsets"]["w"], pr) + p["offsets"]["b"]).reshape(Q, Hh, 2, 1, P, 2)
    aw = (mm(x, p["weights"]["w"], pr) + p["weights"]["b"]).reshape(Q, Hh, 2, P)
    aw = jax.nn.softmax(aw, axis=-1).reshape(Q, Hh, 2, 1, P)
    wh = jnp.asarray([cfg["bev_w"], cfg["bev_h"]], jnp.float32)
    levels = [(cfg["bev_h"], cfg["bev_w"])]
    outs = []
    for s in range(2):
        v = linear(p["value"], value[s], pr).reshape(Q, Hh, -1)
        loc = refs[:, None, None, None, :] + off[:, :, s] / wh
        outs.append(deformable(v, levels, loc, aw[:, :, s], pr))
    return linear(p["out"], (outs[0] + outs[1]) / 2, pr) + query


def sca_camera(p, cfg, q_hit, refs_hit, feats_c, pr):
    """One camera's MSDA over its hit queries (n, d), anchors (n, Z, 2)."""
    levels = [tuple(l) for l in cfg["levels"]]
    Hh, P, L = cfg["num_heads"], cfg["sca_points"], len(levels)
    n, Z = refs_hit.shape[:2]
    v = linear(p["value"], feats_c, pr).reshape(feats_c.shape[0], Hh, -1)
    off, aw = msda_offsets(p, cfg, q_hit, L, P, pr)
    wh = jnp.asarray([[w, h] for h, w in levels], jnp.float32)
    off = off.reshape(n, Hh, L, P // Z, Z, 2) / wh[None, None, :, None, None, :]
    loc = (refs_hit[:, None, None, None] + off).reshape(n, Hh, L, P, 2)
    return deformable(v, levels, loc, aw, pr)


def encoder_tail(lp, cfg, q, slots, count, pr):
    """SCA's normalisation, projection and residual; the norms and FFN."""
    eps = cfg["norm_eps"]
    q = linear(lp["sca"]["out"], slots / count[:, None], pr) + q
    q = layer_norm(lp["norm2"], q, eps)
    return layer_norm(lp["norm3"], q + ffn(lp["ffn"], q, pr), eps)


def inverse_sigmoid(x, eps=1e-5):
    x = jnp.clip(x, 0, 1)
    return jnp.log(jnp.clip(x, eps, None) / jnp.clip(1 - x, eps, None))


def decoder(params, cfg, bev, pr):
    d, eps = cfg["d_model"], cfg["norm_eps"]
    Hh, P = cfg["num_heads"], cfg["decoder_points"]
    D = d // Hh
    pc = cfg["pc_range"]
    levels = [(cfg["bev_h"], cfg["bev_w"])]
    qe = params["query_embedding"].astype(jnp.float32)
    qpos, q = qe[:, :d], qe[:, d:]
    ref = jax.nn.sigmoid(linear(params["reference_points"], qpos, pr))
    nq = q.shape[0]
    wh = jnp.asarray([cfg["bev_w"], cfg["bev_h"]], jnp.float32)
    box = None
    for l in range(cfg["decoder_layers"]):
        lp = jax.tree.map(lambda x: x[l], params["dec"])
        sa = lp["self_attn"]
        qk = q + qpos
        qh = linear(sa["q"], qk, pr).reshape(nq, Hh, D)
        kh = linear(sa["k"], qk, pr).reshape(nq, Hh, D)
        vh = linear(sa["v"], q, pr).reshape(nq, Hh, D)
        s = jnp.einsum("qhd,khd->hqk", pr(qh), pr(kh), precision=HIGHEST) / math.sqrt(D)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", pr(a), pr(vh), precision=HIGHEST).reshape(nq, d)
        q = layer_norm(lp["norm1"], q + linear(sa["o"], o, pr), eps)
        m = lp["cross"]
        v = linear(m["value"], bev, pr).reshape(-1, Hh, D)
        off, aw = msda_offsets(m, cfg, q + qpos, 1, P, pr)
        loc = ref[:, None, None, None, :2] + off / wh
        h = linear(m["out"], deformable(v, levels, loc, aw, pr), pr)
        q = layer_norm(lp["norm2"], q + h, eps)
        q = layer_norm(lp["norm3"], q + ffn(lp["ffn"], q, pr), eps)
        r = lp["reg"]
        tmp = linear(r["l3"], relu(linear(r["l2"], relu(linear(r["l1"], q, pr)), pr)), pr)
        isig = inverse_sigmoid(ref)
        xy = jax.nn.sigmoid(tmp[:, 0:2] + isig[:, 0:2])
        z = jax.nn.sigmoid(tmp[:, 4:5] + isig[:, 2:3])
        box = jnp.concatenate([
            xy[:, 0:1] * (pc[3] - pc[0]) + pc[0], xy[:, 1:2] * (pc[4] - pc[1]) + pc[1],
            tmp[:, 2:4], z * (pc[5] - pc[2]) + pc[2], tmp[:, 5:]], -1)
        ref = jnp.concatenate([xy, z], -1)
    c = lp["cls"]
    x = relu(layer_norm(c["n1"], linear(c["l1"], q, pr), eps))
    x = relu(layer_norm(c["n2"], linear(c["l2"], x, pr), eps))
    return linear(c["l3"], x, pr), box


class Reference:
    """The reference for one configuration and precision, its pieces
    jitted once each (the camera pieces once per hit count)."""

    def __init__(self, cfg: dict, pr: Precision = FLOAT32):
        self.cfg, self.pr = cfg, pr
        c = cfg
        self._temporal = jax.jit(lambda p, cb, prev, first: temporal(
            p, c, cb, prev, first, pr), static_argnums=3)
        self._points = jax.jit(lambda m: camera_points(c, m))
        self._tsa = jax.jit(lambda p, q, pos, v, r: tsa(p, c, q, pos, v, r, pr))
        self._norm = jax.jit(lambda p, x: layer_norm(p, x, c["norm_eps"]))
        self._sca = jax.jit(lambda p, q, r, f: sca_camera(p, c, q, r, f, pr))
        self._tail = jax.jit(lambda lp, q, s, n: encoder_tail(lp, c, q, s, n, pr))
        self._decoder = jax.jit(lambda p, bev: decoder(p, c, bev, pr))

    def frame(self, params, feats, lidar2img, can_bus, prev_bev=None) -> Dict:
        """One frame; ``prev_bev`` None on a scene's first frame.
        feats (N, S, d) camera pyramids before the camera and level
        embeddings."""
        cfg = self.cfg
        first = prev_bev is None
        q0, pos, refs, prev = self._temporal(
            params, can_bus, jnp.zeros((1, 1)) if first else prev_bev, first)
        uv, mask = self._points(lidar2img)
        hit = np.asarray(mask).any(-1)  # (N, Q)
        index = [np.nonzero(h)[0] for h in hit]
        count = jnp.asarray(np.maximum(hit.sum(0), 1), jnp.float32)
        sizes = np.cumsum([0] + [h * w for h, w in cfg["levels"]])
        lvl = np.repeat(np.arange(len(cfg["levels"])), np.diff(sizes))
        feats = (jnp.asarray(feats, jnp.float32)
                 + params["cams_embeds"].astype(jnp.float32)[:, None]
                 + params["level_embeds"].astype(jnp.float32)[lvl][None])
        q = q0
        for l in range(cfg["encoder_layers"]):
            lp = jax.tree.map(lambda x: x[l], params["enc"])
            value = jnp.stack([q, q]) if first else jnp.stack([prev, q0])
            q = self._norm(lp["norm1"], self._tsa(lp["tsa"], q, pos, value, refs))
            slots = jnp.zeros_like(q)
            for c, idx in enumerate(index):
                out = self._sca(lp["sca"], q[idx], uv[c][idx], feats[c])
                slots = slots.at[idx].add(out)
            q = self._tail(lp, q, slots, count)
        logits, boxes = self._decoder(params, q)
        return {"logits": logits, "boxes": boxes, "bev": q,
                "hits": hit.sum(1)}

    def sequence(self, params, frames: Sequence[dict],
                 carry: bool = True) -> List[Dict]:
        """Frames of one scene from its start, the BEV carried (or, with
        ``carry`` off, every frame run as a scene's first)."""
        out, prev = [], None
        for f in frames:
            r = self.frame(params, f["feats"], f["lidar2img"], f["can_bus"], prev)
            prev = r["bev"] if carry else None
            out.append(r)
        return out
