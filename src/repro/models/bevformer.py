"""BEVFormer-base inference: six cameras into a bird's-eye-view (BEV)
grid, with the previous frame's BEV carried from call to call.

arXiv:2203.17270 (BEVFormer) as ``projects/configs/bevformer/
bevformer_base.py`` of github.com/fundamentalvision/BEVFormer configures
it.  One call is one frame of one scene, at batch 1:

* the BEV queries: ``bev_embedding`` (H_bev*W_bev, d) plus the can_bus
  MLP (Linear 18->d/2, ReLU, Linear d/2->d, ReLU, LayerNorm) of the
  frame's 18 can_bus values; a learned positional encoding of d/2 column
  and d/2 row features;
* temporal alignment (``bev_temporal``): the previous BEV rotated by the
  yaw delta about the grid's centre (nearest neighbour, zero fill, as
  torchvision's ``rotate``), and the BEV reference points shifted by the
  ego translation in BEV cells.  Upstream's encoder shifts its 2-D
  reference points in place (``shift_ref_2d = ref_2d``, kept there to
  reproduce the paper), so both temporal sources read the shifted
  points; so do they here.  On a scene's first frame upstream zeroes the
  can_bus translation and yaw delta and has no previous BEV: the
  ``first`` flag selects that inside the same program;
* 6 encoder layers, post-norm: temporal self-attention (TSA), norm,
  spatial cross-attention (SCA), norm, FFN (ReLU), norm;
* TSA (``bev_tsa``): two sources, the aligned previous BEV and the
  encoder's input queries (on a first frame, the layer's query twice);
  offsets and weights from ``concat(source 0, query + pos)``, a softmax
  per source over its 4 points on the one BEV level, one shared value
  projection; the mean of the two sources, the output projection and
  the residual.  Each source is one batch-1 MSDA call;
* SCA (``bev_sca``): the pillar of every BEV cell holds 4 anchors at
  heights ``linspace(0.5, Z-0.5, 4)/Z`` of the z range; projected by
  each camera's ``lidar2img`` in float32, a pillar hits a camera if an
  anchor has depth > 1e-5 and image coordinates strictly inside (0, 1).
  Each camera's hit queries are packed to the camera's static bound
  (``cam_bounds``; ``sca_rebatch``), run through one batch-1 MSDA call
  over that camera's 4-level pyramid (value projection, offsets of
  ``(H, L, P/Z, Z, 2)`` around the anchors, softmax over L*P, no output
  projection: upstream's ``MSDeformableAttention3D``), scattered back
  and divided by the number of cameras hit; then the output projection
  and the residual.  Hits past a camera's bound are counted in
  ``overflow`` and left out, never silently: the caller fails the frame;
* the decoder: 900 object queries (``query_pos, query`` split from one
  (900, 2d) embedding), reference points ``sigmoid(Linear(query_pos))``;
  6 layers of self-attention (8 heads), norm, one-level MSDA over the
  final BEV with 4 points, norm, FFN, norm; after each layer the layer's
  reg branch refines the reference in inverse-sigmoid space (dims 0:2
  and 4:5);
* the heads of the last layer: class ``(Linear, LN, ReLU) x2, Linear``,
  box ``(Linear, ReLU) x2, Linear`` with its centre on the refined
  reference, scaled to the point-cloud range.

Every MSDA call goes through ``repro.core.msda`` (``sampling_locations``,
``attention_plan`` and its plan) at batch 1.  The MSDA projections carry
biases; upstream's are ``nn.Linear`` layers.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MSDAConfig
from repro.core import msda as msda_mod
from repro.models import layers
from repro.obs import registry, scopes

DEPTH_EPS = 1e-5
# camera reference points are clamped to this many image extents, so the
# kernels' int32 corner arithmetic stays in range for an anchor behind a
# camera (its coordinates are divided by DEPTH_EPS); a sample that far
# off the image reads zero either way
REF_CLAMP = 1e4


@dataclass(frozen=True)
class BEVFormerConfig:
    """The model's shapes; the published ones are in the configuration
    file ``chipbench/configs/bevformer-base.json`` (``from_dict``)."""

    levels: Tuple[Tuple[int, int], ...]
    num_cams: int
    d_model: int
    num_heads: int
    d_ff: int
    encoder_layers: int
    decoder_layers: int
    bev_h: int
    bev_w: int
    pc_range: Tuple[float, ...]
    num_anchors: int
    sca_points: int
    tsa_points: int
    decoder_points: int
    num_queries: int
    num_classes: int
    code_size: int
    can_bus_dims: int
    # (h, w) the camera coordinates are normalised by
    image_size: Tuple[int, int]
    norm_eps: float
    # packed-query bound of each camera's SCA call (``camera_bounds``)
    cam_bounds: Tuple[int, ...] = ()
    backend: str = "auto"

    @classmethod
    def from_dict(cls, d: dict, cam_bounds=()) -> "BEVFormerConfig":
        """The program's configuration from a configuration file's keys."""
        return cls(
            levels=tuple(tuple(l) for l in d["levels"]), num_cams=d["num_cams"],
            d_model=d["d_model"], num_heads=d["num_heads"], d_ff=d["d_ff"],
            encoder_layers=d["encoder_layers"],
            decoder_layers=d["decoder_layers"], bev_h=d["bev_h"],
            bev_w=d["bev_w"], pc_range=tuple(d["pc_range"]),
            num_anchors=d["num_points_in_pillar"], sca_points=d["sca_points"],
            tsa_points=d["tsa_points"], decoder_points=d["decoder_points"],
            num_queries=d["num_queries"], num_classes=d["num_classes"],
            code_size=d["code_size"], can_bus_dims=d["can_bus_dims"],
            image_size=tuple(d["image_size"]), norm_eps=d["norm_eps"],
            cam_bounds=tuple(cam_bounds), backend=d.get("backend", "auto"))

    @property
    def bev_queries(self) -> int:
        return self.bev_h * self.bev_w

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads

    @property
    def cam_pixels(self) -> int:
        return sum(h * w for h, w in self.levels)

    def sca_msda(self) -> MSDAConfig:
        return MSDAConfig(levels=self.levels, num_points=self.sca_points,
                          num_heads=self.num_heads, backend=self.backend)

    def bev_msda(self, points: int) -> MSDAConfig:
        return MSDAConfig(levels=((self.bev_h, self.bev_w),),
                          num_points=points, num_heads=self.num_heads,
                          backend=self.backend)


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------


def _ring(H: int, n: int, P: int) -> jax.Array:
    """Upstream's sampling-offset bias: head h's points on a ring at
    angle 2*pi*h/H, point p at radius p + 1; (H, n, P, 2) flattened."""
    theta = jnp.arange(H, dtype=jnp.float32) * (2.0 * math.pi / H)
    grid = jnp.stack([jnp.cos(theta), jnp.sin(theta)], -1)
    grid = grid / jnp.abs(grid).max(-1, keepdims=True)
    scale = (jnp.arange(P, dtype=jnp.float32) + 1.0)[None, None, :, None]
    return (jnp.tile(grid[:, None, None], (1, n, P, 1)) * scale).reshape(-1)


def init_params(key, cfg: BEVFormerConfig) -> dict:
    """Seeded weights in the program's layout, layers stacked on a leading
    axis: LeCun-normal matrices, biases N(0, 0.02), LayerNorms at unit
    scale; embeddings as upstream initialises them (N(0, 1), the
    positional rows and columns U(0, 1)); sampling offsets on upstream's
    ring plus 0.25 of LeCun-normal weights and attention weights at 0.25
    of LeCun normal, so that both depend on the query.  Every MSDA module
    (TSA, SCA, the decoder's cross-attention) holds ``value``,
    ``offsets``, ``weights`` and ``out`` linears.

    The last layer of each reg branch is drawn at 0.01 of LeCun normal:
    Deformable-DETR's head starts it at zero (mmdet's
    ``DeformableDETRHead.init_weights``), so that the box refinement
    starts near the identity; at full scale the refinement loop over a
    random BEV amplifies rounding from layer to layer."""
    d, ff, H = cfg.d_model, cfg.d_ff, cfg.num_heads
    L, C = len(cfg.levels), cfg.num_classes
    keys = (jax.random.fold_in(key, i) for i in itertools.count())

    def lin(i, o, scale=1.0, bias=None):
        w = jax.random.normal(next(keys), (i, o), jnp.float32) / math.sqrt(i)
        b = (jax.random.normal(next(keys), (o,), jnp.float32) * 0.02
             if bias is None else bias)
        return {"w": w * scale, "b": b}

    def norm():
        return {"scale": jnp.ones((d,), jnp.float32),
                "bias": jnp.zeros((d,), jnp.float32)}

    def msda(d_in, n_levels, P):
        """An MSDA module; TSA's ``n_levels`` is its two sources."""
        return {"value": lin(d, d),
                "offsets": lin(d_in, H * n_levels * P * 2, 0.25,
                               _ring(H, n_levels, P)),
                "weights": lin(d_in, H * n_levels * P, 0.25),
                "out": lin(d, d)}

    def ffn():
        return {"l1": lin(d, ff), "l2": lin(ff, d)}

    def enc_layer(_):
        return {
            "tsa": msda(2 * d, 2, cfg.tsa_points), "norm1": norm(),
            "sca": msda(d, L, cfg.sca_points), "norm2": norm(),
            "ffn": ffn(), "norm3": norm(),
        }

    def dec_layer(_):
        return {
            "self_attn": {n: lin(d, d) for n in ("q", "k", "v", "o")},
            "norm1": norm(),
            "cross": msda(d, 1, cfg.decoder_points),
            "norm2": norm(), "ffn": ffn(), "norm3": norm(),
            "cls": {"l1": lin(d, d), "n1": norm(), "l2": lin(d, d),
                    "n2": norm(), "l3": lin(d, C)},
            "reg": {"l1": lin(d, d), "l2": lin(d, d),
                    "l3": lin(d, cfg.code_size, 0.01)},
        }

    def stack(make, n):
        trees = [make(i) for i in range(n)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)

    normal = lambda shape: jax.random.normal(next(keys), shape, jnp.float32)  # noqa: E731
    uniform = lambda shape: jax.random.uniform(next(keys), shape, jnp.float32)  # noqa: E731
    return {
        "bev_embedding": normal((cfg.bev_queries, d)),
        "row_embed": uniform((cfg.bev_h, d // 2)),
        "col_embed": uniform((cfg.bev_w, d // 2)),
        "cams_embeds": normal((cfg.num_cams, d)),
        "level_embeds": normal((L, d)),
        "can_bus": {"l1": lin(cfg.can_bus_dims, d // 2), "l2": lin(d // 2, d),
                    "norm": norm()},
        "enc": stack(enc_layer, cfg.encoder_layers),
        "query_embedding": normal((cfg.num_queries, 2 * d)),
        "reference_points": lin(d, 3),
        "dec": stack(dec_layer, cfg.decoder_layers),
    }


# --------------------------------------------------------------------------
# the camera rig: hits and static bounds (host side)
# --------------------------------------------------------------------------


def bev_anchors(cfg: BEVFormerConfig, xp=jnp):
    """(Z, H_bev*W_bev, 3) anchor positions in the lidar frame, metres:
    cell (i, j) at x = (j + 0.5)/W, y = (i + 0.5)/H of the range, the
    anchors at heights ``linspace(0.5, Z - 0.5, n)/Z`` of it."""
    pc = cfg.pc_range
    zlen = pc[5] - pc[2]
    n, H, W = cfg.num_anchors, cfg.bev_h, cfg.bev_w
    f32 = xp.float32
    zs = xp.linspace(0.5, zlen - 0.5, n, dtype=f32) / zlen
    xs = (xp.arange(W, dtype=f32) + 0.5) / W
    ys = (xp.arange(H, dtype=f32) + 0.5) / H
    x = xp.broadcast_to(xs[None, None, :], (n, H, W)) * (pc[3] - pc[0]) + pc[0]
    y = xp.broadcast_to(ys[None, :, None], (n, H, W)) * (pc[4] - pc[1]) + pc[1]
    z = xp.broadcast_to(zs[:, None, None], (n, H, W)) * zlen + pc[2]
    return xp.stack([x, y, z], -1).reshape(n, H * W, 3).astype(f32)


def project(cfg: BEVFormerConfig, lidar2img, xp=jnp):
    """Every anchor in every camera: ``(uv (N, Z, Q, 2), mask (N, Z, Q))``,
    uv the image coordinates over the image size, mask the anchors in
    front of the camera (depth > 1e-5) and strictly inside the image.
    Float32, each coordinate as ``((m0 x + m1 y) + m2 z) + m3``."""
    pts = bev_anchors(cfg, xp)
    m = lidar2img.astype(xp.float32)[:, None, None]  # (N, 1, 1, 4, 4)
    x, y, z = (pts[None, :, :, None, k] for k in range(3))
    cam = ((m[..., 0] * x + m[..., 1] * y) + m[..., 2] * z) + m[..., 3]
    depth = cam[..., 2]
    uv = cam[..., 0:2] / xp.maximum(depth, xp.float32(DEPTH_EPS))[..., None]
    h, w = cfg.image_size
    u, v = uv[..., 0] / xp.float32(w), uv[..., 1] / xp.float32(h)
    mask = (depth > DEPTH_EPS) & (v > 0.0) & (v < 1.0) & (u < 1.0) & (u > 0.0)
    return xp.stack([u, v], -1), mask


def camera_hits(cfg: BEVFormerConfig, lidar2img: np.ndarray) -> np.ndarray:
    """Hit queries of each camera for a rig, on the host: (N,) int."""
    _, mask = project(cfg, np.asarray(lidar2img, np.float32), np)
    return mask.any(axis=1).sum(axis=1)


def camera_bounds(hits) -> Tuple[int, ...]:
    """Each camera's static bound: its hits and 1% more, rounded up to 64
    (room for the device's float32 mask to differ at the image edges)."""
    return tuple(int(-(-(n + -(-n // 100)) // 64) * 64) for n in hits)


def with_rig(cfg: BEVFormerConfig, lidar2img) -> BEVFormerConfig:
    """``cfg`` with the static bounds of the rig ``lidar2img``."""
    return dataclasses.replace(
        cfg, cam_bounds=camera_bounds(camera_hits(cfg, lidar2img)))


def record_counts(cfg: BEVFormerConfig, hits, overflow) -> None:
    """A frame's per-camera hit counts and packed slots
    (``bevformer.sca.hits`` / ``.slots`` gauges by camera) and its
    overflow (``bevformer.sca.overflow``) into ``repro.obs.registry``."""
    g_hits = registry.gauge("bevformer.sca.hits", "hit BEV queries per camera")
    g_slots = registry.gauge("bevformer.sca.slots", "packed slots per camera")
    for c, (n, b) in enumerate(zip(np.asarray(hits), cfg.cam_bounds)):
        g_hits.set(int(n), camera=c)
        g_slots.set(int(b), camera=c)
    registry.counter("bevformer.sca.overflow",
                     "hit queries past a camera's bound").inc(int(overflow))


# --------------------------------------------------------------------------
# the forward pass
# --------------------------------------------------------------------------


def _norm(p, x, cfg):
    return layers.apply_norm(p, x, cfg.norm_eps)


def _ffn(p, x):
    return layers.apply_linear(p["l2"], jax.nn.relu(layers.apply_linear(p["l1"], x)))


def bev_ref_2d(cfg: BEVFormerConfig) -> jax.Array:
    """(x, y) centre of every BEV cell over the grid: (H_bev*W_bev, 2)."""
    ys = (jnp.arange(cfg.bev_h, dtype=jnp.float32) + 0.5) / cfg.bev_h
    xs = (jnp.arange(cfg.bev_w, dtype=jnp.float32) + 0.5) / cfg.bev_w
    gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
    return jnp.stack([gx, gy], -1).reshape(-1, 2)


def bev_pos(params, cfg: BEVFormerConfig) -> jax.Array:
    """Learned positional encoding: column features, then row features."""
    H, W, half = cfg.bev_h, cfg.bev_w, cfg.d_model // 2
    col = jnp.broadcast_to(params["col_embed"].astype(jnp.float32)[None], (H, W, half))
    row = jnp.broadcast_to(params["row_embed"].astype(jnp.float32)[:, None], (H, W, half))
    return jnp.concatenate([col, row], -1).reshape(H * W, cfg.d_model)


def ego_shift(cfg: BEVFormerConfig, can_bus) -> jax.Array:
    """The ego translation ``can_bus[0:2]`` in BEV grids, (x, y), seen at
    the ego yaw ``can_bus[-2]`` (radians), as upstream's transformer
    computes it."""
    pc = cfg.pc_range
    dx, dy, yaw = can_bus[0], can_bus[1], can_bus[-2]
    length = jnp.sqrt(dx * dx + dy * dy)
    bev_angle = yaw - jnp.arctan2(dy, dx)
    grid_y = (pc[4] - pc[1]) / cfg.bev_h
    grid_x = (pc[3] - pc[0]) / cfg.bev_w
    shift_y = length * jnp.cos(bev_angle) / grid_y / cfg.bev_h
    shift_x = length * jnp.sin(bev_angle) / grid_x / cfg.bev_w
    return jnp.stack([shift_x, shift_y])


def rotate_bev(cfg: BEVFormerConfig, bev, angle_deg) -> jax.Array:
    """``bev`` (H*W, d) rotated by ``angle_deg`` about the grid's centre:
    torchvision's ``rotate`` (nearest neighbour, zero fill), in its
    float32 arithmetic (affine grid over the normalised output, then
    ``grid_sample``'s un-normalisation and round half to even), so that a
    source that falls on a rounding tie is resolved as upstream does."""
    H, W = cfg.bev_h, cfg.bev_w
    r = -angle_deg * (math.pi / 180.0)
    cos, sin = jnp.cos(r), jnp.sin(r)
    x = jnp.arange(W, dtype=jnp.float32)[None, :] - (W * 0.5 - 0.5)
    y = jnp.arange(H, dtype=jnp.float32)[:, None] - (H * 0.5 - 0.5)
    gx = x * (cos / (0.5 * W)) + y * (sin / (0.5 * W))
    gy = x * (-sin / (0.5 * H)) + y * (cos / (0.5 * H))
    sx = jnp.round(((gx + 1.0) * W - 1.0) / 2.0)
    sy = jnp.round(((gy + 1.0) * H - 1.0) / 2.0)
    inside = (sx >= 0) & (sx <= W - 1) & (sy >= 0) & (sy <= H - 1)
    src = jnp.where(inside, sy * W + sx, H * W).astype(jnp.int32).reshape(-1)
    ext = jnp.concatenate([bev, jnp.zeros((1, bev.shape[1]), bev.dtype)])
    return jnp.take(ext, src, axis=0)


def _msda_call(mcfg: MSDAConfig, value, loc, aw):
    """One batch-1 MSDA call through the module's committed plan."""
    plan = msda_mod.attention_plan(mcfg, num_queries=loc.shape[1],
                                   head_dim=value.shape[-1], dtype=value.dtype)
    return plan(value, loc, aw)


def deformable_attention(p, mcfg: MSDAConfig, query, value_src, refs):
    """One batch-1 MSDA call of ``query`` (Q, d) over ``value_src`` (S, d)
    around ``refs`` (Q, 2), or (Q, Z, 2) anchors: the value projection,
    offsets, a softmax over levels x points; unprojected, (Q, d)."""
    Q, d = query.shape
    H, L, P = mcfg.num_heads, len(mcfg.levels), mcfg.num_points
    # projected with the batch axis on, as ``msda_attention`` does: the
    # value's layout then suits the kernel's slabs without a copy
    query, value_src = query[None], value_src[None]
    with jax.named_scope(scopes.MSDA_PROJ):
        value = layers.apply_linear(p["value"], value_src).reshape(1, -1, H, d // H)
        off = layers.apply_linear(p["offsets"], query).reshape(1, Q, H, L, P, 2)
        loc = msda_mod.sampling_locations(refs[None], off.astype(jnp.float32),
                                          mcfg.levels)
        aw = layers.apply_linear(p["weights"], query).reshape(1, Q, H, L * P)
        aw = jax.nn.softmax(aw.astype(jnp.float32), axis=-1)
        aw = aw.reshape(1, Q, H, L, P).astype(query.dtype)
    return _msda_call(mcfg, value, loc, aw)[0]


def temporal_self_attention(p, cfg: BEVFormerConfig, query, pos, src0, src1,
                            refs) -> jax.Array:
    """TSA over the sources ``src0`` (the aligned previous BEV) and
    ``src1``, each one batch-1 MSDA call; with the residual."""
    Q, d = query.shape
    H, D, P = cfg.num_heads, cfg.head_dim, cfg.tsa_points
    mcfg = cfg.bev_msda(P)
    with jax.named_scope(scopes.BEV_TSA):
        with jax.named_scope(scopes.MSDA_PROJ):
            x = jnp.concatenate([src0, query + pos], -1)
            off = layers.apply_linear(p["offsets"], x).reshape(1, Q, H, 2, 1, P, 2)
            aw = layers.apply_linear(p["weights"], x).reshape(Q, H, 2, P)
            aw = jax.nn.softmax(aw.astype(jnp.float32), axis=-1)
        out = 0.0
        for s, src in enumerate((src0, src1)):
            with jax.named_scope(scopes.MSDA_PROJ):
                value = layers.apply_linear(p["value"], src).reshape(1, Q, H, D)
                loc = msda_mod.sampling_locations(
                    refs[None], off[:, :, :, s].astype(jnp.float32), mcfg.levels)
            out = out + _msda_call(mcfg, value, loc,
                                   aw[None, :, :, s, None].astype(query.dtype))[0]
        with jax.named_scope(scopes.MSDA_PROJ):
            out = layers.apply_linear(p["out"], out * 0.5)
        return out + query


class Rebatch:
    """A frame's packing of hit queries, per camera: ``index`` (bound,)
    the packed queries (padding reads query 0), ``refs`` (bound, Z, 2)
    their anchors in the camera, ``slot`` (Q,) each query's packed row
    (the bound, a zero row, where it missed or overflowed); ``count``
    (Q,) the cameras hit (at least 1), ``hits`` (N,) and ``overflow``."""

    def __init__(self, cfg: BEVFormerConfig, lidar2img):
        Q = cfg.bev_queries
        uv, mask = project(cfg, lidar2img)
        uv = jnp.clip(uv, -REF_CLAMP, REF_CLAMP)
        hit = mask.any(axis=1)  # (N, Q)
        self.index, self.refs, self.slot = [], [], []
        overflow = jnp.zeros((), jnp.int32)
        for c, bound in enumerate(cfg.cam_bounds):
            rank = jnp.cumsum(hit[c].astype(jnp.int32)) - 1
            slot = jnp.where(hit[c] & (rank < bound), rank, bound)
            index = jnp.zeros((bound + 1,), jnp.int32).at[slot].set(
                jnp.arange(Q, dtype=jnp.int32))[:bound]
            self.slot.append(slot)
            self.index.append(index)
            self.refs.append(jnp.transpose(uv[c][:, index], (1, 0, 2)))
            overflow = overflow + jnp.maximum(rank[-1] + 1 - bound, 0)
        self.hits = hit.sum(axis=1).astype(jnp.int32)
        self.count = jnp.maximum(hit.sum(axis=0), 1).astype(jnp.float32)
        self.overflow = overflow


def spatial_cross_attention(p, cfg: BEVFormerConfig, query, feats,
                            rb: Rebatch) -> jax.Array:
    """SCA: one batch-1 MSDA call per camera over its packed hit queries,
    scattered back and averaged over the cameras hit; with the output
    projection and the residual."""
    mcfg = cfg.sca_msda()
    with jax.named_scope(scopes.BEV_SCA):
        slots = jnp.zeros_like(query)
        for c in range(cfg.num_cams):
            with jax.named_scope(scopes.SCA_REBATCH):
                packed = jnp.take(query, rb.index[c], axis=0)
            out = deformable_attention(p, mcfg, packed, feats[c], rb.refs[c])
            with jax.named_scope(scopes.SCA_REBATCH):
                out = jnp.concatenate([out, jnp.zeros((1, out.shape[1]), out.dtype)])
                slots = slots + jnp.take(out, rb.slot[c], axis=0)
        with jax.named_scope(scopes.SCA_REBATCH):
            slots = slots / rb.count[:, None]
        with jax.named_scope(scopes.MSDA_PROJ):
            return layers.apply_linear(p["out"], slots) + query


def encode(params, cfg: BEVFormerConfig, feats, lidar2img, can_bus, prev_bev,
           first):
    """The BEV of one frame: ``(bev (Q, d), Rebatch)``.

    feats (N, S, d): each camera's pyramid, levels flattened in order;
    lidar2img (N, 4, 4); can_bus (18,); prev_bev (Q, d) the previous
    frame's BEV, read unless ``first``."""
    f32 = jnp.float32
    with jax.named_scope(scopes.ENCODER):
        with jax.named_scope(scopes.BEV_TEMPORAL):
            # upstream's first frame: no ego motion, no previous BEV
            moved = jnp.ones((cfg.can_bus_dims,), f32).at[
                jnp.array([0, 1, 2, cfg.can_bus_dims - 1])].set(0.0)
            can_bus = jnp.where(first, can_bus * moved, can_bus).astype(f32)
            cb = params["can_bus"]
            h = jax.nn.relu(layers.apply_linear(cb["l1"], can_bus))
            h = jax.nn.relu(layers.apply_linear(cb["l2"], h))
            q0 = params["bev_embedding"].astype(f32) + _norm(cb["norm"], h, cfg)[None]
            refs = bev_ref_2d(cfg) + ego_shift(cfg, can_bus)[None]
            prev = rotate_bev(cfg, prev_bev.astype(f32), can_bus[-1])
        pos = bev_pos(params, cfg)
        with jax.named_scope(scopes.BEV_SCA), jax.named_scope(scopes.SCA_REBATCH):
            rb = Rebatch(cfg, lidar2img)
        offs = np.cumsum([0] + [h * w for h, w in cfg.levels])
        level = np.repeat(np.arange(len(cfg.levels)), np.diff(offs))
        feats = (feats.astype(f32) + params["cams_embeds"].astype(f32)[:, None]
                 + params["level_embeds"].astype(f32)[level][None])

        def layer(q, lp):
            src0 = jnp.where(first, q, prev)
            src1 = jnp.where(first, q, q0)
            q = temporal_self_attention(lp["tsa"], cfg, q, pos, src0, src1, refs)
            q = _norm(lp["norm1"], q, cfg)
            q = spatial_cross_attention(lp["sca"], cfg, q, feats, rb)
            q = _norm(lp["norm2"], q, cfg)
            with jax.named_scope(scopes.FFN):
                q = _norm(lp["norm3"], q + _ffn(lp["ffn"], q), cfg)
            return q, None

        bev, _ = jax.lax.scan(layer, q0, params["enc"])
    return bev, rb


def _self_attention(p, cfg: BEVFormerConfig, qk, v):
    T, d = qk.shape
    H, D = cfg.num_heads, cfg.head_dim
    q = layers.apply_linear(p["q"], qk).reshape(T, H, D)
    k = layers.apply_linear(p["k"], qk).reshape(T, H, D)
    vv = layers.apply_linear(p["v"], v).reshape(T, H, D)
    a = jax.nn.softmax(jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(D), axis=-1)
    return layers.apply_linear(p["o"], jnp.einsum("hqk,khd->qhd", a, vv).reshape(T, d))


def inverse_sigmoid(x, eps: float = 1e-5):
    x = jnp.clip(x, 0.0, 1.0)
    return jnp.log(jnp.maximum(x, eps) / jnp.maximum(1.0 - x, eps))


def refine(cfg: BEVFormerConfig, tmp, ref):
    """The reg branch's output on the reference: ``(box, new reference)``,
    the box's centre in metres."""
    isig = inverse_sigmoid(ref)
    xy = jax.nn.sigmoid(tmp[:, 0:2] + isig[:, 0:2])
    z = jax.nn.sigmoid(tmp[:, 4:5] + isig[:, 2:3])
    pc = cfg.pc_range
    lo = jnp.asarray(pc[0:2], jnp.float32)
    span = jnp.asarray([pc[3] - pc[0], pc[4] - pc[1]], jnp.float32)
    box = jnp.concatenate([xy * span + lo, tmp[:, 2:4],
                           z * (pc[5] - pc[2]) + pc[2], tmp[:, 5:]], -1)
    return box, jnp.concatenate([xy, z], -1)


def _cls_branch(p, cfg, x):
    x = jax.nn.relu(_norm(p["n1"], layers.apply_linear(p["l1"], x), cfg))
    x = jax.nn.relu(_norm(p["n2"], layers.apply_linear(p["l2"], x), cfg))
    return layers.apply_linear(p["l3"], x)


def _reg_branch(p, x):
    x = jax.nn.relu(layers.apply_linear(p["l1"], x))
    x = jax.nn.relu(layers.apply_linear(p["l2"], x))
    return layers.apply_linear(p["l3"], x)


def decode(params, cfg: BEVFormerConfig, bev):
    """900 object queries over the BEV: ``(logits (900, C), boxes (900,
    code_size))`` of the last layer."""
    d = cfg.d_model
    mcfg = cfg.bev_msda(cfg.decoder_points)
    with jax.named_scope(scopes.DECODER):
        qe = params["query_embedding"].astype(jnp.float32)
        qpos, q = qe[:, :d], qe[:, d:]
        ref = jax.nn.sigmoid(layers.apply_linear(params["reference_points"], qpos))

        def layer(carry, lp):
            q, ref, _ = carry
            with jax.named_scope(scopes.SELF_ATTN):
                q = _norm(lp["norm1"], q + _self_attention(
                    lp["self_attn"], cfg, q + qpos, q), cfg)
            h = deformable_attention(lp["cross"], mcfg, q + qpos, bev, ref[:, 0:2])
            with jax.named_scope(scopes.MSDA_PROJ):
                h = layers.apply_linear(lp["cross"]["out"], h)
            q = _norm(lp["norm2"], q + h, cfg)
            with jax.named_scope(scopes.FFN):
                q = _norm(lp["norm3"], q + _ffn(lp["ffn"], q), cfg)
            with jax.named_scope(scopes.HEADS):
                box, ref = refine(cfg, _reg_branch(lp["reg"], q), ref)
            return (q, ref, box), None

        box0 = jnp.zeros((cfg.num_queries, cfg.code_size), jnp.float32)
        (q, _, box), _ = jax.lax.scan(layer, (q, ref, box0), params["dec"])
    with jax.named_scope(scopes.HEADS):
        last = jax.tree.map(lambda x: x[-1], params["dec"]["cls"])
        logits = _cls_branch(last, cfg, q)
    return logits, box


def frame(params, cfg: BEVFormerConfig, feats, lidar2img, can_bus, prev_bev,
          first) -> Dict[str, jax.Array]:
    """One frame: the last decoder layer's ``logits`` and ``boxes``, the
    frame's ``bev`` (the next frame's ``prev_bev``), each camera's
    ``hits`` and the ``overflow`` past the bounds (a frame with any is
    failed)."""
    bev, rb = encode(params, cfg, feats, lidar2img, can_bus, prev_bev, first)
    logits, boxes = decode(params, cfg, bev)
    return {"logits": logits, "boxes": boxes, "bev": bev, "hits": rb.hits,
            "overflow": rb.overflow}


def msda_plans(cfg: BEVFormerConfig, dtype="float32") -> Dict[str, object]:
    """The MSDA plans one frame commits: one per camera (its bound), the
    TSA's and the decoder's."""
    D = cfg.head_dim
    plans = {f"sca.cam{c}": msda_mod.attention_plan(
        cfg.sca_msda(), num_queries=b, head_dim=D, dtype=dtype)
        for c, b in enumerate(cfg.cam_bounds)}
    plans["tsa"] = msda_mod.attention_plan(
        cfg.bev_msda(cfg.tsa_points), num_queries=cfg.bev_queries,
        head_dim=D, dtype=dtype)
    plans["decoder"] = msda_mod.attention_plan(
        cfg.bev_msda(cfg.decoder_points), num_queries=cfg.num_queries,
        head_dim=D, dtype=dtype)
    return plans
