"""The BEVFormer family: a configuration file with ``"family":
"bevformer"`` runs the program's BEVFormer-base frame
(``repro.models.bevformer``) over a scene of six-camera frames, the
previous BEV carried from call to call, and is checked against the plain
float32 BEVFormer (``repro.models.bevformer_ref``).

A cell's traffic (``chipbench/traffic/<name>.json``) gives:

* ``distinct_frames`` six-camera frames made on the device from the seed:
  per camera a pyramid of N(0, ``feature_std``) features over every pixel
  of every level, plus ``bump`` on one channel per box at the image
  projection of each of ``num_boxes`` synthetic 3-D box centres (x, y
  uniform in +-``box_xy`` m, z in [``box_z_low``, ``box_z_high``]); 18
  can_bus values N(0, 1), of which the translation (0:2) is a speed
  uniform in [``speed_low``, ``speed_high``] m/s over
  ``frame_interval_s`` along the heading half way through the frame's
  turn, and the yaw delta (-1) is uniform in +-``yaw_delta_deg``; the ego
  yaw (-2) stays N(0, 1) radians;
* scenes of ``scene_frames`` frames, closed loop at batch 1: the window
  starts a scene at its first call (the program's ``first`` flag), cycles
  through the distinct frames within it, and carries each call's BEV
  into the next;
* the configuration's fixed rig (``rig`` of the configuration file).

One frame is one sample: ``images`` counts six-camera frames.

The comparison that decides `correct`: the window's scene's first
``checked_frames`` frames (frame 0 without a previous BEV, the others
carrying it) against the reference's own sequence over the same frames:

* ``logit_gap``: the widest gap of a class logit over the reference
  logits' root mean square;
* ``box_gap``: the widest gap of a box coordinate over the root mean
  square of that coordinate in the reference (the coordinates are
  metres, log sizes, sine and cosine, velocities);
* ``bev_gap``: the widest gap of a BEV feature over the reference BEV's
  root mean square;
* ``hit_count_gap``: the summed difference of the per-camera hit counts
  from the reference's own mask (any difference fails).
"""
from __future__ import annotations

import gc
import math
import time
from typing import Dict, List

import numpy as np

from chipbench import harness, work
from chipbench.catalog import CatalogError
from chipbench.harness import BenchError, log

COMPARED = ("logit_gap", "box_gap", "bev_gap", "hit_count_gap")


def _program():
    try:
        from repro.models import bevformer
    except ImportError as e:
        raise CatalogError(f"the program has no BEVFormer model "
                           f"(repro.models.bevformer): {e}") from None
    return bevformer


# --------------------------------------------------------------------------
# the rig and the program's configuration
# --------------------------------------------------------------------------


def lidar2img(cfg: dict) -> np.ndarray:
    """(N, 4, 4) float32 lidar-to-image matrices of the configuration's
    rig: a level camera at yaw ``yaw_deg`` (counter-clockwise from x)
    looks along (cos, sin, 0) with image x to its right and y down."""
    out = []
    for cam in cfg["rig"]["cameras"]:
        a = math.radians(cam["yaw_deg"])
        rot = np.array([[math.sin(a), -math.cos(a), 0.0],
                        [0.0, 0.0, -1.0],
                        [math.cos(a), math.sin(a), 0.0]])
        ext = np.eye(4)
        ext[:3, :3] = rot
        ext[:3, 3] = -rot @ np.asarray(cam["position"], np.float64)
        k = np.eye(4)
        k[0, 0] = k[1, 1] = cam["f"]
        k[0, 2], k[1, 2] = cam["cx"], cam["cy"]
        out.append(k @ ext)
    return np.stack(out).astype(np.float32)


def program_config(cfg: dict):
    """The program's configuration, with each camera's static bound from
    the rig."""
    bf = _program()
    if len(cfg["rig"]["cameras"]) != cfg["num_cams"]:
        raise BenchError("the rig must hold num_cams cameras")
    if cfg["d_model"] != cfg["num_heads"] * cfg["head_dim"]:
        raise BenchError("d_model must be num_heads x head_dim")
    if cfg["pos_feats"] * 2 != cfg["d_model"] or cfg["tsa_sources"] != 2:
        raise BenchError("the program takes d_model/2 positional features "
                         "per axis and two TSA sources")
    return bf.with_rig(bf.BEVFormerConfig.from_dict(cfg), lidar2img(cfg))


# --------------------------------------------------------------------------
# weights and frames, on the device from the seed
# --------------------------------------------------------------------------


def _lin(key, n, i, o, scale=1.0, bias=None):
    """``n`` stacked linears (i -> o): LeCun-normal matrices times
    ``scale``, biases N(0, 0.02) or ``bias``."""
    import jax
    import jax.numpy as jnp

    kw, kb = jax.random.split(key)
    w = jax.random.normal(kw, (n, i, o), jnp.float32) * (scale / math.sqrt(i))
    b = (jax.random.normal(kb, (n, o), jnp.float32) * 0.02 if bias is None
         else jnp.broadcast_to(bias, (n, o)))
    return {"b": b, "w": w}


def _norm(n, d):
    import jax.numpy as jnp

    return {"bias": jnp.zeros((n, d), jnp.float32),
            "scale": jnp.ones((n, d), jnp.float32)}


def _ring(H, n_levels, P):
    """Upstream's sampling-offset bias: head h's points on a ring at
    angle 2 pi h / H, point p at radius p + 1; (H, levels, P, 2) flat."""
    import jax.numpy as jnp

    theta = jnp.arange(H, dtype=jnp.float32) * (2.0 * math.pi / H)
    grid = jnp.stack([jnp.cos(theta), jnp.sin(theta)], -1)
    grid = grid / jnp.abs(grid).max(-1, keepdims=True)
    grid = jnp.tile(grid[:, None, None], (1, n_levels, P, 1))
    scale = (jnp.arange(P, dtype=jnp.float32) + 1.0)[None, None, :, None]
    return (grid * scale).reshape(-1)


def _msda(key, n, d, H, d_in, n_levels, P):
    """An MSDA module's linears (TSA's ``n_levels`` is its two sources):
    offsets on upstream's ring plus 0.25 of LeCun-normal weights, attention
    weights at 0.25 of LeCun normal, so that both follow the query."""
    import jax

    k = jax.random.split(key, 4)
    return {"offsets": _lin(k[0], n, d_in, H * n_levels * P * 2, 0.25,
                            _ring(H, n_levels, P)),
            "out": _lin(k[1], n, d, d),
            "value": _lin(k[2], n, d, d),
            "weights": _lin(k[3], n, d_in, H * n_levels * P, 0.25)}


def init(key, cfg: dict) -> dict:
    """BEVFormer's parameters drawn by the benchmark from the configuration
    file's shapes, in the program's layout (layers stacked on a leading
    axis): LeCun-normal matrices, biases N(0, 0.02), LayerNorms at unit
    scale; the BEV, camera, level and object-query embeddings N(0, 1) and
    the positional rows and columns U(0, 1), as upstream initialises
    them; MSDA modules as ``_msda``.

    The last layer of each reg branch is drawn at 0.01 of LeCun normal.
    Deformable-DETR's head starts that layer at zero (mmdet's
    ``DeformableDETRHead.init_weights``: ``constant_init(m[-1], 0)`` over
    the reg branches), so that box refinement starts near the identity.
    Drawn at full scale, the refinement loop over a random BEV amplifies
    rounding from layer to layer, and float32 against float32 no longer
    agrees on the last layer's boxes; at zero the boxes would not depend
    on the queries, and the check could not read them."""
    import jax
    import jax.numpy as jnp

    d, ff, H = cfg["d_model"], cfg["d_ff"], cfg["num_heads"]
    L, C, code = len(cfg["levels"]), cfg["num_classes"], cfg["code_size"]
    ne, nd = cfg["encoder_layers"], cfg["decoder_layers"]
    Q, nq = cfg["bev_h"] * cfg["bev_w"], cfg["num_queries"]
    k = iter(jax.random.split(key, 24))

    def ffn(n):
        return {"l1": _lin(next(k), n, d, ff), "l2": _lin(next(k), n, ff, d)}

    one = lambda p: jax.tree.map(lambda x: x[0], p)  # noqa: E731
    normal = lambda shape: jax.random.normal(next(k), shape, jnp.float32)  # noqa: E731
    return {
        "bev_embedding": normal((Q, d)),
        "row_embed": jax.random.uniform(next(k), (cfg["bev_h"], d // 2)),
        "col_embed": jax.random.uniform(next(k), (cfg["bev_w"], d // 2)),
        "cams_embeds": normal((cfg["num_cams"], d)),
        "level_embeds": normal((L, d)),
        "can_bus": {"l1": one(_lin(next(k), 1, cfg["can_bus_dims"], d // 2)),
                    "l2": one(_lin(next(k), 1, d // 2, d)),
                    "norm": one(_norm(1, d))},
        "enc": {
            "tsa": _msda(next(k), ne, d, H, 2 * d, cfg["tsa_sources"],
                         cfg["tsa_points"]),
            "sca": _msda(next(k), ne, d, H, d, L, cfg["sca_points"]),
            "ffn": ffn(ne),
            "norm1": _norm(ne, d), "norm2": _norm(ne, d), "norm3": _norm(ne, d),
        },
        "query_embedding": normal((nq, 2 * d)),
        "reference_points": one(_lin(next(k), 1, d, 3)),
        "dec": {
            "self_attn": {n: _lin(kk, nd, d, d) for n, kk in
                          zip("qkvo", jax.random.split(next(k), 4))},
            "cross": _msda(next(k), nd, d, H, d, 1, cfg["decoder_points"]),
            "ffn": ffn(nd),
            "cls": {"l1": _lin(next(k), nd, d, d), "n1": _norm(nd, d),
                    "l2": _lin(next(k), nd, d, d), "n2": _norm(nd, d),
                    "l3": _lin(next(k), nd, d, C)},
            "reg": {"l1": _lin(next(k), nd, d, d), "l2": _lin(next(k), nd, d, d),
                    "l3": _lin(next(k), nd, d, code, 0.01)},
            "norm1": _norm(nd, d), "norm2": _norm(nd, d), "norm3": _norm(nd, d),
        },
    }


def check_layout(params, mcfg) -> None:
    """The benchmark's weights must have the program's parameter layout."""
    import jax

    want = jax.eval_shape(lambda k: _program().init_params(k, mcfg),
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda x: (x.shape, str(x.dtype)), params)
    want = jax.tree.map(lambda x: (x.shape, str(x.dtype)), want)
    if got != want:
        raise BenchError("benchmark weights do not match the program's "
                         "parameter layout")


def weights(seed: int, cfg: dict, mcfg):
    """The seed's weights (``init``) as inference serves them: every leaf
    of two or more axes in the configuration's dtype, vectors in float32;
    made in one jitted call, checked against the program's layout."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(cfg["dtype"])
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 1)
    check_layout(jax.eval_shape(lambda k: init(k, cfg), key), mcfg)

    def build(k):
        return jax.tree.map(lambda x: x.astype(dt) if x.ndim >= 2 else x,
                            init(k, cfg))

    return jax.jit(build)(key)


def _frames(key, cfg: dict, tr: dict, l2i):
    import jax
    import jax.numpy as jnp

    n, N, T = tr["distinct_frames"], cfg["num_cams"], tr["num_boxes"]
    d = cfg["d_model"]
    S = sum(h * w for h, w in cfg["levels"])
    kf, kb, kz, kc, ks, ky = jax.random.split(key, 6)
    feats = jax.random.normal(kf, (n, N, S, d), jnp.float32) * tr["feature_std"]
    xy = jax.random.uniform(kb, (n, T, 2), jnp.float32, -tr["box_xy"], tr["box_xy"])
    z = jax.random.uniform(kz, (n, T, 1), jnp.float32, tr["box_z_low"], tr["box_z_high"])
    pts = jnp.concatenate([xy, z, jnp.ones((n, T, 1), jnp.float32)], -1)
    cam = jnp.einsum("crk,ntk->nctr", l2i, pts, precision="highest")
    depth = cam[..., 2]
    img_h, img_w = cfg["image_size"]
    u = cam[..., 0] / jnp.maximum(depth, 1e-5) / img_w
    v = cam[..., 1] / jnp.maximum(depth, 1e-5) / img_h
    seen = (depth > 1e-5) & (u > 0) & (u < 1) & (v > 0) & (v < 1)  # (n, N, T)
    chan = (jnp.arange(T) * 97) % d
    ni = jnp.arange(n)[:, None, None]
    ci = jnp.arange(N)[None, :, None]
    offset = 0
    for h, w in cfg["levels"]:
        col = jnp.clip(jnp.floor(u * w), 0, w - 1).astype(jnp.int32)
        row = jnp.clip(jnp.floor(v * h), 0, h - 1).astype(jnp.int32)
        feats = feats.at[ni, ci, offset + row * w + col, chan[None, None]].add(
            tr["bump"] * seen)
        offset += h * w
    can_bus = jax.random.normal(kc, (n, cfg["can_bus_dims"]), jnp.float32)
    speed = jax.random.uniform(ks, (n,), jnp.float32, tr["speed_low"], tr["speed_high"])
    yaw_delta = jax.random.uniform(ky, (n,), jnp.float32, -tr["yaw_delta_deg"],
                                   tr["yaw_delta_deg"])
    heading = can_bus[:, -2] - jnp.deg2rad(yaw_delta) / 2
    dist = speed * tr["frame_interval_s"]
    can_bus = can_bus.at[:, 0].set(dist * jnp.cos(heading))
    can_bus = can_bus.at[:, 1].set(dist * jnp.sin(heading))
    can_bus = can_bus.at[:, -1].set(yaw_delta)
    return feats, can_bus


def make_frames(seed: int, cfg: dict, tr: dict, l2i) -> List[Dict]:
    """The distinct frames for ``seed``, each ``{"feats" (N, S, d),
    "can_bus" (18,)}`` of device arrays, made in one jitted call."""
    import jax

    key = jax.random.fold_in(jax.random.PRNGKey(seed), 2)
    feats, can_bus = jax.jit(lambda k: _frames(k, cfg, tr, l2i))(key)
    out = [{"feats": feats[i], "can_bus": can_bus[i]}
           for i in range(tr["distinct_frames"])]
    del feats
    return out


# --------------------------------------------------------------------------
# the cell
# --------------------------------------------------------------------------


def committed_plans(mcfg) -> Dict:
    """The MSDA plans the compiled entry committed: fetching them again
    must hit the plan cache."""
    from repro.kernels import plan as plan_mod

    misses = plan_mod.plan_cache_info()["misses"]
    plans = _program().msda_plans(mcfg, dtype="float32")
    if plan_mod.plan_cache_info()["misses"] != misses:
        raise BenchError("the reported MSDA plans are not the ones the "
                         "compiled entry committed")
    return plans


def infer_cell(ctx: dict) -> dict:
    """The jitted frame, compiled ahead of time and warmed on a first and
    a carried frame, then the window: a scene from its first frame, the
    BEV carried from call to call.  The scene's first ``checked_frames``
    frames are kept for the check (run on after the window if it held
    fewer)."""
    import jax
    import jax.numpy as jnp

    bf = _program()
    cfg, tr, mcfg = ctx["cfg"], ctx["traffic"], ctx["mcfg"]
    if tr["batch"] != 1:
        raise BenchError("the program runs a frame at batch 1")
    params = weights(ctx["seed"], cfg, mcfg)
    l2i = jnp.asarray(lidar2img(cfg))
    frames = make_frames(ctx["seed"], cfg, tr, l2i)
    flags = (jnp.asarray(True), jnp.asarray(False))
    prev0 = jnp.zeros((mcfg.bev_queries, mcfg.d_model), jnp.float32)

    def frame(p, feats, m, can_bus, prev_bev, first):
        return bf.frame(p, mcfg, feats, m, can_bus, prev_bev, first)

    frame = ctx["hooks"].get("frame_fn", lambda f: f)(frame)
    f0 = frames[0]
    t0 = time.perf_counter()
    compiled = jax.jit(frame).lower(params, f0["feats"], l2i, f0["can_bus"],
                                    prev0, flags[0]).compile()
    log(f"frame lowered and compiled (or loaded) in "
        f"{time.perf_counter() - t0:.3f}s")
    plans = committed_plans(mcfg)
    harness.report_program(compiled, plans)
    log(f"cameras' hit queries of the rig "
        f"{[int(n) for n in bf.camera_hits(mcfg, np.asarray(l2i))]}, "
        f"static bounds {list(mcfg.cam_bounds)}")
    ctx["plans"] = plans
    warm = compiled(params, f0["feats"], l2i, f0["can_bus"], prev0, flags[0])
    jax.block_until_ready(compiled(params, f0["feats"], l2i, f0["can_bus"],
                                   warm["bev"], flags[1]))
    del warm

    scene, n_checked = tr["scene_frames"], tr["checked_frames"]
    state = {"prev": prev0}
    kept: List[Dict] = []  # per call: logits, boxes, hits, overflow
    checked: List[Dict] = []

    def call(i):
        k = i % scene
        f = frames[k % len(frames)]
        out = compiled(params, f["feats"], l2i, f["can_bus"], state["prev"],
                       flags[0] if k == 0 else flags[1])
        state["prev"] = out["bev"]
        kept.append({n: out[n] for n in ("logits", "boxes", "hits", "overflow")})
        if i < n_checked:
            checked.append(out)
        return out

    ctx["setup_s"] = harness.setup_s()
    run = ctx["window"](call, 0)
    ctx["memory_peak_bytes"] = harness.memory_peak(ctx["devices"])
    for i in range(run["calls"], n_checked):
        jax.block_until_ready(call(i))
    kept = jax.device_get(kept)
    got = [{n: np.asarray(v) for n, v in jax.device_get(o).items()}
           for o in checked]
    for k in kept:
        bf.record_counts(mcfg, k["hits"], k["overflow"])
    bad = sum(1 for k in kept[:run["calls"]]
              if k["overflow"] > 0 or not (np.isfinite(k["logits"]).all()
                                           and np.isfinite(k["boxes"]).all()))
    del compiled, state, checked
    gc.collect()
    return {
        "images": run["calls"] * tr["batch"], "window_s": run["window_s"],
        "calls": run["calls"], "failed": bad * tr["batch"],
        "program": {"frames": got},
        "inputs": {"params": params, "frames": frames[:n_checked],
                   "lidar2img": np.asarray(l2i)},
    }


CELLS = {"infer": infer_cell}


def msda_calls(cfg: dict, mode: str, plans: Dict):
    """Per encoder layer one MSDA call per camera and one per TSA source;
    per decoder layer one."""
    enc = cfg["encoder_layers"]
    return ([work.MsdaCalls(plans[f"sca.cam{c}"], enc)
             for c in range(cfg["num_cams"])]
            + [work.MsdaCalls(plans["tsa"], cfg["tsa_sources"] * enc),
               work.MsdaCalls(plans["decoder"], cfg["decoder_layers"])])


# --------------------------------------------------------------------------
# the work counts
# --------------------------------------------------------------------------


def model_forward_flops(cfg: dict, hits) -> float:
    """Model FLOPs of one frame (multiply-add = 2 FLOPs), SCA over the
    cameras' hit queries ``hits``.

    Counts the projections and interpolation of every MSDA module (TSA's
    offsets and weights from its 2d-wide input, the value projection of
    both sources, of every camera's whole pyramid and of the BEV in each
    decoder layer), the output projections, the FFNs, decoder
    self-attention, the reg branches of every decoder layer and the last
    class branch.  Norms, softmaxes, the can_bus MLP, the mask and the
    packing are left out: they are a few FLOPs per element beside these.
    """
    d, ff, H = cfg["d_model"], cfg["d_ff"], cfg["num_heads"]
    hd = cfg["head_dim"]
    Q = cfg["bev_h"] * cfg["bev_w"]
    L = len(cfg["levels"])
    S = sum(h * w for h, w in cfg["levels"])
    nq, C, code = cfg["num_queries"], cfg["num_classes"], cfg["code_size"]
    interp = work.FWD_FLOPS_PER_CHANNEL
    P_t, P_s, P_d = cfg["tsa_points"], cfg["sca_points"], cfg["decoder_points"]
    n_src = cfg["tsa_sources"]

    tsa = (2 * Q * (2 * d) * H * n_src * P_t * 3      # offsets (2) and weights (1)
           + n_src * 2 * Q * d * d                    # value projections
           + interp * n_src * Q * H * P_t * hd        # interpolation
           + 2 * Q * d * d)                           # output projection
    sca = sum(2 * S * d * d                           # the camera's values
              + 2 * n * d * H * L * P_s * 3           # offsets and weights
              + interp * n * H * L * P_s * hd for n in hits) + 2 * Q * d * d
    ffn = lambda t: 2 * t * d * ff * 2  # noqa: E731
    enc = cfg["encoder_layers"] * (tsa + sca + ffn(Q))
    self_attn = 4 * 2 * nq * d * d + 2 * 2 * nq * nq * d
    cross = (2 * Q * d * d + 2 * nq * d * H * P_d * 3
             + interp * nq * H * P_d * hd + 2 * nq * d * d)
    reg = 2 * nq * (2 * d * d + d * code)
    dec = cfg["decoder_layers"] * (self_attn + cross + ffn(nq) + reg)
    heads = 2 * nq * d * 3 + 2 * nq * (2 * d * d + d * C)
    return float(enc + dec + heads)


def flops_per_image(cfg: dict, mode: str) -> float:
    """One six-camera frame's forward FLOPs, over the rig's hit queries."""
    bf = _program()
    hits = bf.camera_hits(bf.BEVFormerConfig.from_dict(cfg), lidar2img(cfg))
    return model_forward_flops(cfg, [int(n) for n in hits])


# --------------------------------------------------------------------------
# the check
# --------------------------------------------------------------------------


_REFERENCES: Dict = {}


def reference(cfg: dict, inputs: dict, precision=None, carry=True) -> Dict:
    """The reference's own sequence over the checked frames:
    ``{"frames": [...]}``.  One reference (and its compiled pieces) per
    process, configuration and precision."""
    import json

    import jax

    from repro.models import bevformer_ref

    pr = precision or bevformer_ref.FLOAT32
    key = (json.dumps(cfg, sort_keys=True), pr)
    if key not in _REFERENCES:
        _REFERENCES[key] = bevformer_ref.Reference(cfg, pr)
    params = jax.tree.map(lambda x: x.astype("float32"), inputs["params"])
    frames = [{"feats": f["feats"], "can_bus": f["can_bus"],
               "lidar2img": inputs["lidar2img"]} for f in inputs["frames"]]
    out = _REFERENCES[key].sequence(params, frames, carry)
    return {"frames": [{n: np.asarray(v) for n, v in o.items()} for o in out]}


def numbers(prog: List[Dict], ref: List[Dict]) -> Dict[str, float]:
    """The compared numbers of the program's frames against the
    reference's, frame by frame."""
    if not ref or len(prog) < len(ref):
        return {n: float("inf") for n in COMPARED}
    out = dict.fromkeys(COMPARED, 0.0)
    for p, r in zip(prog, ref):
        rms = lambda x, axis=None: np.sqrt(np.mean(np.square(x), axis=axis))  # noqa: E731
        out["logit_gap"] = max(out["logit_gap"], float(
            np.max(np.abs(p["logits"] - r["logits"])) / rms(r["logits"])))
        out["box_gap"] = max(out["box_gap"], float(np.max(
            np.abs(p["boxes"] - r["boxes"]).max(0) / rms(r["boxes"], 0))))
        out["bev_gap"] = max(out["bev_gap"], float(
            np.max(np.abs(p["bev"] - r["bev"])) / rms(r["bev"])))
        out["hit_count_gap"] += float(np.abs(
            np.asarray(p["hits"], np.int64) - np.asarray(r["hits"], np.int64)).sum())
    return out


def compare(mode, cfg, traffic, prog, inputs, hooks=None):
    """(numbers, every reading beside them)."""
    ref = reference(cfg, inputs)
    if hooks and "on_reference" in hooks:
        hooks["on_reference"](ref, inputs)
    n = numbers(prog["frames"], ref["frames"])
    readings = dict(n, hits=[int(x) for x in ref["frames"][0]["hits"]],
                    overflow=[int(f["overflow"]) for f in prog["frames"]])
    return n, readings


def control(mode, cfg, traffic, inputs, ref) -> Dict[str, float]:
    """The control's readings against the reference ``ref``: the
    reference computed in float8 (e4m3), the step below the
    configuration's bfloat16, in the program's place."""
    from repro.models import bevformer_ref

    return numbers(reference(cfg, inputs, bevformer_ref.FLOAT8)["frames"],
                   ref["frames"])


def no_carry_control(cfg, inputs, ref) -> Dict[str, float]:
    """The readings against ``ref`` of the reference run with no BEV
    carried (every frame a scene's first) in the program's place: what a
    program that drops the carried BEV reads."""
    return numbers(reference(cfg, inputs, carry=False)["frames"], ref["frames"])


def no_carry(frame):
    """A planted fault: every frame runs as a scene's first, with no
    previous BEV carried."""
    def f(p, feats, m, can_bus, prev_bev, first):
        return frame(p, feats, m, can_bus, prev_bev, True)
    return f


def logit_altered(frame):
    """A planted fault: one class logit 0.5 off where it is produced."""
    def f(*args):
        out = frame(*args)
        return dict(out, logits=out["logits"].at[0, 0].add(0.5))
    return f


FAULTS = {"no_carry": no_carry, "logit_altered": logit_altered}
