"""BEVFormer-base (``repro.models.bevformer``) against its plain float32
reference (``repro.models.bevformer_ref``), at a small size on the CPU.

The small size keeps every head width of the published configuration
(``chipbench/configs/bevformer-base.json``: d=256, 8 heads of 32, FFN 512,
8 SCA points over 4 pillar anchors, 4 TSA and decoder points, 10 classes,
code size 10) and cuts the BEV to 8x8, the rig to 2 cameras of a 128x192
image with 2 levels, the depth to 2+2 layers and the object queries to
20.  Weights are seeded (``init_params``).
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MSDAConfig
from repro.core import msda as msda_mod
from repro.models import bevformer as bf
from repro.models import bevformer_ref as ref
from repro.obs import registry

with open(os.path.join(os.path.dirname(__file__), "..", "chipbench", "configs",
                       "bevformer-base.json")) as _f:
    PUBLISHED = json.load(_f)
SMALL = dict(PUBLISHED, levels=[[16, 24], [8, 12]], num_cams=2,
             encoder_layers=2, decoder_layers=2, bev_h=8, bev_w=8,
             num_queries=20, image_size=[128, 192])
# float32 on both sides; they differ by the order of sums and XLA:CPU's
# FMA contraction: 2e-6 of the logits' root mean square at most over
# these seeds, so 5e-5 leaves room and still catches any term left out
TOL = 5e-5


def _camera(yaw, pos, f, cx=96.0, cy=64.0):
    a = np.deg2rad(yaw)
    rot = np.array([[np.sin(a), -np.cos(a), 0.0], [0.0, 0.0, -1.0],
                    [np.cos(a), np.sin(a), 0.0]])
    ext = np.eye(4)
    ext[:3, :3], ext[:3, 3] = rot, -rot @ np.asarray(pos)
    k = np.eye(4)
    k[0, 0] = k[1, 1] = f
    k[0, 2], k[1, 2] = cx, cy
    return k @ ext


RIG = np.stack([_camera(0.0, (0.7, 0.0, -0.3), 150.0),
                _camera(180.0, (-0.9, 0.0, -0.3), 100.0)]).astype(np.float32)


def small_cfg(backend="auto"):
    cfg = bf.with_rig(bf.BEVFormerConfig.from_dict(SMALL), RIG)
    return dataclasses.replace(cfg, backend=backend)


def frames(seed, n=3, yaw=4.0):
    key = jax.random.PRNGKey(seed)
    out = []
    for i in range(n):
        key, kf, kc = jax.random.split(key, 3)
        cb = jax.random.normal(kc, (18,))
        cb = cb.at[0].set(2.0 + i).at[1].set(-1.5).at[17].set(yaw * (i + 1) / n)
        out.append({"feats": jax.random.normal(kf, (2, 16 * 24 + 8 * 12, 256)) * 0.05,
                    "lidar2img": RIG, "can_bus": cb})
    return out


def run_program(cfg, params, frs, first_prev=None):
    f = jax.jit(lambda p, x, m, cb, prev, first: bf.frame(p, cfg, x, m, cb, prev, first))
    prev = jnp.zeros((cfg.bev_queries, cfg.d_model)) if first_prev is None else first_prev
    outs = []
    for i, fr in enumerate(frs):
        o = f(params, fr["feats"], fr["lidar2img"], fr["can_bus"], prev, i == 0)
        prev = o["bev"]
        outs.append(o)
    return outs


def gap(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return float(np.max(np.abs(a - b)) / np.sqrt(np.mean(np.square(b))))


@pytest.mark.parametrize("backend", ["cpu", "pallas"])
def test_model_matches_reference_over_three_frames(backend):
    cfg = small_cfg(backend)
    params = bf.init_params(jax.random.PRNGKey(3), cfg)
    frs = frames(5)
    got = run_program(cfg, params, frs)
    want = ref.Reference(SMALL).sequence(params, frs)
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g["hits"]), w["hits"])
        assert int(g["overflow"]) == 0
        for k in ("logits", "boxes", "bev"):
            assert gap(g[k], w[k]) < TOL, k
    # the carried BEV moves the later frames: the first frame's BEV is not
    # the second's
    assert gap(got[1]["bev"], got[0]["bev"]) > 0.1


def test_first_frame_reads_no_previous_bev():
    """On a first frame TSA's two sources are the layer's query twice
    (upstream's ``stack([query, query])``): the previous BEV handed in
    is not read, and the frame equals the reference's frame without one."""
    cfg = small_cfg()
    params = bf.init_params(jax.random.PRNGKey(4), cfg)
    fr = frames(6, n=1)
    a = run_program(cfg, params, fr)[0]
    b = run_program(cfg, params, fr, first_prev=jax.random.normal(
        jax.random.PRNGKey(9), (cfg.bev_queries, cfg.d_model)))[0]
    for k in ("logits", "boxes", "bev"):
        assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), k
    w = ref.Reference(SMALL).frame(params, fr[0]["feats"], RIG, fr[0]["can_bus"])
    assert gap(a["bev"], w["bev"]) < TOL


@pytest.mark.parametrize("size", [8, 200])
def test_rotation_matches_reference(size):
    """Nearest-neighbour rotation with zero fill, cell for cell, except a
    cell whose source lies within 1e-4 of a rounding tie (the two
    implementations may round such a source either way)."""
    cfg = dataclasses.replace(bf.BEVFormerConfig.from_dict(PUBLISHED),
                              bev_h=size, bev_w=size)
    bev = jnp.arange(size * size, dtype=jnp.float32)[:, None] + 1.0
    rot = jax.jit(lambda a: bf.rotate_bev(cfg, bev, a))
    rng = np.random.default_rng(0)
    for angle in [0.0, 90.0, 30.0, -3.0, *rng.uniform(-5, 5, 6)]:
        a32 = np.float32(angle)
        got = np.asarray(rot(a32))[:, 0]
        want = np.asarray(ref.rotate({"bev_h": size, "bev_w": size}, bev, a32))[:, 0]
        r = np.deg2rad(-np.float64(a32))
        c = (size - 1) / 2
        j, i = np.meshgrid(np.arange(size), np.arange(size))
        sx = np.cos(r) * (j - c) + np.sin(r) * (i - c) + c
        sy = -np.sin(r) * (j - c) + np.cos(r) * (i - c) + c
        tie = ((np.abs(np.abs(sx - np.floor(sx)) - 0.5) < 1e-4)
               | (np.abs(np.abs(sy - np.floor(sy)) - 0.5) < 1e-4)).reshape(-1)
        assert np.array_equal(got[~tie], want[~tie]), angle
        if angle == 30.0:  # the corners have no source: zero fill
            assert (want == 0).any()
    # a turn by 0 moves nothing
    assert np.array_equal(np.asarray(rot(np.float32(0.0)))[:, 0], np.asarray(bev)[:, 0])


def test_shift_matches_reference():
    cfg = bf.BEVFormerConfig.from_dict(PUBLISHED)
    rng = np.random.default_rng(1)
    for _ in range(5):
        cb = jnp.asarray(rng.normal(size=18), jnp.float32).at[0:2].set(
            jnp.asarray(rng.uniform(-8, 8, 2), jnp.float32))
        np.testing.assert_allclose(
            np.asarray(bf.ego_shift(cfg, cb)),
            np.asarray(ref.shift({"pc_range": list(cfg.pc_range), "bev_h": 200,
                                  "bev_w": 200}, cb)), rtol=1e-5, atol=1e-7)
    # driving straight ahead along the ego yaw shifts the grid along y alone
    cb = jnp.zeros(18).at[0].set(3.0).at[16].set(0.0)
    sx, sy = np.asarray(bf.ego_shift(cfg, cb))
    assert abs(sx) < 1e-7 and sy == pytest.approx(3.0 / 0.512 / 200)


def test_rebatch_matches_dense_masked_computation():
    """Each camera's packed hit queries through its MSDA call, scattered
    back and averaged, equal every query through every camera's call,
    masked by the hits and averaged (up to the order of the value
    projection's sums over a different query count: 1e-5)."""
    cfg = small_cfg("cpu")
    params = bf.init_params(jax.random.PRNGKey(7), cfg)
    lp = jax.tree.map(lambda x: x[0], params["enc"]["sca"])
    q = jax.random.normal(jax.random.PRNGKey(8), (cfg.bev_queries, cfg.d_model))
    feats = jax.random.normal(jax.random.PRNGKey(9), (2, cfg.cam_pixels, 256)) * 0.05
    rb = bf.Rebatch(cfg, jnp.asarray(RIG))
    got = bf.spatial_cross_attention(lp, cfg, q, feats, rb)

    uv, mask = bf.project(cfg, jnp.asarray(RIG))
    hit = mask.any(axis=1)
    slots = 0.0
    for c in range(2):
        refs = jnp.transpose(jnp.clip(uv[c], -bf.REF_CLAMP, bf.REF_CLAMP), (1, 0, 2))
        out = bf.deformable_attention(lp, cfg.sca_msda(), q, feats[c], refs)
        slots = slots + jnp.where(hit[c][:, None], out, 0.0)
    count = jnp.maximum(hit.sum(0), 1)[:, None]
    want = slots / count @ lp["out"]["w"] + lp["out"]["b"] + q
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    assert np.array_equal(np.asarray(rb.hits), bf.camera_hits(cfg, RIG))
    assert np.array_equal(np.asarray(rb.count), np.asarray(count[:, 0], np.float32))


def test_overflow_is_counted_and_recorded():
    """Bounds below a camera's hits: the overflow is the hits past them,
    the packed rows are the first hits in query order, and the frame's
    counts land in the registry."""
    cfg = small_cfg()
    hits = bf.camera_hits(cfg, RIG)
    short = dataclasses.replace(cfg, cam_bounds=(int(hits[0]) - 3, int(hits[1])))
    rb = bf.Rebatch(short, jnp.asarray(RIG))
    assert int(rb.overflow) == 3
    _, mask = bf.project(cfg, jnp.asarray(RIG))
    first = np.nonzero(np.asarray(mask.any(axis=1))[0])[0][: hits[0] - 3]
    assert np.array_equal(np.asarray(rb.index[0]), first)
    dropped = np.nonzero(np.asarray(mask.any(axis=1))[0])[0][hits[0] - 3:]
    assert (np.asarray(rb.slot[0])[dropped] == short.cam_bounds[0]).all()
    full = bf.Rebatch(cfg, jnp.asarray(RIG))
    assert int(full.overflow) == 0

    registry.reset("bevformer")
    bf.record_counts(short, rb.hits, rb.overflow)
    assert registry.gauge("bevformer.sca.hits").value(camera=0) == hits[0]
    assert registry.gauge("bevformer.sca.slots").value(camera=0) == hits[0] - 3
    assert registry.counter("bevformer.sca.overflow").total() == 3


def test_camera_bounds_cover_the_hits():
    hits = [0, 1, 63, 64, 6127, 9537]
    bounds = bf.camera_bounds(hits)
    assert all(b >= n * 1.01 and b % 64 == 0 for n, b in zip(hits, bounds))
    assert bounds[-2:] == (6208, 9664)


# --------------------------------------------------------------------------
# core/msda.py: anchors per query
# --------------------------------------------------------------------------


def _msda_inputs(Z=None, P=4):
    mcfg = MSDAConfig(levels=((8, 12), (4, 6)), num_points=P, num_heads=8)
    d, Q = 256, 16
    k = jax.random.split(jax.random.PRNGKey(0), 8)
    L = len(mcfg.levels)
    p = {"value_proj": jax.random.normal(k[0], (d, d)) / 16,
         "out_proj": jax.random.normal(k[1], (d, d)) / 16,
         "w_offsets": jax.random.normal(k[2], (d, 8 * L * P * 2)) / 16,
         "b_offsets": jax.random.normal(k[3], (8 * L * P * 2,)),
         "w_weights": jax.random.normal(k[4], (d, 8 * L * P)) / 16,
         "b_weights": jnp.zeros((8 * L * P,))}
    q = jax.random.normal(k[5], (1, Q, d))
    v = jax.random.normal(k[6], (1, 8 * 12 + 4 * 6, d))
    shape = (1, Q, 2) if Z is None else (1, Q, Z, 2)
    refs = jax.random.uniform(k[7], shape)
    return p, mcfg, q, v, refs


@pytest.mark.parametrize("backend", ["cpu", "pallas"])
def test_msda_one_anchor_is_bitwise_the_point_path(backend):
    p, mcfg, q, v, refs = _msda_inputs()
    mcfg = dataclasses.replace(mcfg, backend=backend)
    f = jax.jit(lambda r: msda_mod.msda_attention(p, mcfg, q, v, r))
    a = np.asarray(f(refs))
    b = np.asarray(f(refs[:, :, None, :]))
    assert np.array_equal(a, b)


def test_msda_anchors_place_point_p_on_anchor_p_mod_z():
    """(B, Q, Z, 2) reference points: point p of every (head, level)
    samples around anchor p % Z; the same call with each point's anchor
    as a one-point-per-anchor layout, built by hand, agrees bitwise."""
    Z, P = 4, 8
    p, mcfg, q, v, refs = _msda_inputs(Z=Z, P=P)
    got = msda_mod.msda_attention(p, mcfg, q, v, refs)
    L, H, D = 2, 8, 32
    off = (q @ p["w_offsets"] + p["b_offsets"]).reshape(1, 16, H, L, P, 2)
    wh = jnp.asarray([[12, 8], [6, 4]], jnp.float32)
    anchor = refs[:, :, jnp.arange(P) % Z]  # (1, Q, P, 2)
    loc = anchor[:, :, None, None] + off / wh[None, None, None, :, None, :]
    aw = jax.nn.softmax((q @ p["w_weights"] + p["b_weights"]).reshape(1, 16, H, L * P),
                        -1).reshape(1, 16, H, L, P)
    plan = msda_mod.attention_plan(mcfg, num_queries=16, head_dim=D, dtype=q.dtype)
    want = plan((v @ p["value_proj"]).reshape(1, -1, H, D), loc, aw) @ p["out_proj"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError):
        msda_mod.msda_attention(p, mcfg, q, v, refs[:, :, :3])


def test_model_msda_call_is_the_shared_op():
    """The model's MSDA call (``deformable_attention``, biased linears)
    with its output projection, on zero value and output biases, equals
    ``msda_attention`` over the same weights, anchors and all."""
    Z, P = 4, 8
    p, mcfg, q, v, refs = _msda_inputs(Z=Z, P=P)
    zero = jnp.zeros((256,))
    lin = {"value": {"w": p["value_proj"], "b": zero},
           "offsets": {"w": p["w_offsets"], "b": p["b_offsets"]},
           "weights": {"w": p["w_weights"], "b": p["b_weights"]}}
    got = bf.deformable_attention(lin, mcfg, q[0], v[0], refs[0]) @ p["out_proj"]
    want = msda_mod.msda_attention(p, mcfg, q, v, refs)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6,
                               atol=1e-6)
