"""The gather kernel's chip loop structure, run in the interpreter.

On a TPU ``msda_gather`` walks a query in steps of
``msda_fwd.step_points`` points: the whole query when it fits
``msda_fwd.STEP_ROWS`` rows, else a rolled loop of steps with every
level's chain unrolled inside.  The interpreter rolls even a one-step
walk, so the chip's form never runs on a CPU unless forced.  These tests
force it (``unroll=True``) and hold it to the rolled form bitwise and to
the float32 oracle — for the step shapes of DETR's encoder, BEVFormer's
SCA and its one-level TSA, with and without the saved corners of the
training forward.  They also hold the gather to a
float32 numpy walk in the scalar-weight kernel's add order, bit for bit,
over every weight-row layout and step shape, and the MXU weight spread to
every float32 weight.  XLA:CPU is held to separate multiplies and adds
(``conftest.py``), as v5e computes them.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import msda_fwd, ops
from repro.kernels.ref import msda_ref

# head_dim 16 puts all 8 heads in one group: a query step gathers
# 3 * 8 * 3 * 4 = 288 rows, a rolled point loop.  At 2 points a step
# (192 rows) is the whole query, unrolled.
LEVELS = ((8, 6), (4, 3), (2, 2))
B, Q, H, D, P = 2, 19, 8, 16, 3
# (levels, points, head_dim) of the walk forms, 8 heads: this file's
# rolled 3-level pyramid, BEVFormer's SCA (4 levels x 8 points in heads
# of 32: four points a rolled step) and its one-level TSA (the whole
# query one step)
FORMS = {
    "fused": (LEVELS, P, D),
    "L4P8": (((6, 5), (3, 3), (2, 2), (1, 2)), 8, 32),
    "L1P4": (((7, 6),), 4, 32),
}


def _inputs(seed=0, P=P, levels=LEVELS, D=D):
    S = sum(h * w for h, w in levels)
    L = len(levels)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    value = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    # straddle the border: masked corners take the same walk
    loc = jax.random.uniform(ks[1], (B, Q, H, L, P, 2), minval=-0.2,
                             maxval=1.2)
    attn = jax.nn.softmax(
        jax.random.normal(ks[2], (B, Q, H, L * P)), axis=-1
    ).reshape(B, Q, H, L, P)
    return value, loc, attn


def _results(form, train, seed=0, P=None):
    """Forward, and for training plans the full VJP (whose weight grads
    read the forward's saved corners)."""
    levels, p, d = FORMS[form]
    params = ops.MSDAParams(spatial_shapes=levels, block_q=8, interpret=True,
                            save_sampled=train)
    op = ops.build_kernel_op(params)
    value, loc, attn = _inputs(seed, P or p, levels, d)
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
    L = len(LEVELS)
    assert msda_fwd.step_points(L, G, P) < P  # three levels: a rolled walk
    assert msda_fwd.step_points(1, G, P) == P  # one level: a whole query
    assert msda_fwd.step_points(1, G, 2) == 2


@pytest.mark.parametrize("train", [False, True], ids=["infer", "train"])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_chip_form_matches_rolled_form_and_oracle(form, train, chip_form):
    rolled = _results(form, train)
    chip_form()
    chip = _results(form, train)
    for name, a, b in zip(("out", "grad_value", "grad_loc", "grad_attn"),
                          chip, rolled):
        np.testing.assert_array_equal(a, b, err_msg=name)
    levels, p, d = FORMS[form]
    value, loc, attn = _inputs(0, p, levels, d)
    ref = msda_ref(value, levels, loc, attn)
    np.testing.assert_allclose(chip[0], np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("train", [False, True], ids=["infer", "train"])
def test_unrolled_query_step(train, chip_form):
    rolled = _results("fused", train, seed=3, P=2)
    chip_form()
    chip = _results("fused", train, seed=3, P=2)
    for name, a, b in zip(("out", "grad_value", "grad_loc", "grad_attn"),
                          chip, rolled):
        np.testing.assert_array_equal(a, b, err_msg=name)
    value, loc, attn = _inputs(seed=3, P=2)
    ref = msda_ref(value, LEVELS, loc, attn)
    np.testing.assert_allclose(chip[0], np.asarray(ref), atol=2e-5)


# (level shapes, points, head_dim) of the gather's weight layouts and walk
# shapes, with 8 heads: head_dim 16 puts them in one group of 8, 32 in
# two groups of 4
WALKS = {
    # one row per point, one point a rolled step
    "rolled-1-G8": (((6, 5), (3, 4), (2, 2)), 3, 16),
    # one row per point, two points a rolled step (the paper encoder's)
    "rolled-2-G4": (((5, 6), (4, 3), (3, 2), (2, 2), (1, 2)), 4, 32),
    # four points a rolled step (BEVFormer's SCA)
    "rolled-4-G4": (((4, 5), (3, 3), (2, 2), (1, 2)), 8, 32),
    # 4L > D: two weight rows per point
    "two-rows-G8": (((4, 4), (3, 3), (2, 3), (2, 2), (1, 1)), 2, 16),
    # every point in one row, the whole query one step (TSA's)
    "packed-G4": (((7, 6),), 4, 32),
    "packed-G8": (((7, 6),), 4, 16),
}


def _walk_inputs(shapes, P, D, Q=13, qb=8, seed=0):
    """A head group's slab, corner-row table, weights and weight rows."""
    H = 8
    G = msda_fwd.head_group(H, D)
    L, GD = len(shapes), G * D
    rng = np.random.default_rng(seed)
    offs, rows = ops.pyramid_row_offsets(shapes)
    geoms = tuple((o, w + 2, ops.slab_rows((h, w)))
                  for o, (h, w) in zip(offs, shapes))
    slab = rng.standard_normal((1, 1, rows, GD)).astype(np.float32)
    qp = -(-Q // qb) * qb
    # top-left corner rows anywhere their four corners stay in the level
    top = np.stack([o + rng.integers(0, h + 1, (qp, G, P)) * (w + 2)
                    + rng.integers(0, w + 1, (qp, G, P))
                    for o, (h, w) in zip(offs, shapes)], axis=2)  # q,h,l,p
    w = rng.random((qp, G, L, 4, P)).astype(np.float32)
    w[rng.random(w.shape) < 0.1] = 0.0
    w[rng.random(w.shape) < 0.1] = -0.0
    w[Q:] = 0.0
    chunk = msda_fwd.table_block(G * qb * L * P)
    idx = np.zeros((qp // qb, chunk), np.int32)
    R, _ = msda_fwd.weight_layout(L, P, D)
    wrows = np.zeros((1, 1, qp * R, GD), np.float32)
    for q in range(qp):
        for h in range(G):
            for l in range(L):
                for p in range(P):
                    idx[q // qb, msda_fwd.idx_slot(h, q % qb, l, p, qb, L, P)] = (
                        top[q, h, l, p])
                    for c in range(4):
                        r, lane = msda_fwd.weight_lane(h, l, c, p, L, P, D)
                        wrows[0, 0, q * R + r, lane] = w[q, h, l, c, p]
    return geoms, slab, idx.reshape(-1), w, wrows, top


def _parent_walk(geoms, slab, w, top, P, D):
    """Per query: every level's chain of ``acc + w * row``, heads on
    their own lanes, points outer and corners inner; the level sums
    added in level order — the scalar-weight kernel's adds, in float32.
    Also each query's raw corners in saved-slot order."""
    qp, G, L = w.shape[0], w.shape[1], len(geoms)
    out = np.zeros((qp, G * D), np.float32)
    saved = np.zeros((qp * L * 4 * P, G * D), np.float32)
    order = [(p, c) for p in range(P) for c in range(4)]
    for q in range(qp):
        total = np.zeros(G * D, np.float32)
        for l, (_, wp, _) in enumerate(geoms):
            acc = np.zeros(G * D, np.float32)
            for p, c in order:
                for h in range(G):
                    lanes = slice(h * D, (h + 1) * D)
                    row = slab[0, 0, top[q, h, l, p]
                               + msda_fwd.corner_offset(c, wp), lanes]
                    acc[lanes] = acc[lanes] + w[q, h, l, c, p] * row
                    saved[q * L * 4 * P + msda_fwd.saved_slot(l, c, p, P),
                          lanes] = row
            total = total + acc
        out[q] = total
    return out, saved


@pytest.mark.parametrize("save", [False, True], ids=["infer", "train"])
@pytest.mark.parametrize("walk", sorted(WALKS))
def test_gather_equals_scalar_weight_walk(walk, save, chip_form):
    """The chip's form of the gather adds, in every lane, exactly what a
    walk of scalar weights adds, in the same order: bitwise."""
    shapes, P, D = WALKS[walk]
    geoms, slab, idx, w, wrows, top = _walk_inputs(shapes, P, D)
    chip_form()
    out, saved = msda_fwd.msda_gather(
        jnp.asarray(slab), jnp.asarray(idx), jnp.asarray(wrows),
        levels=geoms, num_points=P, head_dim=D, block_q=8,
        save_dtype=jnp.float32 if save else None, interpret=True)
    want, want_saved = _parent_walk(geoms, slab, w, top, P, D)
    np.testing.assert_array_equal(np.asarray(out)[0, 0], want)
    if save:
        np.testing.assert_array_equal(np.asarray(saved)[0, 0], want_saved)


@pytest.mark.parametrize("L,P,D", [(1, 4, 32), (5, 4, 32), (4, 8, 32),
                                   (5, 2, 16), (1, 4, 16), (3, 3, 16)],
                         ids=lambda v: str(v))
def test_weight_rows_place_every_weight_once(L, P, D):
    """``ops._weight_rows`` puts every (head, level, corner, point) weight
    of a query in the lane ``msda_fwd.weight_lane`` names, and nothing
    anywhere else."""
    G = msda_fwd.head_group(8, D)
    NG, qp = 8 // G, 16
    n = L * NG * G * 4 * P * qp
    w = (np.arange(n, dtype=np.float32) + 1).reshape(L, 1, NG, G, 4 * P, qp)
    rows = np.asarray(ops._weight_rows(jnp.asarray(w), D))
    R, _ = msda_fwd.weight_layout(L, P, D)
    assert rows.shape == (1, NG, qp * R, G * D)
    seen = np.zeros(rows.shape, bool)
    for g in range(NG):
        for q in range(qp):
            for h in range(G):
                for l in range(L):
                    for c in range(4):
                        for p in range(P):
                            r, lane = msda_fwd.weight_lane(h, l, c, p, L, P, D)
                            assert not seen[0, g, q * R + r, lane]
                            seen[0, g, q * R + r, lane] = True
                            assert rows[0, g, q * R + r, lane] == (
                                w[l, 0, g, h, c * P + p, q])
    assert not rows[~seen].any()


FLOATS = st.floats(width=32, allow_nan=False, allow_infinity=False,
                   min_value=-(2.0 ** 103), max_value=2.0 ** 103,
                   exclude_min=True, exclude_max=True)
EDGES = [0.0, -0.0, 2.0 ** -149, -(2.0 ** -149), 2.0 ** -127, 2.0 ** -126,
         -(2.0 ** -126), 1.1754944e-38, 7e-34, 2.0 ** -103, 1e-30,
         1.0000001, 3.1415927, -2.7182817e10, 1e30,
         np.nextafter(np.float32(2.0 ** 103), np.float32(0))]


@settings(max_examples=30, deadline=None)
@given(st.lists(FLOATS, min_size=16, max_size=16))
def test_weight_split_rebuilds_every_float32(values):
    """``spread_weights`` hands every lane of a head's segment that head's
    weight back exactly, for any float32 of magnitude below 2**103: a
    subnormal weight comes back as zero, as the chip's flush of it to
    zero in any product makes it, and a negative zero as zero."""
    D, G = 16, 8
    x = np.asarray(values, np.float32)
    _check_spread(np.concatenate([x, np.asarray(EDGES, np.float32)]), D, G)


def _check_spread(x, D, G):
    # head h's slots, D of them, in lanes D*h .. D*h + D-1
    x = np.resize(x, G * D).astype(np.float32)
    pick = msda_fwd.slot_grid(D, 1, D, G * D) == 0
    seg = msda_fwd.segment_matrix(D, G * D)
    got = np.asarray(jax.jit(msda_fwd.spread_weights)(
        jnp.asarray(x[None]), pick, seg))
    normal = np.where(np.abs(x) >= np.float32(2.0 ** -126), x, 0)
    for h in range(G):
        for j in range(D):
            # sublane j: slot j of every head on all of its segment's lanes
            np.testing.assert_array_equal(
                got[j, h * D:(h + 1) * D], np.full(D, normal[h * D + j]))


def test_weight_split_edges():
    _check_spread(np.asarray(EDGES, np.float32), 16, 8)
    _check_spread(np.asarray(EDGES, np.float32)[::-1], 32, 4)


def test_inference_builds_no_backward_weight_table():
    """A program that takes no gradient computes the forward's weight
    rows only: the backward's flat SMEM weight table is dropped, and it
    is there once a gradient is taken."""
    L, Qp = len(LEVELS), 24
    G = msda_fwd.head_group(H, D)
    n = B * (H // G) * (Qp // 8) * msda_fwd.table_block(4 * G * 8 * L * P)
    table = f"f32[{n}]"
    params = ops.MSDAParams(spatial_shapes=LEVELS, block_q=8, interpret=True)
    op = ops.build_kernel_op(params)
    value, loc, attn = _inputs()
    hlo = jax.jit(op).lower(value, loc, attn).compile().as_text()
    assert "f32[2,1,72,128]" in hlo  # the weight rows, 3 per query
    assert table not in hlo
    grad = jax.grad(lambda v, l, a: jnp.sum(op(v, l, a)), argnums=(1, 2))
    assert table in jax.jit(grad).lower(value, loc, attn).compile().as_text()
