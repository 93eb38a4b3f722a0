"""Pallas-TPU forward kernel for multi-scale deformable attention.

Paper mapping (xMSDA §4.1 -> TPU):

* The whole pyramid's padded feature maps, packed into one slab, are
  **resident in VMEM** across all query blocks of one (batch, head
  group): the paper's "feature map fits UB" insight.  One launch per
  direction gathers every level; the corner rows are lifted by static
  per-level row offsets outside the kernel.
* **Scalar-addressed row gather.**  Mosaic has no general vector gather,
  so — like the paper's scalar-addressed pixel copies out of the on-chip
  buffer — the top-left corner row of every (query, head, level, point)
  arrives in SMEM, computed outside the kernel by cheap element-wise XLA
  (:func:`corner_indices`).  The x-pair partner sits at ``idx + 1`` and
  the y-pair partner at ``idx + Wp`` of the row-major slab, so a point's
  four corners are two two-row loads (:func:`fetch_pair`), branch-free.
  A table read is its step's base, carried by the loop, plus a static
  offset: one add per read.
* **Weights as vector operands.**  The corner weights arrive as VMEM
  rows, G heads' weights of a query on the lanes of one or more rows
  (:func:`weight_layout`).  The MXU spreads each head's weight over its
  segment of lanes (:func:`spread_weights`), the G heads' corner rows
  of a level merge into one row by lane selects, and one ``acc + w *
  row`` advances the level's accumulation chain: every lane adds what a
  scalar weight would, in the same order, so the output is bitwise that
  of a walk with scalar weights.  The scalar unit keeps only the corner
  rows and their addresses.
* **The walk.**  A step gathers :func:`step_points` points of every
  chain, at most :data:`STEP_ROWS` rows: the whole query when it fits,
  else a rolled loop of steps, each of which spreads the next step's
  weights on the MXU while it gathers, so no chain waits for them.
* **Heads on lanes.**  The slab is ``(rows, G*D)``: G heads of one group
  side by side on the lane axis (G*D = 128 for the DETR head_dim 32), so
  VMEM and HBM hold no lane padding.  A row load fetches all G heads of a
  pixel; each head keeps its own lanes through a lane mask.
* **Padding-based alignment fix**: each level is zero-padded to
  ``(H+2, W+2)`` so the corner pair never leaves the slab; out-of-bounds
  corners carry zero weight (``grid_sample(padding_mode='zeros')``).
* **Train mode** (``save_sampled``): the kernel also streams the raw
  gathered corners to HBM, so the backward's phase 1 (grad of the
  bilinear weights) issues no gathers.

The slab is stored in fp32 whatever the committed slab dtype: the values
are first rounded to that dtype (so a bf16 plan computes on bf16 data),
and Mosaic cannot address single rows of a packed 16-bit tile.

Grid: ``(B, head_groups, num_q_blocks)`` with ``q`` innermost, so the
slab block (index independent of ``q``) stays resident.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.obs import scopes

# lane width of one vreg: the head group fills it when head_dim allows
LANES = 128

# (row offset in the slab, padded width Wp, slab rows) per level
LevelGeom = Tuple[int, int, int]


def corner_indices(loc, H: int, W: int, Wp: int):
    """Bilinear corner bookkeeping shared by every backend.

    loc: (..., 2) fp32 in [0,1] (x, y), grid_sample(align_corners=False).
    Returns (idx00, lx, ly, masks) where ``idx00`` indexes the padded
    row-major slab of width ``Wp = W + 2`` whose real image origin sits
    at pixel (1, 1) — one LEADING and one TRAILING zero pad row/column.
    The x-pair partner is ``idx + 1`` and the y-pair partner ``idx + Wp``;
    with ``x0`` clipped into ``[-1, W-1]`` every pair lands in-slab and
    clipped-to-pad corners read zeros.  masks = (m00, m10, m01, m11)
    validity of each corner (required: e.g. ``x0 = -5`` clips to ``-1``
    whose +1 partner would read real column 0).
    """
    px = loc[..., 0] * W - 0.5
    py = loc[..., 1] * H - 0.5
    x0f = jnp.floor(px)
    y0f = jnp.floor(py)
    lx = px - x0f
    ly = py - y0f
    x0 = x0f.astype(jnp.int32)
    y0 = y0f.astype(jnp.int32)
    vx0 = (x0 >= 0) & (x0 < W)
    vx1 = (x0 + 1 >= 0) & (x0 + 1 < W)
    vy0 = (y0 >= 0) & (y0 < H)
    vy1 = (y0 + 1 >= 0) & (y0 + 1 < H)
    # clip into [-1, W-1]; +1 shift lands on the padded origin
    x0c = jnp.clip(x0, -1, W - 1) + 1
    y0c = jnp.clip(y0, -1, H - 1) + 1
    idx00 = y0c * Wp + x0c
    masks = (vx0 & vy0, vx1 & vy0, vx0 & vy1, vx1 & vy1)
    return idx00, lx, ly, masks


def head_group(num_heads: int, head_dim: int) -> int:
    """Heads per launch: the largest divisor G of ``num_heads`` with
    ``G * head_dim <= LANES`` (at least 1) — the head group whose slab
    rows fill one vreg row."""
    g = 1
    for c in range(1, num_heads + 1):
        if num_heads % c == 0 and c * head_dim <= LANES:
            g = c
    return g


def static_loop(n: int, body, init, unroll: bool):
    """``fori_loop`` over ``range(n)``, or a Python loop over static ints
    when ``unroll``.  Mosaic gets the unrolled form (static offsets, one
    straight-line block to schedule); the interpreter gets the rolled
    one, which compiles far faster.  Same operations in the same order.
    """
    if unroll:
        carry = init
        for i in range(n):
            carry = body(i, carry)
        return carry
    return jax.lax.fori_loop(0, n, body, init)


def corner_walk(P: int, unroll: bool, body, init):
    """Visit every (point, corner) of one head and level, point-major.
    ``body(p, c, carry) -> carry``."""
    return static_loop(P, lambda p, a: static_loop(
        4, lambda c, b: body(p, c, b), a, unroll), init, unroll)


def corner_offset(c, wp: int):
    """Row offset of corner ``c`` (x0y0, x1y0, x0y1, x1y1) from the
    top-left corner row."""
    return (c & 1) + (c >> 1) * wp


def saved_slot(level, corner, point, num_points: int):
    """Row of corner ``(level, corner, point)`` in a query's saved block."""
    return (level * 4 + corner) * num_points + point


def idx_slot(h, q, l, p, qb: int, L: int, P: int):
    """Entry of (head, query, level, point) in a block's corner-row table
    chunk, laid out (head, level, point, query): the query is minor, so
    every XLA-side step that builds the tables transposes large dims."""
    return ((h * L + l) * P + p) * qb + q


def w_slot(h, q, l, c, p, qb: int, L: int, P: int):
    """Entry of a corner weight in a block's backward weight table chunk,
    laid out (head, saved slot, query) — also the layout the backward's
    weight grads come out in."""
    return (h * (L * 4 * P) + saved_slot(l, c, p, P)) * qb + q


def weight_layout(L: int, P: int, D: int) -> Tuple[int, int]:
    """``(rows per query, slots per point)`` of the forward's weight rows.

    A query's corner weights are ``R`` rows of ``G*D`` lanes; lane
    ``D*h + j`` of a row holds head ``h``'s weight for slot ``j``.  A
    point's ``4L`` slots are (level, corner) ``4l + c``.  All points
    share one row when ``4LP <= D``; otherwise each point starts a row,
    and a point whose ``4L`` slots outgrow ``D`` takes several.
    """
    slots = 4 * L
    if slots * P <= D:
        return 1, slots
    per_point = -(-slots // D)
    return P * per_point, per_point * D


def weight_lane(h, l, c, p, L: int, P: int, D: int) -> Tuple[int, int]:
    """``(row, lane)`` of head ``h``'s weight of (level, corner, point)
    among one query's weight rows (:func:`weight_layout`)."""
    t = p * weight_layout(L, P, D)[1] + 4 * l + c
    return t // D, h * D + t % D


# the split scale: a float32 weight times this is split into bf16 parts
# that are all normal numbers (exact on a chip that flushes subnormals),
# for every weight of magnitude below 2**103
SPLIT_SCALE = 2.0 ** 24


def _round8(n: int) -> int:
    return -(-n // 8) * 8


def slot_grid(n: int, stride: int, D: int, lanes: int):
    """(round8(n), lanes) int32: the slot each lane of an expanded weight
    block holds, less ``stride`` times its sublane, so that ``grid ==
    first`` selects slot ``first + stride * j`` into sublane ``j``."""
    shape = (_round8(n), lanes)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1) % D
    return lane - stride * jax.lax.broadcasted_iota(jnp.int32, shape, 0)


def segment_matrix(D: int, lanes: int):
    """(lanes, lanes) bf16 0/1: entry (k, n) is 1 where lanes k and n
    belong to the same head."""
    shape = (lanes, lanes)
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0) // D
            == jax.lax.broadcasted_iota(jnp.int32, shape, 1) // D
            ).astype(jnp.bfloat16)


def spread_weights(row, pick, seg):
    """Weight rows out of one compact row, on the MXU.

    ``row`` (1, G*D) holds each head's weights on its own lanes;
    ``pick`` (n, G*D) bool keeps, in sublane ``j``, the lane of each
    head's segment that holds slot ``j``.  Multiplying by the segment
    matrix ``seg`` copies each head's kept weight to every lane of its
    segment.  The float32 weights go through the MXU as three exact bf16
    parts, ``hi + mid + lo``, each times a 0/1 entry and summed with
    zeros only, so every lane gets its weight back bit for bit.  A
    negative zero comes back as zero, and a subnormal as zero, as the
    chip flushes it in any product: no accumulation can tell either.
    """
    return spread_parts(split_weights(row, pick), seg)


def split_weights(row, pick):
    """The MXU operand of :func:`spread_weights` as its three bf16 parts,
    ``hi``, ``mid`` and ``lo``."""
    x = jnp.where(pick, jnp.broadcast_to(row * SPLIT_SCALE, pick.shape), 0.0)
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)


def spread_parts(parts, seg):
    """The parts of :func:`split_weights` times the segment matrix,
    summed back into float32 weights."""
    hi, mid, lo = (jnp.dot(part, seg, preferred_element_type=jnp.float32)
                   for part in parts)
    return ((hi + mid) + lo) * (1 / SPLIT_SCALE)


def row_block(q, k: int):
    """Rows ``[q*k, q*k + k)``: query ``q``'s block of ``k`` saved-corner
    rows, with the alignment Mosaic needs to prove for a packed store."""
    return pl.ds(pl.multiple_of(q * k, k & -k), k)


def fetch_row(slab_ref, i):
    """One (1, lanes) fp32 slab row at dynamic row ``i``."""
    return slab_ref[pl.ds(i, 1), :]


def lane_heads(group: int, head_dim: int):
    """(1, G*D) int32: the head of the group each lane belongs to."""
    return jax.lax.broadcasted_iota(
        jnp.int32, (1, group * head_dim), 1) // head_dim


def keep_corner(row_scratch, k, mine, row):
    """Write the lanes ``mine`` of ``row`` into saved-corner row ``k``."""
    row_scratch[pl.ds(k, 1), :] = jnp.where(
        mine, row, row_scratch[pl.ds(k, 1), :])


def fetch_pair(slab_ref, i):
    """Rows ``i`` and ``i + 1`` (a corner pair along x) as two (1, lanes)
    fp32 rows: one two-row load and one address, the second row rotated
    down a sublane."""
    two = slab_ref[pl.ds(i, 2), :]
    return two[0:1], two[1:2]


# rows one step of the forward's walk gathers at most.  A longer step
# hides the MXU's latency under more gathers but holds more corner-row
# table values live: on v5e the paper encoder's 80-row points gather at
# 1.19 bundles a row one to a step and 0.90 two to a step, SCA's 64-row
# points at 0.89 four to a step and 0.94 eight (tools/kernel_schedule.py)
STEP_ROWS = 256


def step_points(L: int, G: int, P: int) -> int:
    """Points one step of the forward's walk gathers: the most that
    divide the query's ``P`` and keep the step within :data:`STEP_ROWS`
    rows (at least one).  ``P`` of them make the step a whole query."""
    return max(k for k in range(1, P + 1)
               if P % k == 0 and (k == 1 or 4 * L * G * k <= STEP_ROWS))


def _gather_kernel(idx_ref, w_ref, slab_ref, out_ref, saved_ref, row_scratch,
                   *, levels: Tuple[LevelGeom, ...], P: int, G: int, D: int,
                   qb: int, unroll: bool):
    """One (batch, head group, query block) step of the forward.

    ``idx_ref``: this block's chunk of the SMEM corner-row table (see
    :func:`idx_slot`), corner rows already lifted into this launch's
    slab.  ``w_ref``: the block's weight rows in VMEM (see
    :func:`weight_layout`), validity mask and attention weight folded in.

    Every level is one accumulation chain over all G heads' lanes: the
    heads' corner rows are merged by lane selects and one
    ``acc + w * row`` advances the chain, with ``w`` a weight row spread
    on the MXU (:func:`spread_weights`).  Each lane sees the same adds in
    the same order as one (level, head) chain of scalar weights would.
    A walk step gathers :func:`step_points` points of every chain (the
    whole query when they are all of its points) and spreads the next
    step's weights meanwhile, so no chain waits for the MXU; the steps
    of a query are a rolled loop.  The corner-row table is linear in its
    indices, so a read is a static offset from the step's base.
    """
    L = len(levels)
    S = 4 * L  # weight slots of one point
    R, stride = weight_layout(L, P, D)
    packed = R == 1
    per_point = stride // D  # weight rows of a point when not packed
    GD = G * D
    heads = lane_heads(G, D)
    mine = [heads == h for h in range(G)]
    zero = jnp.zeros((1, GD), jnp.float32)
    save = saved_ref is not None
    seg = segment_matrix(D, GD)
    # a query whose weights share one row is one step
    k = P if packed else step_points(L, G, P)
    steps = P // k
    # the slot picks, built once per grid step
    if packed:
        pick_query = slot_grid(S * P, 1, D, GD) == 0
    else:
        pick_rows = [slot_grid(min(D, S - r * D), 1, D, GD) == 0
                     for r in range(per_point)]

    def weight_row(r):
        # row r of the block's weight rows
        return w_ref[pl.ds(r, 1), :]

    def step_weights(u):
        # the spread weights of the block's u-th walk step, one dot a
        # weight row of its points
        if packed:
            return (spread_weights(weight_row(u), pick_query, seg),)
        out = []
        for r in range(per_point):
            parts = [split_weights(weight_row((u * k + i) * per_point + r),
                                   pick_rows[r]) for i in range(k)]
            out.append(spread_parts(tuple(
                jnp.concatenate(part, axis=0) for part in zip(*parts)), seg))
        return tuple(out)

    def point_weights(spread, i):
        # the weight rows of the step's i-th point, slot 4l + c each
        if packed:
            return [spread[0][i * S + s:i * S + s + 1] for s in range(S)]
        out = []
        for r, e in enumerate(spread):
            n = min(D, S - r * D)
            base = i * _round8(n)
            out += [e[base + j:base + j + 1] for j in range(n)]
        return out

    def merged(rows):
        # each head's lanes from its own row
        out = rows[0]
        for h in range(1, G):
            out = jnp.where(mine[h], rows[h], out)
        return out

    def keep(l, c, p, row):
        if save:
            row_scratch[pl.ds(saved_slot(l, c, p, P), 1), :] = row

    def point_step(q, t, carry):
        # points t*k .. t*k + k-1 of every chain, each point's four
        # corners as two row pairs; ``base`` == idx_slot(0, q, 0, t*k)
        base, accs, spread = carry
        accs = list(accs)
        ws = [point_weights(spread, i) for i in range(k)]
        spread = step_weights(jnp.minimum(q * steps + t + 1,
                                          qb * steps - 1))
        for i in range(k):
            pbase = base + idx_slot(0, 0, 0, i, qb, L, P)
            for l, geom in enumerate(levels):
                corners = [[], [], [], []]
                for h in range(G):
                    at = idx_ref[pbase + idx_slot(h, 0, l, 0, qb, L, P)]
                    rows = fetch_pair(slab_ref, at) + fetch_pair(
                        slab_ref, at + geom[1])
                    for c, row in enumerate(rows):
                        corners[c].append(row)
                acc = accs[l]
                for c in range(4):
                    row = merged(corners[c])
                    acc = acc + ws[i][4 * l + c] * row
                    keep(l, c, t * k + i, row)
                accs[l] = acc
        return base + idx_slot(0, 0, 0, k, qb, L, P), tuple(accs), spread

    def body(q, spread):
        # ``spread``: the weights of the query's first step, spread by
        # the step before
        _, accs, spread = static_loop(
            steps, functools.partial(point_step, q), (q, (zero,) * L, spread),
            unroll and steps == 1)
        # the level sums added to the total in level order
        total = zero
        for acc in accs:
            total = total + acc
        out_ref[pl.ds(q, 1), :] = total
        if save:
            saved_ref[row_block(q, L * 4 * P), :] = row_scratch[...].astype(
                saved_ref.dtype)
        return spread

    jax.lax.fori_loop(0, qb, body, step_weights(0))


def compiler_params(vmem_limit: int):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=int(vmem_limit) if vmem_limit else None)


# Mosaic tiles a 1-D SMEM operand in chunks of this many elements
SMEM_TILE = 1024


def kernel_metadata(kind: str, levels: int, block_q: int) -> dict:
    """The metadata an MSDA kernel carries into its HLO custom call
    (``frontend_attributes={kernel_metadata={...}}``) and trace event."""
    return {"msda": kind,
            "levels": f"0-{levels - 1}",
            "block_q": str(block_q)}


def table_block(n: int) -> int:
    """Padded length of one query block's table chunk of ``n`` entries."""
    return -(-n // SMEM_TILE) * SMEM_TILE


def table_spec(n: int, NG: int, nq: int):
    """SMEM BlockSpec of a flat table with ``n`` entries per query block.

    One chunk per (batch, head group, query block), each padded to
    :func:`table_block`.  Mosaic tiles the last two dims of an SMEM
    block like VMEM ones, so neither a squeezed (batch, group) prefix
    nor a chunk off the 1-D tiling would lower.
    """
    def index(b, g, q):
        return ((b * NG + g) * nq + q,)

    return pl.BlockSpec((table_block(n),), index, memory_space=pltpu.SMEM)


def resident(shape, index_map):
    """BlockSpec of a block every query step shares (the slab, the grad
    slab): single-buffered, since it is fetched once per (batch, head
    group) and double buffering would only double its VMEM."""
    return pl.BlockSpec(shape, index_map, pipeline_mode=pl.Buffered(1))


def msda_gather(
    slab: jax.Array,   # (B, NG, rows, G*D) fp32, zero-padded levels packed
    idx: jax.Array,    # flat int32 table: top-left corner rows
    w: jax.Array,      # (B, NG, Qp*R, G*D) fp32 weight rows (weight_layout)
    *,
    levels: Tuple[LevelGeom, ...],
    num_points: int,
    head_dim: int,
    block_q: int,
    save_dtype=None,
    interpret: bool,
    vmem_limit: int = 0,
) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Weighted corner gather over one slab: ``(out, saved)``.

    ``out`` is (B, NG, Qp, G*D) fp32: per query and head the sum over
    levels, points and corners of weight x corner row.  With
    ``save_dtype`` the raw corners also come back as (B, NG, Qp*L*4P,
    G*D) in that dtype: each query's L*4P rows in :func:`saved_slot`
    order.
    """
    B, NG, rows, GD = slab.shape
    L, P, D = len(levels), num_points, head_dim
    G = GD // D
    R = weight_layout(L, P, D)[0]
    qp = w.shape[2] // R
    nq = qp // block_q
    K = L * 4 * P
    kernel = functools.partial(
        _gather_kernel, levels=tuple(levels), P=P, G=G, D=D, qb=block_q,
        unroll=not interpret)
    out_shapes = [jax.ShapeDtypeStruct((B, NG, qp, GD), jnp.float32)]
    out_specs = [pl.BlockSpec((None, None, block_q, GD),
                              lambda b, g, q: (b, g, q, 0))]
    scratch = []
    if save_dtype is not None:
        out_shapes.append(
            jax.ShapeDtypeStruct((B, NG, qp * K, GD), jnp.dtype(save_dtype)))
        out_specs.append(pl.BlockSpec((None, None, block_q * K, GD),
                                      lambda b, g, q: (b, g, q, 0)))
        scratch = [pltpu.VMEM((K, GD), jnp.float32)]
    else:
        kernel = functools.partial(_nosave_wrap, kernel)
    outs = pl.pallas_call(
        kernel,
        grid=(B, NG, nq),
        in_specs=[
            table_spec(G * block_q * L * P, NG, nq),
            pl.BlockSpec((None, None, block_q * R, GD),
                         lambda b, g, q: (b, g, q, 0)),
            # slab: same block for every q -> resident across the block walk
            resident((None, None, rows, GD), lambda b, g, q: (b, g, 0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=scratch,
        compiler_params=compiler_params(vmem_limit),
        interpret=interpret,
        name=scopes.GATHER_KERNEL,
        metadata=kernel_metadata("gather", L, block_q),
    )(idx, w, slab)
    if save_dtype is not None:
        return outs[0], outs[1]
    return outs[0], None


def _nosave_wrap(kernel, idx_ref, w_ref, slab_ref, out_ref):
    kernel(idx_ref, w_ref, slab_ref, out_ref, None, None)
