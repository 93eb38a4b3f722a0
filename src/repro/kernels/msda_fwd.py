"""Pallas-TPU forward kernel for multi-scale deformable attention.

Paper mapping (xMSDA §4.1 -> TPU):

* The level's (or the whole pyramid's) padded feature map is **resident
  in VMEM** across all query blocks of one (batch, head group): the
  paper's "feature map fits UB" insight.
* **Scalar-addressed row gather.**  Mosaic has no general vector gather,
  so — like the paper's scalar-addressed pixel copies out of the on-chip
  buffer — the top-left corner row of every (query, head, level, point)
  and its four bilinear weights arrive in SMEM, computed outside the
  kernel by cheap element-wise XLA (:func:`corner_indices`).  The
  x-pair partner sits at ``idx + 1`` and the y-pair partner at
  ``idx + Wp`` of the row-major slab, so a point's four corners are two
  two-row loads (:func:`fetch_pair`), branch-free.
* **The row loop's scalar budget.**  The scalar unit sets the pace: a
  bundle has two scalar slots, at most one of them reading SMEM, and a
  gathered row costs a weight read and its address, plus a corner-row
  read, its address and a VMEM address per point or row pair.  Each
  (level, head) pair is one accumulation chain.  Once a query's rows
  exceed :data:`UNROLLED_ROWS` the point loop is rolled and one step of
  it advances every chain by a point: the chains' adds hide each
  other's latency, and the step's L*G*5 table values stay in the scalar
  register file (unrolled over every point, the scheduler hoists them
  all and spills them to SMEM).  A table read is its step's base,
  carried by the loop, plus a static offset: one add per read.
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
* **Fused whole pyramid**: one launch gathers every level from one
  packed super-slab (the corner rows are lifted by static per-level row
  offsets outside the kernel), accumulating the per-level partials in
  the same order the per-level launches sum theirs — so every fusion
  tier is bitwise identical.

The slab is stored in fp32 whatever the committed slab dtype: the values
are first rounded to that dtype (so a bf16 plan computes on bf16 data),
and Mosaic cannot address single rows of a packed 16-bit tile.

Grid: ``(B, head_groups, num_q_blocks)`` with ``q`` innermost, so the
slab block (index independent of ``q``) stays resident.

Ablation variants (they compile for v5e too; ``chip_smoke.py`` checks
each against the oracle there): ``onehot`` levels fetch each row as a
one-hot MXU matmul; ``fuse_gather=False`` walks the corners corner-major
(four per-corner passes) instead of point-major.
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

# (row offset in the slab, padded width Wp, slab rows, one-hot?) per level
LevelGeom = Tuple[int, int, int, bool]


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


def corner_walk(P: int, fuse: bool, unroll: bool, body, init):
    """Visit every (point, corner) of one head and level: point-major for
    the fused walk, corner-major (four per-corner passes) for the
    ablation.  ``body(p, c, carry) -> carry``."""
    if fuse:
        return static_loop(P, lambda p, a: static_loop(
            4, lambda c, b: body(p, c, b), a, unroll), init, unroll)
    return static_loop(4, lambda c, a: static_loop(
        P, lambda p, b: body(p, c, b), a, unroll), init, unroll)


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
    """Entry of a corner weight in a block's weight table chunk, laid out
    (head, saved slot, query) — also the layout the backward's weight
    grads come out in."""
    return (h * (L * 4 * P) + saved_slot(l, c, p, P)) * qb + q


def row_block(q, k: int):
    """Rows ``[q*k, q*k + k)``: query ``q``'s block of ``k`` saved-corner
    rows, with the alignment Mosaic needs to prove for a packed store."""
    return pl.ds(pl.multiple_of(q * k, k & -k), k)


def fetch_row(slab_ref, geom: LevelGeom, i):
    """One (1, lanes) fp32 slab row at dynamic row ``i``."""
    off, _, rows, onehot = geom
    if onehot:
        # MXU route: the row as a one-hot matmul against the level rows
        hot = (jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
               == i - off).astype(jnp.float32)
        return jnp.dot(hot, slab_ref[off:off + rows, :],
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    return slab_ref[pl.ds(i, 1), :]


def lane_heads(group: int, head_dim: int):
    """(1, G*D) int32: the head of the group each lane belongs to."""
    return jax.lax.broadcasted_iota(
        jnp.int32, (1, group * head_dim), 1) // head_dim


def keep_corner(row_scratch, k, mine, row):
    """Write the lanes ``mine`` of ``row`` into saved-corner row ``k``."""
    row_scratch[pl.ds(k, 1), :] = jnp.where(
        mine, row, row_scratch[pl.ds(k, 1), :])


def fetch_pair(slab_ref, geom: LevelGeom, i):
    """Rows ``i`` and ``i + 1`` (a corner pair along x) as two (1, lanes)
    fp32 rows: one two-row load and one address, the second row rotated
    down a sublane."""
    if geom[3]:
        return fetch_row(slab_ref, geom, i), fetch_row(slab_ref, geom, i + 1)
    two = slab_ref[pl.ds(i, 2), :]
    return two[0:1], two[1:2]


# rows one unrolled query step may gather: past this, the table values
# the scheduler keeps live outgrow the scalar register file and spill to
# SMEM (on v5e a 64-row step spills 3 words, a 128-row one 131)
UNROLLED_ROWS = 64


def _gather_kernel(idx_ref, w_ref, slab_ref, out_ref, saved_ref, row_scratch,
                   *, levels: Tuple[LevelGeom, ...], P: int, G: int, D: int,
                   qb: int, fuse_gather: bool, unroll: bool):
    """One (batch, head group, query block) step of the forward.

    ``idx_ref`` / ``w_ref``: this block's chunk of the SMEM tables (see
    :func:`idx_slot` / :func:`w_slot`), corner rows already lifted into
    this launch's slab, weights with the validity mask and the attention
    weight folded in.

    Every (level, head) pair is one accumulation chain.  A step of the
    walk's outer index (the point, or the corner of the corner-major
    walk) advances every chain; the steps are a rolled loop when the
    query's rows exceed :data:`UNROLLED_ROWS`.  The tables are linear in
    their indices, so a read is a static offset from the step's base.
    """
    L = len(levels)
    heads = lane_heads(G, D)
    zero = jnp.zeros((1, G * D), jnp.float32)
    rolled = not unroll or L * G * P * 4 > UNROLLED_ROWS
    save = saved_ref is not None

    def point_step(q, p, carry):
        # point p of every chain, its four corners as two row pairs;
        # ``base`` == idx_slot(0, q, 0, p) == w_slot(0, q, 0, 0, p)
        base, accs = carry
        accs = list(accs)
        for l, geom in enumerate(levels):
            kept = [zero] * 4  # the level's corners, each head on its lanes
            for h in range(G):
                i = idx_ref[base + idx_slot(h, 0, l, 0, qb, L, P)]
                rows = fetch_pair(slab_ref, geom, i) + fetch_pair(
                    slab_ref, geom, i + geom[1])
                acc = accs[l * G + h]
                for c, row in enumerate(rows):
                    w = w_ref[base + w_slot(h, 0, l, c, 0, qb, L, P)]
                    acc = acc + w * row
                    if save:
                        kept[c] = jnp.where(heads == h, row, kept[c])
                accs[l * G + h] = acc
            if save:
                for c in range(4):
                    row_scratch[pl.ds(saved_slot(l, c, p, P), 1), :] = kept[c]
        return base + idx_slot(0, 0, 0, 1, qb, L, P), tuple(accs)

    def corner_step(q, c, carry):
        # corner c of every point of every chain (the corner-major walk);
        # ``base`` == w_slot(0, q, 0, c, 0)
        base, accs = carry
        accs = list(accs)
        for l, geom in enumerate(levels):
            for p in range(P):
                kept = zero
                for h in range(G):
                    i = idx_ref[idx_slot(h, q, l, p, qb, L, P)]
                    row = fetch_row(slab_ref, geom,
                                    i + corner_offset(c, geom[1]))
                    accs[l * G + h] = accs[l * G + h] + w_ref[
                        base + w_slot(h, 0, l, 0, p, qb, L, P)] * row
                    if save:
                        kept = jnp.where(heads == h, row, kept)
                if save:
                    row_scratch[pl.ds(saved_slot(l, c, p, P), 1), :] = kept
        return base + w_slot(0, 0, 0, 1, 0, qb, L, P), tuple(accs)

    step, n = (point_step, P) if fuse_gather else (corner_step, 4)

    def body(q, carry):
        _, accs = static_loop(n, functools.partial(step, q),
                              (q, (zero,) * (L * G)), not rolled)
        # each head's lanes take that head's level sums, added to the
        # total in level order: the adds the per-level launches' outputs
        # see outside (tier parity)
        total = zero
        for l in range(L):
            part = zero
            for h in range(G):
                part = jnp.where(heads == h, accs[l * G + h], part)
            total = total + part
        out_ref[pl.ds(q, 1), :] = total
        if save:
            saved_ref[row_block(q, L * 4 * P), :] = row_scratch[...].astype(
                saved_ref.dtype)
        return carry

    jax.lax.fori_loop(0, qb, body, 0)


def compiler_params(vmem_limit: int):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=int(vmem_limit) if vmem_limit else None)


# Mosaic tiles a 1-D SMEM operand in chunks of this many elements
SMEM_TILE = 1024


def kernel_metadata(kind: str, first_level: int, levels: int,
                    block_q: int) -> dict:
    """The metadata an MSDA kernel carries into its HLO custom call
    (``frontend_attributes={kernel_metadata={...}}``) and trace event."""
    return {"msda": kind,
            "levels": f"{first_level}-{first_level + levels - 1}",
            "block_q": str(block_q)}


def table_block(n: int) -> int:
    """Padded length of one query block's table chunk of ``n`` entries."""
    return -(-n // SMEM_TILE) * SMEM_TILE


def table_specs(qb: int, G: int, L: int, P: int, NG: int, nq: int):
    """SMEM BlockSpecs of the corner-row and weight tables.

    The tables are flat: one chunk per (batch, head group, query block),
    each padded to :func:`table_block`.  Mosaic tiles the last two dims
    of an SMEM block like VMEM ones, so neither a squeezed (batch,
    group) prefix nor a chunk off the 1-D tiling would lower.
    """
    n = G * qb * L * P

    def index(b, g, q):
        return ((b * NG + g) * nq + q,)

    return [
        pl.BlockSpec((table_block(n),), index, memory_space=pltpu.SMEM),
        pl.BlockSpec((table_block(4 * n),), index, memory_space=pltpu.SMEM),
    ]


def resident(shape, index_map):
    """BlockSpec of a block every query step shares (the slab, the grad
    slab): single-buffered, since it is fetched once per (batch, head
    group) and double buffering would only double its VMEM."""
    return pl.BlockSpec(shape, index_map, pipeline_mode=pl.Buffered(1))


def msda_gather(
    slab: jax.Array,   # (B, NG, R, G*D) fp32, zero-padded levels packed
    idx: jax.Array,    # flat int32 table: top-left corner rows
    w: jax.Array,      # flat fp32 table: corner weights (see table_specs)
    *,
    levels: Tuple[LevelGeom, ...],
    num_points: int,
    head_dim: int,
    block_q: int,
    fuse_gather: bool = True,
    save_dtype=None,
    interpret: bool,
    vmem_limit: int = 0,
    first_level: int = 0,
) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Weighted corner gather over one slab: ``(out, saved)``.

    ``out`` is (B, NG, Qp, G*D) fp32: per query and head the sum over
    levels, points and corners of weight x corner row.  With
    ``save_dtype`` the raw corners also come back as (B, NG, Qp*L*4P,
    G*D) in that dtype: each query's L*4P rows in :func:`saved_slot`
    order.  ``first_level`` is the pyramid index of ``levels[0]``, for
    the kernel's metadata only.
    """
    B, NG, R, GD = slab.shape
    L, P, D = len(levels), num_points, head_dim
    G = GD // D
    nq = idx.shape[0] // (B * NG * table_block(block_q * G * L * P))
    qp = nq * block_q
    K = L * 4 * P
    kernel = functools.partial(
        _gather_kernel, levels=tuple(levels), P=P, G=G, D=D, qb=block_q,
        fuse_gather=fuse_gather, unroll=not interpret)
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
        grid=(B, NG, qp // block_q),
        in_specs=table_specs(block_q, G, L, P, NG, nq) + [
            # slab: same block for every q -> resident across the block walk
            resident((None, None, R, GD), lambda b, g, q: (b, g, 0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=scratch,
        compiler_params=compiler_params(vmem_limit),
        interpret=interpret,
        name=scopes.GATHER_KERNEL,
        metadata=kernel_metadata("gather", first_level, L, block_q),
    )(idx, w, slab)
    if save_dtype is not None:
        return outs[0], outs[1]
    return outs[0], None


def _nosave_wrap(kernel, idx_ref, w_ref, slab_ref, out_ref):
    kernel(idx_ref, w_ref, slab_ref, out_ref, None, None)
