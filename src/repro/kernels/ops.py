"""MSDA kernel glue + the legacy one-shot ``msda(...)`` shim.

The *planning* surface lives in ``repro.kernels.plan`` (``MsdaSpec`` →
``msda_plan`` → ``MsdaPlan``) and the backend registry in
``repro.kernels.registry``; this module keeps

* the layout/padding contract and the kernel driver
  (``build_kernel_op``) the pallas backend builder compiles into an
  executor.  One launch schedule (:func:`plan_launches`) covers every
  fusion tier: one launch per level, a fused prefix (levels packed into
  one super-slab by ``_pack_pyramid`` / ``pyramid_row_offsets``) plus a
  per-level tail, or the whole pyramid in ONE launch per direction,
* the heuristic block planner (``plan_blocks`` — the paper's adaptive
  vec-len model, Fig. 7) and the MXU one-hot routing rule
  (``plan_onehot``), both invoked once per plan, and
* ``msda(...)``: a thin compatibility shim that builds a spec, fetches
  the cached plan, and executes it.  Per-call tuning kwargs
  (``block_q``, ``fuse_gather``, …) are deprecated — commit them on the
  spec / plan instead.

The layout/padding contract between the wrapper and the kernels:
each level is zero-padded from ``(H, W)`` to ``(H+2, W+2)`` (leading +
trailing pad row/column — the paper's §4.1 padding fix, re-derived for
branch-free corner pairs) and flattened row-major to a slab of
``hwp_rows = round_up((H+2) * (W+2), 8)`` rows × ``G*D`` lanes: the
heads of one head group (``msda_fwd.head_group``) side by side.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import msda_bwd, msda_fwd
from repro.obs import scopes

Shapes = Tuple[Tuple[int, int], ...]

# Default block-planning budget for direct plan_blocks() calls that pass
# none.  Plans carry an explicit per-device budget on the spec
# (plan.default_vmem_budget), which also becomes Mosaic's VMEM limit.
VMEM_BUDGET = 32 * 2**20
# SMEM the double-buffered table chunks of one query step may take (of
# the 1 MiB a v5e TensorCore has; the rest is left to Mosaic's own
# scalars): the corner rows, and the weights the backward still reads
SMEM_BUDGET = 768 * 1024
_SUBLANE = 8
_LANES = 128


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def slab_rows(hw: Tuple[int, int]) -> int:
    h, w = hw
    return _round_up((h + 2) * (w + 2), _SUBLANE)


def lane_width(heads: int, head_dim: int) -> int:
    """VMEM lanes of one slab row: a head group's ``heads * head_dim``
    values, padded to whole vreg rows."""
    return _round_up(heads * head_dim, _LANES)


def resident_bytes(rows: int, head_dim: int, *, heads: int = 1) -> int:
    """VMEM a launch keeps resident over ``rows`` slab rows: the fp32
    value slab in the forward, the fp32 grad slab in the backward (one
    at a time, single-buffered)."""
    return rows * lane_width(heads, head_dim) * 4


def per_query_bytes(num_points: int, head_dim: int, *, train: bool = False,
                    slab_itemsize: int = 4, levels: int = 1,
                    heads: int = 1) -> int:
    """Per-query VMEM of one launch's pipelined step blocks.

    Inference: the forward's double-buffered fp32 output row and weight
    rows (``msda_fwd.weight_layout``).  ``train=True`` plans the larger
    of the training forward (those plus the saved corners it writes,
    ``levels*4P`` rows in the slab dtype) and the backward step: the
    double-buffered gout row, saved corners and weight grads, plus the
    fp32 copy of the corners that phase 1 reduces.  ``heads`` is the
    launch's head group (its lanes).

    Single source of truth for the paper's occupancy model — used by the
    block planner below and by ``MsdaPlan.level_report``.
    """
    lanes = lane_width(heads, head_dim)
    io = lanes * 4
    weights = msda_fwd.weight_layout(levels, num_points, head_dim)[0] * io
    if not train:
        return 2 * (io + weights)
    k = levels * 4 * num_points
    saved = k * lanes * slab_itemsize
    grads = heads * k * 4
    return max(2 * (io + weights + saved),
               2 * (io + saved + grads) + k * lanes * 4)


def smem_block_cap(num_points: int, *, levels: int = 1, heads: int = 1,
                   train: bool = False) -> int:
    """Largest query block whose double-buffered SMEM table chunks fit
    :data:`SMEM_BUDGET`, less a tiling chunk of slack per table: the
    forward's int32 corner row per head, level and point, and for a
    training plan the backward's four fp32 weights besides."""
    tables = 2 if train else 1
    per_q = 2 * (1 + 4 * (tables - 1)) * heads * levels * num_points * 4
    slack = 2 * tables * msda_fwd.SMEM_TILE * 4
    return max(_SUBLANE, (SMEM_BUDGET - slack) // per_q)


def pyramid_row_offsets(spatial_shapes: Shapes) -> Tuple[Tuple[int, ...], int]:
    """Static row offsets of each level inside the packed super-slab.

    Returns ``(offsets, total_rows)``: level ``l`` occupies rows
    ``[offsets[l], offsets[l] + slab_rows(hw_l))`` of the row-major
    ``(total_rows, G*D)`` super-slab (every level's slab is already
    padded to a sublane multiple, so the offsets stay aligned).
    """
    offs, total = [], 0
    for hw in spatial_shapes:
        offs.append(total)
        total += slab_rows(hw)
    return tuple(offs), total


def _per_level_itemsizes(spatial_shapes: Shapes, value_itemsize) -> Tuple[int, ...]:
    """Normalise a scalar-or-per-level itemsize spec to a per-level tuple."""
    if isinstance(value_itemsize, (tuple, list)):
        assert len(value_itemsize) == len(spatial_shapes), (
            value_itemsize, spatial_shapes)
        return tuple(int(i) for i in value_itemsize)
    return (int(value_itemsize),) * len(spatial_shapes)


def fused_resident_bytes(spatial_shapes: Shapes, head_dim: int, *,
                         heads: int = 1) -> int:
    """VMEM-resident bytes of a fused launch over ``spatial_shapes``: the
    packed super-slab (:func:`resident_bytes` over every level's rows).
    The ONE definition of the packed pyramid's residency: the fitting
    rung, the fused block planner and ``MsdaPlan.level_report`` all read
    it from here."""
    _, total = pyramid_row_offsets(spatial_shapes)
    return resident_bytes(total, head_dim, heads=heads)


def fusion_prefix(
    spatial_shapes: Shapes,
    num_points: int,
    head_dim: int,
    *,
    value_itemsize=4,
    train: bool = True,
    vmem_budget: int = VMEM_BUDGET,
    heads: int = 1,
) -> int:
    """The planner's partial-fusion occupancy model.

    Returns the largest level prefix length ``k`` such that the packed
    super-slab of levels ``[0..k)`` (:func:`fused_resident_bytes`) PLUS a
    minimal one-sublane query step's working set over those ``k`` levels
    (saved corners at the widest committed itemsize) fits
    ``vmem_budget`` — ``k == len(spatial_shapes)`` means the whole
    pyramid fuses, ``0`` means not even a single level does.  The fused
    launch covers ``[0..k)`` and the tail runs per-level, so launches
    per direction drop from ``L`` to ``L - k + 1``.
    """
    L = len(spatial_shapes)
    items = _per_level_itemsizes(spatial_shapes, value_itemsize)
    for k in range(L, 0, -1):
        resident = fused_resident_bytes(spatial_shapes[:k], head_dim,
                                        heads=heads)
        per_q = per_query_bytes(num_points, head_dim, train=train,
                                slab_itemsize=max(items[:k]), levels=k,
                                heads=heads)
        if resident + _SUBLANE * per_q <= vmem_budget:
            return k
    return 0


def fused_pyramid_fits(
    spatial_shapes: Shapes,
    num_points: int,
    head_dim: int,
    *,
    value_itemsize=4,
    train: bool = True,
    vmem_budget: int = VMEM_BUDGET,
    heads: int = 1,
) -> bool:
    """Whole-pyramid fitting rung: does the FULL prefix fit?

    Thin compatibility wrapper over :func:`fusion_prefix` — fused-all
    exactly when the largest fitting prefix is the whole pyramid.
    """
    return fusion_prefix(
        spatial_shapes, num_points, head_dim, value_itemsize=value_itemsize,
        train=train, vmem_budget=vmem_budget,
        heads=heads) == len(spatial_shapes)


def plan_blocks(
    spatial_shapes: Shapes,
    num_points: int,
    head_dim: int,
    num_queries: int,
    *,
    value_itemsize=4,
    train: bool = True,
    vmem_budget: int = VMEM_BUDGET,
    adaptive: bool = True,
    fused: bool = False,
    heads: int = 1,
) -> Tuple[int, ...]:
    """Per-level query-block sizes (the paper's adaptive vec-len, Fig. 7).

    Larger levels leave less VMEM for per-step tensors, so their blocks
    shrink; tiny levels get wide blocks (long vectors).  ``adaptive=False``
    reproduces the "-Adaptive VecLen" ablation (fixed minimal block).
    Every block also respects the SMEM cap of its table chunks
    (:func:`smem_block_cap`).

    ``value_itemsize`` is the itemsize of the committed slab dtype — a
    scalar, or a per-level sequence when the committed slab dtypes mix;
    it sizes the train-mode saved corners (the resident slab is fp32
    whatever the dtype, see :func:`resident_bytes`).  ``heads`` is the
    head group one launch holds on its lanes.

    ``fused=True`` plans the whole-pyramid kernel instead: the resident
    set is the PACKED super-slab and one shared block serves every level
    — returned replicated per level so the tuple shape stays uniform.
    To plan a partial-fusion prefix, pass the prefix's shapes/itemsizes
    only.
    """
    def _clamp(bq: int, levels: int) -> int:
        bq = min(bq, smem_block_cap(num_points, levels=levels, heads=heads,
                                    train=train))
        bq = max(_SUBLANE, min(2048, (bq // _SUBLANE) * _SUBLANE))
        return min(bq, _round_up(num_queries, _SUBLANE))

    items = _per_level_itemsizes(spatial_shapes, value_itemsize)
    if fused:
        L = len(spatial_shapes)
        if not adaptive:
            return (_SUBLANE,) * L
        resident = fused_resident_bytes(spatial_shapes, head_dim, heads=heads)
        avail = max(vmem_budget - resident, 1 * 2**20)
        per_q = per_query_bytes(num_points, head_dim, train=train,
                                slab_itemsize=max(items), levels=L,
                                heads=heads)
        return (int(_clamp(avail // per_q, L)),) * L

    out = []
    for hw, it in zip(spatial_shapes, items):
        if not adaptive:
            out.append(_SUBLANE)
            continue
        resident = resident_bytes(slab_rows(hw), head_dim, heads=heads)
        avail = max(vmem_budget - resident, 1 * 2**20)
        per_q = per_query_bytes(num_points, head_dim, train=train,
                                slab_itemsize=it, heads=heads)
        out.append(int(_clamp(avail // per_q, 1)))
    return tuple(out)


@dataclass(frozen=True)
class MSDAParams:
    """Static (hashable) kernel configuration."""

    spatial_shapes: Shapes
    block_q: Tuple[int, ...]
    # True runs the kernels in the Pallas interpreter (CPU tests).  No
    # default: a TPU build must say it compiles them with Mosaic.
    interpret: bool
    fuse_gather: bool = True
    fuse_scatter: bool = True
    save_sampled: bool = False
    # per-level: fetch and update rows through one-hot MXU matmuls
    # (beyond-paper ablation of the row loop)
    onehot_levels: Tuple[bool, ...] = ()
    # mixed precision: per-level dtype the value slab is rounded to
    # ('' entries / empty tuple -> keep the operand dtype); the kernels
    # accumulate outputs and the grad slab in fp32
    slab_dtypes: Tuple[str, ...] = ()
    # fused whole-pyramid kernels: levels packed into ONE super-slab,
    # one pallas launch per direction with a single shared block_q
    # (block_q[0]; the planner replicates it across the fused levels)
    fuse_levels: bool = False
    # partial fusion: number of levels in the fused prefix [0..k).
    # 0 means "all levels" when fuse_levels is set (whole-pyramid
    # fusion); 0 < k < L runs ONE fused launch over the prefix plus
    # per-level launches for the tail, summed into the same accumulator.
    fuse_prefix: int = 0
    # Mosaic's scoped-VMEM limit for every launch (the plan's budget);
    # 0 leaves the compiler default
    vmem_limit: int = 0

    def slab_dtype(self, level: int) -> str:
        if self.slab_dtypes and self.slab_dtypes[level]:
            return self.slab_dtypes[level]
        return ""

    def fused_prefix_len(self) -> int:
        """Committed fused prefix length k: L when fully fused, 0 when
        per-level, else the strict prefix ``0 < k < L``."""
        L = len(self.spatial_shapes)
        if not self.fuse_levels:
            return 0
        return min(self.fuse_prefix, L) if self.fuse_prefix else L

    def fused_slab_dtypes(self, operand_dtype) -> Tuple[str, ...]:
        """Per-level slab dtypes: the committed one, else the operand
        dtype.  A packed super-slab rounds each level to its own dtype
        (see :func:`_pack_pyramid`)."""
        return tuple(self.slab_dtype(l) or str(jnp.dtype(operand_dtype))
                     for l in range(len(self.spatial_shapes)))


# levels with padded slabs up to this many rows use the MXU one-hot path
ONEHOT_MAX_ROWS = 1152


def plan_onehot(spatial_shapes: Shapes) -> Tuple[bool, ...]:
    return tuple(slab_rows(hw) <= ONEHOT_MAX_ROWS for hw in spatial_shapes)


def _pad_level(value_t: jax.Array, offset: int, hw: Tuple[int, int]) -> jax.Array:
    """(B,H,S,D) -> zero-padded level slab (B,H,hwp_rows,D)."""
    B, Hh, S, D = value_t.shape
    h, w = hw
    lvl = jax.lax.dynamic_slice_in_dim(value_t, offset, h * w, axis=2)
    lvl = lvl.reshape(B, Hh, h, w, D)
    lvl = jnp.pad(lvl, ((0, 0), (0, 0), (1, 1), (1, 1), (0, 0)))
    lvl = lvl.reshape(B, Hh, (h + 2) * (w + 2), D)
    rows = slab_rows(hw)
    extra = rows - (h + 2) * (w + 2)
    if extra:
        lvl = jnp.pad(lvl, ((0, 0), (0, 0), (0, extra), (0, 0)))
    return lvl


def _unpad_grad(slab: jax.Array, hw: Tuple[int, int]) -> jax.Array:
    """Inverse of _pad_level for the grad slab: (B,H,rows,D) -> (B,H,HW,D)."""
    B, Hh, rows, D = slab.shape
    h, w = hw
    slab = slab[:, :, : (h + 2) * (w + 2)].reshape(B, Hh, h + 2, w + 2, D)
    return slab[:, :, 1 : h + 1, 1 : w + 1].reshape(B, Hh, h * w, D)


def _pad_q(x: jax.Array, q_axis: int, qpad: int, fill=0.0) -> jax.Array:
    q = x.shape[q_axis]
    if q == qpad:
        return x
    pads = [(0, 0)] * x.ndim
    pads[q_axis] = (0, qpad - q)
    return jnp.pad(x, pads, constant_values=fill)


def _pack_pyramid(value_t: jax.Array, spatial_shapes: Shapes,
                  dtypes: Tuple[str, ...]) -> jax.Array:
    """(B,G,S,C) -> packed fp32 super-slab (B,G,total_rows,C), every level
    zero-padded to its ``slab_rows`` extent at its static row offset.

    Each level is rounded to its own committed dtype in ``dtypes``, then
    stored in fp32 (exact for every narrower float): the kernels address
    the slab row by row, which Mosaic cannot do inside a packed 16-bit
    tile.
    """
    parts = []
    offset = 0
    for hw, dt in zip(spatial_shapes, dtypes):
        lvl = _pad_level(value_t, offset, hw)
        parts.append(lvl.astype(dt).astype(jnp.float32))
        offset += hw[0] * hw[1]
    return jnp.concatenate(parts, axis=2)


@dataclass(frozen=True)
class Launch:
    """One kernel launch of a plan: the levels it covers, its query
    block, and whether it runs over a packed super-slab (the fused
    prefix or whole pyramid) or over one level's own slab."""

    levels: Tuple[int, ...]
    block_q: int
    packed: bool


def plan_launches(p: MSDAParams) -> Tuple[Launch, ...]:
    """The launch schedule of ``p``, the same in both directions: the
    whole pyramid, a fused prefix plus a per-level tail, or one launch
    per level."""
    L = len(p.spatial_shapes)
    k = p.fused_prefix_len()
    out = [Launch(tuple(range(k)), p.block_q[0], True)] if k else []
    out += [Launch((l,), p.block_q[l], False) for l in range(k, L)]
    return tuple(out)


def _launch_geometry(p: MSDAParams, launch: Launch):
    """((row offset, Wp, slab rows, one-hot?) per level, total rows)."""
    hws = [p.spatial_shapes[l] for l in launch.levels]
    offs, total = pyramid_row_offsets(hws)
    geoms = tuple(
        (off, hw[1] + 2, slab_rows(hw),
         bool(p.onehot_levels[l]) if p.onehot_levels else False)
        for off, hw, l in zip(offs, hws, launch.levels))
    return geoms, total


def _level_tables(p: MSDAParams, loc, attn, G: int, qmax: int):
    """Corner rows and weights of every level: ``(idx, w)``.

    Plain element-wise XLA, differentiable in ``loc`` / ``attn`` through
    ``w``: JAX's autodiff of the bilinear weights is the backward's
    grad-loc / grad-attn chain rule.  ``idx`` (L, B, H//G, G, P, qmax)
    int32 holds the top-left corner row of every (point, query) in the
    level's own padded slab; ``w`` (L, B, H//G, G, 4*P, qmax) the four
    corner weights (corner-major) with the validity mask and the
    attention weight folded in.  Queries past Q are zero-weight padding.

    A scan over levels: the loop body (and its transpose) is compiled
    apart from whatever a fusion tier does with the tables, so XLA
    cannot fuse — and FMA-contract — the weight math differently per
    tier (tier parity is bitwise).  The query axis is minor-most, so the
    TPU layouts of the tables are dense and cheap to transpose.
    """
    B, Q, H, L, P, _ = loc.shape
    NG = H // G
    hw = jnp.asarray(p.spatial_shapes, jnp.int32)  # (L, 2)
    loc5 = loc.astype(jnp.float32).reshape(B, Q, H, L, P * 2)

    def query_minor(x, n):
        # (B, Q, H, n) -> (B, NG, G, n, qmax), padded with zeros
        x = jnp.transpose(x.reshape(B, Q, NG, G, n), (0, 2, 3, 4, 1))
        return jnp.pad(x, ((0, 0),) * 4 + ((0, qmax - Q),))

    def level(_, xs):
        l, h, w = xs
        xy = jax.lax.dynamic_index_in_dim(loc5, l, axis=3, keepdims=False)
        a = jax.lax.dynamic_index_in_dim(attn, l, axis=3, keepdims=False)
        a = a.astype(jnp.float32)
        idx00, lx, ly, (m00, m10, m01, m11) = msda_fwd.corner_indices(
            xy.reshape(B, Q, H, P, 2), h, w, w + 2)
        wt = jnp.stack([
            (1 - lx) * (1 - ly) * m00 * a,
            lx * (1 - ly) * m10 * a,
            (1 - lx) * ly * m01 * a,
            lx * ly * m11 * a], axis=3)  # (B, Q, H, 4, P)
        return None, (query_minor(idx00, P),
                      query_minor(wt.reshape(B, Q, H, 4 * P), 4 * P))

    _, (idx, w) = jax.lax.scan(
        level, None, (jnp.arange(L), hw[:, 0], hw[:, 1]))
    return idx, w


def _flat_table(x: jax.Array) -> jax.Array:
    """(B, NG, nq, ...) chunks -> the flat SMEM table, each (batch, head
    group, query block) chunk padded to ``msda_fwd.table_block``."""
    B, NG, nq = x.shape[:3]
    x = x.reshape(B * NG * nq, -1)
    pad = msda_fwd.table_block(x.shape[1]) - x.shape[1]
    return jnp.pad(x, ((0, 0), (0, pad))).reshape(-1)


def _chunked(x: jax.Array, block_q: int) -> jax.Array:
    """(Ll, B, NG, G, n, Qp) level tables of one launch -> its flat SMEM
    table, chunks laid out (head, level, n, query) — see
    ``msda_fwd.idx_slot`` / ``w_slot``.  The transpose keeps the
    query-block axis minor."""
    Ll, B, NG, G, n, qp = x.shape
    x = x.reshape(Ll, B, NG, G, n, qp // block_q, block_q)
    return _flat_table(jnp.transpose(x, (1, 2, 5, 3, 0, 4, 6)))


def _weight_rows(w: jax.Array, head_dim: int) -> jax.Array:
    """(Ll, B, NG, G, 4*P, Qp) level weights of one launch -> the
    forward's VMEM weight rows (B, NG, Qp*R, G*D), each weight in its
    ``msda_fwd.weight_lane``.  Data movement only: the slots are ordered
    and padded with the query minor, then one transpose brings the query
    major and the heads' slots onto the lanes."""
    Ll, B, NG, G, n, qp = w.shape
    P, D = n // 4, head_dim
    R, stride = msda_fwd.weight_layout(Ll, P, D)
    x = jnp.transpose(w.reshape(Ll, B, NG, G, 4, P, qp), (1, 2, 3, 5, 0, 4, 6))
    x = x.reshape(B, NG, G, P, 4 * Ll, qp)
    x = jnp.pad(x, ((0, 0),) * 4 + ((0, stride - 4 * Ll), (0, 0)))
    x = jnp.pad(x.reshape(B, NG, G, P * stride, qp),
                ((0, 0),) * 3 + ((0, R * D - P * stride), (0, 0)))
    x = jnp.transpose(x.reshape(B, NG, G, R, D, qp), (0, 1, 5, 3, 2, 4))
    return x.reshape(B, NG, qp * R, G * D)


def _corner_tables(p: MSDAParams, loc, attn, head_dim: int):
    """Per launch, the tables ``(idx, wrows, w)`` the kernels consume:
    :func:`_level_tables` cut to the launch's levels and padded queries,
    corner rows lifted by the levels' row offsets in the launch slab and
    chunked by :func:`_chunked` into the SMEM table; the weights as the
    forward's VMEM rows (:func:`_weight_rows`) and as the backward's
    SMEM table.  A program that takes no gradient never reads the last,
    and XLA drops it.  Data movement only."""
    B, Q, H, L, P, _ = loc.shape
    G = msda_fwd.head_group(H, head_dim)
    launches = plan_launches(p)
    qmax = max(_round_up(Q, launch.block_q) for launch in launches)
    idx_all, w_all = _level_tables(p, loc, attn, G, qmax)
    idxs, wrows, ws = [], [], []
    for launch in launches:
        geoms, _ = _launch_geometry(p, launch)
        lo, hi = launch.levels[0], launch.levels[-1] + 1
        qp = _round_up(Q, launch.block_q)
        offs = jnp.asarray([g[0] for g in geoms], jnp.int32)
        idx = idx_all[lo:hi, ..., :qp] + offs[:, None, None, None, None, None]
        idxs.append(_chunked(idx, launch.block_q))
        wrows.append(_weight_rows(w_all[lo:hi, ..., :qp], head_dim))
        ws.append(_chunked(w_all[lo:hi, ..., :qp], launch.block_q))
    return tuple(idxs), tuple(wrows), tuple(ws)


def _launch_slab(p: MSDAParams, launch: Launch, value_g, operand_dtype):
    """The fp32 slab one launch keeps resident: one level's padded slab,
    or the packed super-slab of a fused launch."""
    dts = p.fused_slab_dtypes(operand_dtype)
    if launch.packed:
        k = len(launch.levels)
        return _pack_pyramid(value_g, p.spatial_shapes[:k], dts[:k])
    (l,) = launch.levels
    start = sum(h * w for h, w in p.spatial_shapes[:l])
    lvl = _pad_level(value_g, start, p.spatial_shapes[l])
    return lvl.astype(dts[l]).astype(jnp.float32)


def _corner_dtype(p: MSDAParams, launch: Launch, operand_dtype):
    """Dtype the raw corners of a launch are kept in: the widest slab
    dtype among its levels (every corner is already rounded to its own
    level's dtype, so this is exact)."""
    dts = p.fused_slab_dtypes(operand_dtype)
    return max((jnp.dtype(dts[l]) for l in launch.levels),
               key=lambda d: d.itemsize)


def _unpack_grad(gslab, p: MSDAParams, launch: Launch, geoms):
    """Grad slab of one launch -> per-level (B, NG, h*w, G*D) grads."""
    return [_unpad_grad(gslab[:, :, off:off + rows], p.spatial_shapes[l])
            for l, (off, _, rows, _) in zip(launch.levels, geoms)]


def _kernel_gather(p: MSDAParams, dims, operand_dtype):
    """Custom-VJP weighted corner gather ``(value, idxs, wrows, ws) ->
    out``.

    ``dims`` = (B, S, H, D, Q, P) are static.  The forward runs the
    gather kernel per launch on the weight rows ``wrows`` (saving
    corners in train mode); the backward runs the scatter kernel per
    launch on the weight table ``ws`` (regathering corners when none
    were saved), which also returns that table's grads.  Both weight
    layouts hold the same weights, so the gradient reaches them through
    ``ws`` alone and ``wrows`` gets none.
    """
    B, S, H, D, Q, P = dims
    G = msda_fwd.head_group(H, D)
    NG = H // G
    launches = plan_launches(p)

    def grouped_value(value):
        return jnp.transpose(value.reshape(B, S, NG, G * D), (0, 2, 1, 3))

    def run_fwd(value, idxs, wrows, save):
        with jax.named_scope(scopes.MSDA_FWD):
            with jax.named_scope(scopes.MSDA_SLAB):
                value_g = grouped_value(value)
            with jax.named_scope(scopes.MSDA_REDUCE):
                out = jnp.zeros((B, NG, Q, G * D), jnp.float32)
            slabs, saved = [], []
            for launch, idx, w in zip(launches, idxs, wrows):
                geoms, _ = _launch_geometry(p, launch)
                with jax.named_scope(scopes.MSDA_SLAB):
                    slab = _launch_slab(p, launch, value_g, operand_dtype)
                with jax.named_scope(scopes.MSDA_KERNEL):
                    o, s = msda_fwd.msda_gather(
                        slab, idx, w, levels=geoms, num_points=P, head_dim=D,
                        block_q=launch.block_q, fuse_gather=p.fuse_gather,
                        save_dtype=(_corner_dtype(p, launch, operand_dtype)
                                    if save else None),
                        interpret=p.interpret, vmem_limit=p.vmem_limit,
                        first_level=launch.levels[0])
                with jax.named_scope(scopes.MSDA_REDUCE):
                    out = out + o[:, :, :Q]
                slabs.append(slab)
                saved.append(s)
            with jax.named_scope(scopes.MSDA_REDUCE):
                out = jnp.transpose(out, (0, 2, 1, 3)).reshape(B, Q, H * D)
                out = out.astype(operand_dtype)
            return out, (tuple(slabs), tuple(saved))

    @jax.custom_vjp
    def gather(value, idxs, wrows, ws):
        return run_fwd(value, idxs, wrows, save=False)[0]

    def fwd(value, idxs, wrows, ws):
        out, (slabs, saved) = run_fwd(value, idxs, wrows, p.save_sampled)
        # train plans keep the corners, inference plans the slabs
        return out, (None if p.save_sampled else slabs,
                     saved if p.save_sampled else None, idxs, ws)

    def bwd(res, gout):
        with jax.named_scope(scopes.MSDA_BWD):
            slabs, saved, idxs, ws = res
            with jax.named_scope(scopes.MSDA_SLAB):
                gout_g = jnp.transpose(
                    gout.astype(jnp.float32).reshape(B, Q, NG, G * D),
                    (0, 2, 1, 3))
            gvals, gws = [], []
            for i, (launch, idx, w) in enumerate(zip(launches, idxs, ws)):
                geoms, rows = _launch_geometry(p, launch)
                qp = _round_up(Q, launch.block_q)
                with jax.named_scope(scopes.MSDA_SLAB):
                    gout_p = _pad_q(gout_g, 2, qp, 0.0)
                with jax.named_scope(scopes.MSDA_KERNEL):
                    gslab, gw = msda_bwd.msda_scatter(
                        gout_p, idx, w,
                        slabs[i] if saved is None else saved[i],
                        regather=saved is None, rows=rows, levels=geoms,
                        num_points=P, head_dim=D, block_q=launch.block_q,
                        fuse_scatter=p.fuse_scatter, interpret=p.interpret,
                        vmem_limit=p.vmem_limit, first_level=launch.levels[0])
                with jax.named_scope(scopes.MSDA_GRAD_UNPACK):
                    gvals += _unpack_grad(gslab, p, launch, geoms)
                    gws.append(_flat_table(gw))
            with jax.named_scope(scopes.MSDA_GRAD_UNPACK):
                gvalue = jnp.concatenate(gvals, axis=2)  # (B,NG,S,G*D)
                gvalue = jnp.transpose(gvalue, (0, 2, 1, 3)).reshape(B, S, H, D)
                gvalue = gvalue.astype(operand_dtype)
            gidx = tuple(np.zeros(i.shape, jax.dtypes.float0) for i in idxs)
            return gvalue, gidx, None, tuple(gws)

    gather.defvjp(fwd, bwd)
    return gather


def build_kernel_op(p: MSDAParams):
    """Differentiable executor for one committed kernel configuration:
    ``op(value (B,S,H,D), loc (B,Q,H,L,P,2), attn (B,Q,H,L,P)) ->
    (B,Q,H*D)``.

    Deliberately *uncached*: the bounded plan cache in
    ``repro.kernels.plan`` owns the lifetime of compiled ops (and its
    ``clear_plans()`` hook lets long-lived serving processes drop them).
    """

    def op(value, loc, attn):
        B, S, H, D = value.shape
        Q, P = loc.shape[1], loc.shape[4]
        # the table math's own transpose, under the forward's name,
        # is the backward's grad-loc / grad-attn (scopes.layer_of)
        with jax.named_scope(scopes.MSDA_FWD), \
                jax.named_scope(scopes.MSDA_TABLES):
            idxs, wrows, ws = _corner_tables(p, loc, attn, D)
        return _kernel_gather(p, (B, S, H, D, Q, P), value.dtype)(
            value, idxs, wrows, ws)

    return jax.jit(op)


def resolve_backend(backend: str) -> str:
    from repro.kernels import registry

    return registry.resolve_backend(backend)


_UNSET = object()
_WARNED_KWARGS: set = set()


def _deprecated_kwarg(name: str) -> None:
    if name not in _WARNED_KWARGS:
        _WARNED_KWARGS.add(name)
        warnings.warn(
            f"ops.msda(..., {name}=...) is deprecated: commit tuning on an "
            "MsdaSpec and build a plan via repro.kernels.plan.msda_plan "
            "(the shim still honours the kwarg)",
            DeprecationWarning,
            stacklevel=3,
        )


def msda(
    value: jax.Array,
    spatial_shapes: Shapes,
    sampling_locations: jax.Array,
    attention_weights: jax.Array,
    *,
    backend: str = "auto",
    train: bool = False,
    dtype_policy: str = "follow",
    fuse_levels: str = "auto",
    sparsity: str = "off",
    sparsity_k: int = 0,
    query_order: str = "identity",
    block_q=_UNSET,
    fuse_gather=_UNSET,
    fuse_scatter=_UNSET,
    adaptive_block=_UNSET,
    onehot_small_levels=_UNSET,
    interpret=_UNSET,
) -> jax.Array:
    """Multi-scale deformable attention (differentiable) — compat shim.

    value: (B, S, H, D); sampling_locations: (B, Q, H, L, P, 2) in [0,1];
    attention_weights: (B, Q, H, L, P); returns (B, Q, H*D).

    This entry point now builds an :class:`~repro.kernels.plan.MsdaSpec`
    from the operands and executes the cached
    :class:`~repro.kernels.plan.MsdaPlan` — repeated calls with an
    identical spec never re-run block planning.  ``dtype_policy``
    ('follow' | 'float32' | 'bfloat16' | 'auto') commits the
    mixed-precision plan variant (bf16 slab + fp32 accumulate; see
    ``plan.resolve_dtype_policy``).  ``fuse_levels``
    ('auto' | 'on' | 'off') commits the whole-pyramid kernel fusion
    rung (one pallas launch per direction when the packed pyramid fits
    VMEM).  ``sparsity`` / ``sparsity_k`` / ``query_order`` commit the
    sparsity rungs: DEFA-style top-k point pruning (lossy, dense
    fallback — see ``kernels/msda_sparse.py``) and the bitwise-neutral
    Morton query permutation.  The per-call tuning kwargs
    (``block_q``, ``fuse_gather``, ``fuse_scatter``,
    ``adaptive_block``, ``onehot_small_levels``, ``interpret``) are
    deprecated; put them on the spec / plan instead.
    """
    from repro.kernels import plan as plan_mod

    slab_dtype, accum_dtype = plan_mod.resolve_dtype_policy(dtype_policy)
    overrides = {"slab_dtype": slab_dtype, "accum_dtype": accum_dtype,
                 "fuse_levels": fuse_levels, "sparsity": sparsity,
                 "sparsity_k": sparsity_k, "query_order": query_order}
    for name, val in (("fuse_gather", fuse_gather), ("fuse_scatter", fuse_scatter),
                      ("adaptive_block", adaptive_block),
                      ("onehot_small_levels", onehot_small_levels)):
        if val is not _UNSET:
            _deprecated_kwarg(name)
            overrides[name] = val
    plan_kwargs = {}
    for name, val in (("block_q", block_q), ("interpret", interpret)):
        if val is not _UNSET:
            _deprecated_kwarg(name)
            plan_kwargs[name] = tuple(val) if name == "block_q" and val is not None else val

    spec = plan_mod.spec_from_arrays(
        value, spatial_shapes, sampling_locations, attention_weights,
        train=train, **overrides)
    plan = plan_mod.msda_plan(spec, backend=backend, **plan_kwargs)
    return plan(value, sampling_locations, attention_weights)
