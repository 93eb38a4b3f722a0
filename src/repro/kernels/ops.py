"""MSDA kernel glue + the legacy one-shot ``msda(...)`` shim.

The *planning* surface lives in ``repro.kernels.plan`` (``MsdaSpec`` →
``msda_plan`` → ``MsdaPlan``) and the backend registry in
``repro.kernels.registry``; this module keeps

* the layout/padding contract and the kernel driver
  (``build_kernel_op``) the pallas backend builder compiles into an
  executor: one launch per direction over the whole pyramid, its levels
  packed into one slab (``_pack_pyramid`` / ``pyramid_row_offsets``),
* the heuristic block planner (``plan_blocks`` — the paper's adaptive
  vec-len model, Fig. 7), invoked once per plan, and
* ``msda(...)``: a thin compatibility shim that builds a spec, fetches
  the cached plan, and executes it.  Per-call tuning kwargs
  (``block_q``, ``interpret``) are deprecated — commit them on the
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


def pyramid_resident_bytes(spatial_shapes: Shapes, head_dim: int, *,
                           heads: int = 1) -> int:
    """VMEM-resident bytes of a launch over ``spatial_shapes``: the packed
    pyramid slab (:func:`resident_bytes` over every level's rows).  The
    block planner, the plan-time fit check and ``MsdaPlan.level_report``
    all read it from here."""
    _, total = pyramid_row_offsets(spatial_shapes)
    return resident_bytes(total, head_dim, heads=heads)


def _step_bytes(spatial_shapes: Shapes, num_points: int, head_dim: int, *,
                value_itemsize, train: bool, heads: int) -> int:
    """Per-query VMEM of a launch over the whole pyramid, its saved
    corners at the widest committed slab itemsize (``value_itemsize``:
    a scalar, or one per level)."""
    if isinstance(value_itemsize, (tuple, list)):
        value_itemsize = max(value_itemsize)
    return per_query_bytes(num_points, head_dim, train=train,
                           slab_itemsize=int(value_itemsize),
                           levels=len(spatial_shapes), heads=heads)


def min_launch_bytes(
    spatial_shapes: Shapes,
    num_points: int,
    head_dim: int,
    *,
    value_itemsize=4,
    train: bool = True,
    heads: int = 1,
) -> int:
    """VMEM the smallest launch needs: the packed pyramid slab plus one
    sublane step of queries.  A budget below it cannot hold the pyramid,
    and the planner refuses the spec."""
    return (pyramid_resident_bytes(spatial_shapes, head_dim, heads=heads)
            + _SUBLANE * _step_bytes(
                spatial_shapes, num_points, head_dim,
                value_itemsize=value_itemsize, train=train, heads=heads))


def plan_blocks(
    spatial_shapes: Shapes,
    num_points: int,
    head_dim: int,
    num_queries: int,
    *,
    value_itemsize=4,
    train: bool = True,
    vmem_budget: int = VMEM_BUDGET,
    heads: int = 1,
) -> int:
    """Query block of the launch (the paper's adaptive vec-len, Fig. 7).

    The packed pyramid slab stays resident; the block takes the VMEM it
    leaves, so smaller pyramids get wider blocks (long vectors).  The
    block also respects the SMEM cap of its table chunks
    (:func:`smem_block_cap`).

    ``value_itemsize`` is the itemsize of the committed slab dtype — a
    scalar, or a per-level sequence when the committed slab dtypes mix;
    it sizes the train-mode saved corners (the resident slab is fp32
    whatever the dtype, see :func:`resident_bytes`).  ``heads`` is the
    head group one launch holds on its lanes.
    """
    L = len(spatial_shapes)
    resident = pyramid_resident_bytes(spatial_shapes, head_dim, heads=heads)
    avail = max(vmem_budget - resident, 1 * 2**20)
    per_q = _step_bytes(spatial_shapes, num_points, head_dim,
                        value_itemsize=value_itemsize, train=train,
                        heads=heads)
    bq = min(avail // per_q, smem_block_cap(num_points, levels=L, heads=heads,
                                            train=train))
    bq = max(_SUBLANE, min(2048, (bq // _SUBLANE) * _SUBLANE))
    return int(min(bq, _round_up(num_queries, _SUBLANE)))


@dataclass(frozen=True)
class MSDAParams:
    """Static (hashable) kernel configuration: one launch per direction
    over the packed pyramid."""

    spatial_shapes: Shapes
    block_q: int
    # True runs the kernels in the Pallas interpreter (CPU tests).  No
    # default: a TPU build must say it compiles them with Mosaic.
    interpret: bool
    save_sampled: bool = False
    # mixed precision: per-level dtype the value slab is rounded to
    # ('' entries / empty tuple -> keep the operand dtype); the kernels
    # accumulate outputs and the grad slab in fp32
    slab_dtypes: Tuple[str, ...] = ()
    # Mosaic's scoped-VMEM limit for every launch (the plan's budget);
    # 0 leaves the compiler default
    vmem_limit: int = 0

    def level_dtypes(self, operand_dtype) -> Tuple[str, ...]:
        """Per-level slab dtypes: the committed one, else the operand
        dtype.  The packed slab rounds each level to its own dtype (see
        :func:`_pack_pyramid`)."""
        dts = self.slab_dtypes or ("",) * len(self.spatial_shapes)
        return tuple(d or str(jnp.dtype(operand_dtype)) for d in dts)


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


def _pyramid_geometry(p: MSDAParams):
    """((row offset, Wp, slab rows) per level, total rows) of the packed
    pyramid slab."""
    offs, total = pyramid_row_offsets(p.spatial_shapes)
    geoms = tuple((off, hw[1] + 2, slab_rows(hw))
                  for off, hw in zip(offs, p.spatial_shapes))
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

    A scan over levels, whose loop body (and its transpose) XLA compiles
    apart from the table layouts that follow.  Nothing binds it to a
    scan any more: every plan runs one launch over the packed pyramid,
    so no second schedule has to see the same weight bits.  Building
    the tables in the kernel's layout in one fusion is the open
    performance work.  The query axis is minor-most, so the TPU layouts
    of the tables are dense and cheap to transpose.
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
    """(L, B, NG, G, n, Qp) level tables -> the flat SMEM table, chunks
    laid out (head, level, n, query) — see ``msda_fwd.idx_slot`` /
    ``w_slot``.  The transpose keeps the query-block axis minor."""
    Ll, B, NG, G, n, qp = x.shape
    x = x.reshape(Ll, B, NG, G, n, qp // block_q, block_q)
    return _flat_table(jnp.transpose(x, (1, 2, 5, 3, 0, 4, 6)))


def _weight_rows(w: jax.Array, head_dim: int) -> jax.Array:
    """(L, B, NG, G, 4*P, Qp) level weights -> the forward's VMEM weight
    rows (B, NG, Qp*R, G*D), each weight in its
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
    """The tables ``(idx, wrows, w)`` the kernels consume:
    :func:`_level_tables` over the padded queries, corner rows lifted by
    the levels' row offsets in the packed slab and chunked by
    :func:`_chunked` into the SMEM table; the weights as the forward's
    VMEM rows (:func:`_weight_rows`) and as the backward's SMEM table.
    A program that takes no gradient never reads the last, and XLA
    drops it.  Data movement only."""
    B, Q, H, L, P, _ = loc.shape
    G = msda_fwd.head_group(H, head_dim)
    qp = _round_up(Q, p.block_q)
    idx, w = _level_tables(p, loc, attn, G, qp)
    offs, _ = pyramid_row_offsets(p.spatial_shapes)
    idx = idx + jnp.asarray(offs, jnp.int32)[:, None, None, None, None, None]
    return (_chunked(idx, p.block_q), _weight_rows(w, head_dim),
            _chunked(w, p.block_q))


def _corner_dtype(p: MSDAParams, operand_dtype):
    """Dtype the raw corners are kept in: the widest slab dtype among the
    levels (every corner is already rounded to its own level's dtype, so
    this is exact)."""
    return max((jnp.dtype(d) for d in p.level_dtypes(operand_dtype)),
               key=lambda d: d.itemsize)


def _kernel_gather(p: MSDAParams, dims, operand_dtype):
    """Custom-VJP weighted corner gather ``(value, idx, wrows, w) ->
    out``.

    ``dims`` = (B, S, H, D, Q, P) are static.  The forward runs the
    gather kernel over the packed pyramid on the weight rows ``wrows``
    (saving corners in train mode); the backward runs the scatter kernel
    on the weight table ``w`` (regathering corners when none were
    saved), which also returns that table's grads.  Both weight layouts
    hold the same weights, so the gradient reaches them through ``w``
    alone and ``wrows`` gets none.
    """
    B, S, H, D, Q, P = dims
    G = msda_fwd.head_group(H, D)
    NG = H // G
    geoms, rows = _pyramid_geometry(p)
    qp = _round_up(Q, p.block_q)

    def grouped_value(value):
        return jnp.transpose(value.reshape(B, S, NG, G * D), (0, 2, 1, 3))

    def run_fwd(value, idx, wrows, save):
        with jax.named_scope(scopes.MSDA_FWD):
            with jax.named_scope(scopes.MSDA_SLAB):
                value_g = grouped_value(value)
            with jax.named_scope(scopes.MSDA_REDUCE):
                out = jnp.zeros((B, NG, Q, G * D), jnp.float32)
            with jax.named_scope(scopes.MSDA_SLAB):
                slab = _pack_pyramid(value_g, p.spatial_shapes,
                                     p.level_dtypes(operand_dtype))
            with jax.named_scope(scopes.MSDA_KERNEL):
                o, saved = msda_fwd.msda_gather(
                    slab, idx, wrows, levels=geoms, num_points=P, head_dim=D,
                    block_q=p.block_q,
                    save_dtype=(_corner_dtype(p, operand_dtype)
                                if save else None),
                    interpret=p.interpret, vmem_limit=p.vmem_limit)
            with jax.named_scope(scopes.MSDA_REDUCE):
                # added onto zeros, so a -0.0 sum leaves as +0.0
                out = out + o[:, :, :Q]
                out = jnp.transpose(out, (0, 2, 1, 3)).reshape(B, Q, H * D)
                out = out.astype(operand_dtype)
            return out, (slab, saved)

    @jax.custom_vjp
    def gather(value, idx, wrows, w):
        return run_fwd(value, idx, wrows, save=False)[0]

    def fwd(value, idx, wrows, w):
        out, (slab, saved) = run_fwd(value, idx, wrows, p.save_sampled)
        # train plans keep the corners, inference plans the slab
        return out, (None if p.save_sampled else slab,
                     saved if p.save_sampled else None, idx, w)

    def bwd(res, gout):
        with jax.named_scope(scopes.MSDA_BWD):
            slab, saved, idx, w = res
            with jax.named_scope(scopes.MSDA_SLAB):
                gout_g = jnp.transpose(
                    gout.astype(jnp.float32).reshape(B, Q, NG, G * D),
                    (0, 2, 1, 3))
                gout_p = _pad_q(gout_g, 2, qp, 0.0)
            with jax.named_scope(scopes.MSDA_KERNEL):
                gslab, gw = msda_bwd.msda_scatter(
                    gout_p, idx, w, slab if saved is None else saved,
                    regather=saved is None, rows=rows, levels=geoms,
                    num_points=P, head_dim=D, block_q=p.block_q,
                    interpret=p.interpret, vmem_limit=p.vmem_limit)
            with jax.named_scope(scopes.MSDA_GRAD_UNPACK):
                gvals = [_unpad_grad(gslab[:, :, off:off + n], hw)
                         for (off, _, n), hw in zip(geoms, p.spatial_shapes)]
                gw = _flat_table(gw)
                gvalue = jnp.concatenate(gvals, axis=2)  # (B,NG,S,G*D)
                gvalue = jnp.transpose(gvalue, (0, 2, 1, 3)).reshape(B, S, H, D)
                gvalue = gvalue.astype(operand_dtype)
            gidx = np.zeros(idx.shape, jax.dtypes.float0)
            return gvalue, gidx, None, gw

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
            idx, wrows, w = _corner_tables(p, loc, attn, D)
        return _kernel_gather(p, (B, S, H, D, Q, P), value.dtype)(
            value, idx, wrows, w)

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
    sparsity: str = "off",
    sparsity_k: int = 0,
    query_order: str = "identity",
    block_q=_UNSET,
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
    ``plan.resolve_dtype_policy``).  ``sparsity`` / ``sparsity_k`` /
    ``query_order`` commit the sparsity rungs: DEFA-style top-k point
    pruning (lossy, dense fallback — see ``kernels/msda_sparse.py``)
    and the bitwise-neutral Morton query permutation.  The per-call
    tuning kwargs (``block_q``, ``interpret``) are deprecated; put them
    on the spec / plan instead.
    """
    from repro.kernels import plan as plan_mod

    slab_dtype, accum_dtype = plan_mod.resolve_dtype_policy(dtype_policy)
    overrides = {"slab_dtype": slab_dtype, "accum_dtype": accum_dtype,
                 "sparsity": sparsity, "sparsity_k": sparsity_k,
                 "query_order": query_order}
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
