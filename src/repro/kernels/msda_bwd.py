"""Pallas-TPU backward kernel for multi-scale deformable attention.

Paper mapping (xMSDA §4.2 -> TPU):

* Phase 1 (grad w.r.t. the bilinear corner weights, hence sampling
  locations and attention weights) is vector math over the corners: per
  corner and head, ``<gout, corner>``.  In train mode the corners were
  **saved by the forward kernel** (paper §4.1), so phase 1 issues no
  gathers; it runs as one MXU segmented reduction per query block.  The
  chain rule from the weights to locations and attention weights is
  JAX's own autodiff of the element-wise weight computation.
* Phase 2 (grad w.r.t. value) is the scatter-add hotspot.  The paper
  staggers vector-core phases to reduce GM write contention; on TPU the
  Pallas grid is *sequential per TensorCore*, so the whole slab's
  ``grad_value`` stays **resident in VMEM** and every corner contribution
  is a scalar-addressed read-modify-write row update — sequential, so
  duplicate corners accumulate exactly, with a single VMEM->HBM
  writeback when the (batch, head group) block retires.  Cross-chip
  parallelism reduces per-shard partial slabs at the distribution layer
  (:func:`ring_allreduce`).
* The corner rows and weights arrive in SMEM exactly as for the forward
  (``msda_fwd``), heads on lanes.
* Inference plans keep no saved corners: their backward **regathers**
  the corners from the slab in the same launch.

The grad slab is an fp32 accumulator whatever the slab dtype, so Q-many
contributions never round through bf16.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.msda_fwd import (
    LevelGeom, compiler_params, corner_offset, corner_walk, fetch_row,
    idx_slot, keep_corner, kernel_metadata, lane_heads, resident, saved_slot,
    static_loop, table_spec, w_slot)
from repro.obs import scopes


def _scatter_kernel(idx_ref, w_ref, gout_ref, src_ref, gval_ref, gw_ref,
                    corner_scratch, *, levels: Tuple[LevelGeom, ...], P: int,
                    G: int, D: int, qb: int, regather: bool, unroll: bool):
    """One (batch, head group, query block) step of the backward.

    Phase 2: scatter-add weight x gout into the resident grad slab, one
    read-modify-write row update per corner.  Phase 1: the grad of every
    corner weight, ``<gout, corner>`` over each head's lanes, as one MXU
    segmented reduction per saved slot over the whole block.  The
    corners (``corner_scratch``, fp32, each query's L*4P rows) come from
    the forward's saved block ``src_ref`` or, when ``regather``, from the
    slab ``src_ref`` during the scatter walk.
    """
    L = len(levels)
    K = L * 4 * P
    heads = lane_heads(G, D)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        gval_ref[...] = jnp.zeros_like(gval_ref)

    if not regather:
        corner_scratch[...] = src_ref[...].astype(jnp.float32)

    def body(q, carry):
        g = gout_ref[pl.ds(q, 1), :]  # (1, G*D) fp32
        for l, (_, wp, _) in enumerate(levels):

            def corner(p, c, carry, l=l, wp=wp):
                def head(h, carry):
                    mine = heads == h
                    i = (idx_ref[idx_slot(h, q, l, p, qb, L, P)]
                         + corner_offset(c, wp))
                    w = w_ref[w_slot(h, q, l, c, p, qb, L, P)]
                    contrib = jnp.where(mine, w * g, 0.0)
                    gval_ref[pl.ds(i, 1), :] += contrib
                    if regather:
                        keep_corner(corner_scratch,
                                    q * K + saved_slot(l, c, p, P), mine,
                                    fetch_row(src_ref, i))
                    return carry

                return static_loop(G, head, carry, unroll)

            corner_walk(P, unroll, corner, 0)
        return carry

    jax.lax.fori_loop(0, qb, body, 0)

    seg = (lane_heads(G, D) == jax.lax.broadcasted_iota(
        jnp.int32, (G, G * D), 0)).astype(jnp.float32)  # (G, G*D)
    g = gout_ref[...]  # (qb, G*D)
    for k in range(K):
        # slot k of every query in the block: rows k, k + K, ...
        prods = corner_scratch[pl.ds(k, qb, stride=K), :] * g
        # (G, qb): row h sums head h's lanes — the weight table's
        # (head, slot, query) chunk order (w_slot)
        gw_ref[:, k, :] = jax.lax.dot_general(
            seg, prods, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)


def msda_scatter(
    gout: jax.Array,                # (B, NG, Qp, G*D) fp32
    idx: jax.Array,                 # flat int32 table (msda_fwd)
    w: jax.Array,                   # flat fp32 table
    src: jax.Array,                 # saved (B,NG,Qp*K,G*D) or slab
    *,
    regather: bool,
    rows: int,
    levels: Tuple[LevelGeom, ...],
    num_points: int,
    head_dim: int,
    block_q: int,
    interpret: bool,
    vmem_limit: int = 0,
) -> Tuple[jax.Array, jax.Array]:
    """Backward over one slab: ``(grad_slab, grad_w)``.

    ``grad_slab`` is (B, NG, rows, G*D) fp32; ``grad_w`` the grads of
    the weight table ``w``, (B, NG, nq, G, L*4P, block_q) fp32 — the
    table's chunk layout.  ``src`` holds the corners: the forward's
    saved block, or (``regather``) the fp32 slab they are re-read from.
    """
    B, NG, qp, GD = gout.shape
    L, P, D = len(levels), num_points, head_dim
    G = GD // D
    assert qp % block_q == 0, (qp, block_q)
    nq = qp // block_q
    K = L * 4 * P
    kernel = functools.partial(
        _scatter_kernel, levels=tuple(levels), P=P, G=G, D=D, qb=block_q,
        regather=regather, unroll=not interpret)
    if regather:
        src_spec = resident((None, None, src.shape[2], GD),
                            lambda b, g, q: (b, g, 0, 0))
    else:
        src_spec = pl.BlockSpec((None, None, block_q * K, GD),
                                lambda b, g, q: (b, g, q, 0))
    gval, gw = pl.pallas_call(
        kernel,
        grid=(B, NG, nq),
        in_specs=[
            table_spec(G * block_q * L * P, NG, nq),
            table_spec(4 * G * block_q * L * P, NG, nq),
            pl.BlockSpec((None, None, block_q, GD),
                         lambda b, g, q: (b, g, q, 0)),
            src_spec,
        ],
        out_specs=[
            # grad slab: same block for every q -> accumulated in VMEM,
            # written back once per (batch, head group)
            resident((None, None, rows, GD), lambda b, g, q: (b, g, 0, 0)),
            pl.BlockSpec((None, None, None, G, K, block_q),
                         lambda b, g, q: (b, g, q, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, NG, rows, GD), jnp.float32),
            jax.ShapeDtypeStruct((B, NG, nq, G, K, block_q), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_q * K, GD), jnp.float32)],
        compiler_params=compiler_params(vmem_limit),
        interpret=interpret,
        name=scopes.SCATTER_KERNEL,
        metadata=kernel_metadata("scatter", L, block_q),
    )(idx, w, gout, src)
    return gval, gw


# --------------------------------------------------------------------------
# ring-reduced grad_value slabs (the 2D dp x tp distribution path)
# --------------------------------------------------------------------------


def ring_allreduce(x: jax.Array, axis_name: str, axis_size: int,
                   *, axis: int = 1) -> jax.Array:
    """All-reduce ``x`` over ``axis_name`` as an explicit ppermute ring.

    QUILL's cache-locality argument, applied across chips: the per-shard
    partial ``grad_value`` slabs a query-sharded backward produces should
    *circulate* — one slab shard resident per step — instead of
    round-tripping through a monolithic all-reduce that materialises the
    full fp32 slab twice per hop.  Classic two-phase ring over the
    ``axis_size`` neighbours:

    * reduce-scatter: ``x`` is chunked along ``axis`` into ``axis_size``
      shards; each step every device forwards its running partial one
      hop (``jax.lax.ppermute``) and folds in its own copy of the chunk
      that just arrived.  After N-1 hops device *i* owns chunk
      ``(i+1) % N`` fully reduced — peak extra residency is ONE chunk,
      not the whole slab.
    * all-gather: the reduced chunks take N-1 more hops around the same
      ring, each device slotting the passing chunk into its output.

    2(N-1) hops of 1/N of the slab — bandwidth-optimal, and every add
    runs in ``x.dtype`` (the caller keeps the slab in fp32/accum dtype).
    Each chunk's final value sums the device partials in ring order (a
    rotation of the device order per chunk); for N=2 that is bitwise
    identical to ``psum`` because fp addition is commutative — the
    parity the conformance tests pin down.

    The chunk axis is zero-padded up to a multiple of ``axis_size``
    (grad slabs are zero there anyway; sums of zeros stay zero).
    """
    n = int(axis_size)
    if n <= 1:
        return x
    xt = jnp.moveaxis(x, axis, 0)
    rows = xt.shape[0]
    pad = (-rows) % n
    if pad:
        xt = jnp.pad(xt, ((0, pad),) + ((0, 0),) * (xt.ndim - 1))
    parts = xt.reshape((n, (rows + pad) // n) + xt.shape[1:])
    i = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]

    # reduce-scatter: circulate one running chunk per device
    send = jax.lax.dynamic_index_in_dim(parts, i, axis=0, keepdims=False)
    for s in range(n - 1):
        recv = jax.lax.ppermute(send, axis_name, perm)
        k = (i - s - 1) % n
        send = recv + jax.lax.dynamic_index_in_dim(parts, k, axis=0,
                                                   keepdims=False)
    # all-gather: the reduced chunks take another lap
    out = jnp.zeros_like(parts)
    cur = send
    out = jax.lax.dynamic_update_index_in_dim(out, cur, (i + 1) % n, axis=0)
    for s in range(1, n):
        cur = jax.lax.ppermute(cur, axis_name, perm)
        out = jax.lax.dynamic_update_index_in_dim(out, cur, (i + 1 - s) % n,
                                                  axis=0)
    out = out.reshape((rows + pad,) + xt.shape[1:])
    if pad:
        out = out[:rows]
    return jnp.moveaxis(out, 0, axis)
