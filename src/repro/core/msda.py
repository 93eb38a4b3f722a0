"""MSDAttention: the paper's op as a composable model module.

Wraps the xMSDA plan/execute API (``repro.kernels.plan``) with the
standard Deformable-DETR parameterisation: per-query learned sampling
offsets around reference points + softmaxed attention weights,
value/output projections.

Planning: :func:`attention_plan` builds the :class:`MsdaPlan` for a
module's static geometry **once** — backend resolution, per-level block
sizes (heuristic or autotuned via ``msda_cfg.tune``) and the sharding
mode are all committed at plan time, and repeated forwards with the same
geometry fetch the cached plan (no per-call re-planning).

Distribution is baked into the plan when a mesh is installed —

* batch over the 'dp' axes, heads over 'tp' (value sharded, no
  reduction needed: each shard owns its heads' slice of grad_value);
* queries over 'tp' instead (``query_parallel=True``) for huge-Q
  workloads (the DETR encoder's 87k pixel queries), or tiled over
  **dp x tp jointly** (the 2D 'query2d' mode — picked automatically
  when Q amortises both axes, forceable via ``sharding="2d"``).  The
  value tensor is then replicated over the query axes and the
  per-shard partial grad_value slabs are reduced explicitly: a
  ppermute **ring** over 'tp' (one slab shard resident per hop) plus a
  psum over 'dp' — the TPU-idiomatic realisation of the paper's
  staggered-scatter idea (contention eliminated via partial
  accumulators + reduction, §4.2), QUILL-style cache-resident.  See
  ``docs/sharding.md``.

``distributed_msda`` survives as a thin compatibility wrapper over a
mesh-carrying plan.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import plan as plan_mod
from repro.models import layers
from repro.obs import scopes
from repro.sharding import rules


def level_ref_points(levels) -> jax.Array:
    """Normalised (x, y) centers for every pixel of every level: (S, 2)."""
    out = []
    for (h, w) in levels:
        ys = (jnp.arange(h, dtype=jnp.float32) + 0.5) / h
        xs = (jnp.arange(w, dtype=jnp.float32) + 0.5) / w
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        out.append(jnp.stack([gx, gy], -1).reshape(h * w, 2))
    return jnp.concatenate(out, axis=0)


def init_msda_attention(key, d_model: int, msda_cfg) -> dict:
    L = len(msda_cfg.levels)
    H, Pn = msda_cfg.num_heads, msda_cfg.num_points
    ks = jax.random.split(key, 4)
    p = {
        "value_proj": layers.dense_init(ks[0], (d_model, d_model)),
        "out_proj": layers.dense_init(ks[1], (d_model, d_model)),
        "w_offsets": jnp.zeros((d_model, H * L * Pn * 2), jnp.float32),
        "w_weights": layers.dense_init(ks[2], (d_model, H * L * Pn)) * 0.01,
        "b_weights": jnp.zeros((H * L * Pn,), jnp.float32),
    }
    # Deformable-DETR offset-bias init: points spread on a ring per head
    theta = jnp.arange(H, dtype=jnp.float32) * (2.0 * math.pi / H)
    grid = jnp.stack([jnp.cos(theta), jnp.sin(theta)], -1)  # (H,2)
    grid = grid / jnp.abs(grid).max(-1, keepdims=True)
    grid = jnp.tile(grid[:, None, None], (1, L, Pn, 1))
    scale = (jnp.arange(Pn, dtype=jnp.float32) + 1.0)[None, None, :, None]
    p["b_offsets"] = (grid * scale).reshape(-1)
    return p


def attention_plan(
    msda_cfg,
    *,
    num_queries: int,
    head_dim: int,
    dtype,
    train: bool = False,
    backend: Optional[str] = None,
    mesh=None,
    query_parallel: bool = False,
    dtype_policy: Optional[str] = None,
    tune: Optional[str] = None,
    sharding: Optional[str] = None,
    grad_reduce: Optional[str] = None,
) -> plan_mod.MsdaPlan:
    """The module's :class:`MsdaPlan` for one static geometry (cached).

    All hardware-aware decisions (backend, block_q, slab dtypes,
    shard_map wiring) are committed here,
    once; forwards just execute.  ``msda_cfg.tune`` selects heuristic vs
    autotuned block planning (``tune`` overrides it per call — the
    offline sweep CLI forces "autotune" on configs that default to the
    heuristic), ``msda_cfg.vmem_budget`` overrides the per-device VMEM
    default (0 = auto), and ``msda_cfg.dtype_policy`` (overridable per
    call) picks the mixed-precision plan variant — 'follow' | 'float32'
    | 'bfloat16' | 'auto' (see
    :func:`repro.kernels.plan.resolve_dtype_policy`).
    ``msda_cfg.sparsity`` /
    ``sparsity_k`` / ``query_order`` commit the sparsity rungs — top-k
    point pruning (lossy, dense fallback) and the Morton query
    permutation (bitwise-neutral).  When a mesh is given,
    ``msda_cfg.sharding`` / ``msda_cfg.grad_reduce`` (both overridable
    per call) select the distribution family and the grad_value
    reduction — see ``docs/sharding.md``.
    """
    policy = dtype_policy or getattr(msda_cfg, "dtype_policy", "follow")
    slab_dtype, accum_dtype = plan_mod.resolve_dtype_policy(policy)
    spec = plan_mod.MsdaSpec(
        spatial_shapes=msda_cfg.levels,
        num_heads=msda_cfg.num_heads,
        head_dim=head_dim,
        num_points=msda_cfg.num_points,
        num_queries=num_queries,
        dtype=str(jnp.dtype(dtype)),
        train=train,
        vmem_budget=getattr(msda_cfg, "vmem_budget", 0),
        slab_dtype=slab_dtype,
        accum_dtype=accum_dtype,
        sparsity=getattr(msda_cfg, "sparsity", "off"),
        sparsity_k=getattr(msda_cfg, "sparsity_k", 0),
        query_order=getattr(msda_cfg, "query_order", "identity"),
    )
    return plan_mod.msda_plan(
        spec,
        backend=backend or msda_cfg.backend,
        tune=tune or getattr(msda_cfg, "tune", "heuristic"),
        mesh=mesh,
        query_parallel=query_parallel,
        sharding=sharding or getattr(msda_cfg, "sharding", "auto"),
        grad_reduce=grad_reduce or getattr(msda_cfg, "grad_reduce", "auto"),
    )


def sampling_locations(
    reference_points: jax.Array,  # (B, Q, 2) or (B, Q, Z, 2) normalised
    off: jax.Array,  # (B, Q, H, L, P, 2) float32, in level pixels
    levels,
    valid_ratios: Optional[jax.Array] = None,  # (B, L, 2) x,y fractions
) -> jax.Array:
    """Normalised sample locations (B, Q, H, L, P, 2): each point's offset
    over its level's (w, h), around its query's reference point.

    ``reference_points`` holds one (x, y) per query, or ``Z`` anchors per
    query, ``(B, Q, Z, 2)``: point ``p`` of every (head, level) then
    belongs to anchor ``p % Z`` (``P % Z == 0``), as BEVFormer's spatial
    cross-attention spreads its points over a pillar's heights.
    """
    B, Q, H, L, Pn = off.shape[:5]
    wh = jnp.asarray([[w, h] for (h, w) in levels], jnp.float32)  # (L,2) x,y order
    if reference_points.ndim == 4:
        # (B, Q, Z, 2) anchors: the points of each (head, level) as
        # (P/Z, Z), point p on anchor p % Z
        Z = reference_points.shape[2]
        if Pn % Z or valid_ratios is not None:
            raise ValueError(f"{Z} anchors per query need num_points "
                             f"% Z == 0 and no valid_ratios")
        refs = reference_points[:, :, None, None, None, :, :]
        off = off.reshape(B, Q, H, L, Pn // Z, Z, 2)
        loc = refs + off / wh[None, None, None, :, None, None, :]
        return loc.reshape(B, Q, H, L, Pn, 2)
    refs = reference_points[:, :, None, None, None, :]
    if valid_ratios is not None:
        # bucketed serving (Deformable-DETR valid_ratios): the pyramid
        # only occupies the top-left (w*rx, h*ry) region of each padded
        # level.  Scaling the REFERENCE POINTS by the ratio (offsets stay
        # normalised by the padded extents wh) lands every sample on the
        # same pixel coordinate as in the unpadded level:
        # (x*r)*W - 0.5 == x*w - 0.5, and pad-region corners gather the
        # zeros that out-of-range corners contributed anyway.
        refs = refs * valid_ratios[:, None, None, :, None, :].astype(jnp.float32)
    return refs + off / wh[None, None, None, :, None, :]


def msda_attention(
    p: dict,
    msda_cfg,
    query: jax.Array,  # (B, Q, d)
    value_feats: jax.Array,  # (B, S, d)
    reference_points: jax.Array,  # (B, Q, 2) or (B, Q, Z, 2) normalised
    *,
    train: bool = False,
    backend: Optional[str] = None,
    query_parallel: bool = False,
    valid_ratios: Optional[jax.Array] = None,  # (B, L, 2) x,y fractions
) -> jax.Array:
    """Deformable attention of ``query`` over the pyramid ``value_feats``
    around ``reference_points`` (see :func:`sampling_locations`)."""
    levels = msda_cfg.levels
    L, H, Pn = len(levels), msda_cfg.num_heads, msda_cfg.num_points
    B, Q, d = query.shape
    D = d // H
    with jax.named_scope(scopes.MSDA_PROJ):
        value = (value_feats @ p["value_proj"].astype(query.dtype)).reshape(B, -1, H, D)

        off = query @ p["w_offsets"].astype(query.dtype) + p["b_offsets"].astype(query.dtype)
        off = off.reshape(B, Q, H, L, Pn, 2).astype(jnp.float32)
        loc = sampling_locations(reference_points, off, levels, valid_ratios)

        aw = query @ p["w_weights"].astype(query.dtype) + p["b_weights"].astype(query.dtype)
        aw = jax.nn.softmax(aw.reshape(B, Q, H, L * Pn).astype(jnp.float32), axis=-1)
        aw = aw.reshape(B, Q, H, L, Pn).astype(query.dtype)
        value = value.astype(query.dtype)

    # one cached plan per static geometry: the mesh (when >1 device) bakes
    # shard_map wiring in, keeping the irregular gathers LOCAL per shard
    # (GSPMD left to itself model-parallelises them and pays huge
    # reshards — same failure mode as the MoE dispatch, see §Perf)
    mesh = rules.current_mesh()
    if mesh is not None and mesh.devices.size <= 1:
        mesh = None
    plan = attention_plan(
        msda_cfg, num_queries=Q, head_dim=D, dtype=query.dtype, train=train,
        backend=backend, mesh=mesh, query_parallel=query_parallel,
    )
    out = plan(value, loc, aw)
    with jax.named_scope(scopes.MSDA_PROJ):
        return out @ p["out_proj"].astype(query.dtype)


# --------------------------------------------------------------------------
# distributed op — compatibility wrapper over a mesh-carrying plan
# --------------------------------------------------------------------------


def distributed_msda(
    value: jax.Array,  # (B, S, H, D)
    levels,
    loc: jax.Array,  # (B, Q, H, L, P, 2)
    attn: jax.Array,  # (B, Q, H, L, P)
    *,
    mesh=None,
    query_parallel: bool = False,
    sharding: str = "auto",
    grad_reduce: str = "auto",
    backend: str = "auto",
    train: bool = False,
) -> jax.Array:
    """shard_map-distributed MSDA (see module docstring).

    Thin wrapper: builds/fetches the mesh-carrying plan and executes it.
    The sharding-mode ladder (2D dp x tp query tiling -> query-parallel
    -> head-parallel -> batch-only) lives in ``plan._plan_sharding``;
    ``sharding``/``grad_reduce`` pass straight through to
    :func:`repro.kernels.plan.msda_plan`.
    """
    mesh = mesh or rules.current_mesh()
    spec = plan_mod.spec_from_arrays(value, levels, loc, attn, train=train)
    plan = plan_mod.msda_plan(
        spec, backend=backend, mesh=mesh, query_parallel=query_parallel,
        sharding=sharding, grad_reduce=grad_reduce)
    return plan(value, loc, attn)
