"""Logical-axis sharding rules -> physical PartitionSpecs.

Contract: this module owns the mapping from *logical* axes ('dp', 'tp',
'ep', 'sp') to *physical* mesh axes, and derives parameter
PartitionSpecs from leaf names — models never name physical axes, and
anything that does not divide a physical axis degrades to replicated
rather than erroring.  The MSDA planner (``repro.kernels.plan``) builds
its 1D/2D sharding ladder on :func:`resolve_axis` / :func:`axis_size` /
:func:`flat_axes`, so a mesh-topology change lands here, once.  See
``docs/sharding.md`` for the full ladder and the 2D (dp x tp) mode.

Logical axes:
  'dp' — data/FSDP axis: batch and the fsdp-sharded dim of weights.
         Maps to ('pod', 'data') on the multi-pod mesh, ('data',) single-pod.
  'tp' — tensor-parallel axis ('model'): heads / d_ff / vocab / experts.
  'ep' — expert-parallel: same physical axis as 'tp' (experts claim it
         when E is divisible by the axis size; otherwise experts fall
         back to TP over d_ff — grok-1's 8 experts on a 16-wide axis).
  'sp' — sequence-parallel: also the 'model' axis, claimed by sequence
         dims (decode KV cache, long-context activations).

Parameter specs are derived from leaf *names* (the contract with
``repro.models``) so any model assembled from those layers inherits a
complete sharding without per-arch tables.  Stacked (scanned) params get
leading ``None`` dims automatically.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_MESH: contextvars.ContextVar[Optional[Mesh]] = contextvars.ContextVar("mesh", default=None)


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Install a mesh for spec resolution + sharding hints."""
    tok = _MESH.set(mesh)
    try:
        with jax.set_mesh(mesh):
            yield mesh
    finally:
        _MESH.reset(tok)


def current_mesh() -> Optional[Mesh]:
    return _MESH.get()


def resolve_axis(logical: Optional[str], mesh: Mesh):
    """Logical axis name -> physical mesh axis (or tuple), or None."""
    if logical is None:
        return None
    names = mesh.axis_names
    if logical == "dp":
        phys = tuple(a for a in ("pod", "data") if a in names)
        return phys if len(phys) > 1 else (phys[0] if phys else None)
    if logical in ("tp", "ep", "sp"):
        return "model" if "model" in names else None
    raise ValueError(f"unknown logical axis {logical!r}")


def flat_axes(axis) -> Tuple[str, ...]:
    """A resolved physical axis (name | tuple | None) as a flat tuple."""
    if axis is None:
        return ()
    return tuple(axis) if isinstance(axis, tuple) else (axis,)


def axis_size(axis, mesh: Mesh) -> int:
    """Total device count along a resolved physical axis (1 for None).

    Accepts the same name | tuple | None shapes :func:`resolve_axis`
    returns, so ``axis_size(resolve_axis('dp', mesh), mesh)`` is the
    data-parallel width even on the multi-pod ('pod', 'data') mesh.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    total = 1
    for a in flat_axes(axis):
        total *= sizes[a]
    return total


def spec(*logical: Optional[str], mesh: Optional[Mesh] = None) -> P:
    mesh = mesh or current_mesh()
    if mesh is None:
        return P()
    return P(*(resolve_axis(a, mesh) for a in logical))


def _divisible(n: int, axis, mesh: Mesh) -> bool:
    if axis is None:
        return False
    return n % axis_size(axis, mesh) == 0


def hint(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """with_sharding_constraint if a mesh is installed; no-op otherwise.

    Logical dims that don't divide the physical axis degrade to None
    (replicated) rather than erroring — keeps one rule set valid across
    every (arch x mesh) cell.
    """
    mesh = current_mesh()
    if mesh is None:
        return x
    axes = []
    for dim, a in enumerate(logical):
        phys = resolve_axis(a, mesh)
        if phys is not None and not _divisible(x.shape[dim], phys, mesh):
            phys = None
        axes.append(phys)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*axes)))


# --------------------------------------------------------------------------
# parameter specs by leaf name
# --------------------------------------------------------------------------

_NAME_RULES = {
    # embeddings / output head
    "emb": ("tp", "dp"),
    "head": ("dp", "tp"),
    # attention
    "wq": ("dp", "tp"),
    "wk": ("dp", "tp"),
    "wv": ("dp", "tp"),
    "wo": ("tp", "dp"),
    "bq": ("tp",),
    "bk": ("tp",),
    "bv": ("tp",),
    # mlp
    "wi": ("dp", "tp"),
    "wg": ("dp", "tp"),
    "wd": ("tp", "dp"),
    # moe (expert tensors handled specially below)
    "router": ("dp", None),
    # rglru
    "wx": ("dp", "tp"),
    "wgate": ("dp", "tp"),
    "conv_w": (None, "tp"),
    "wr": ("tp", None),
    "br": (None,),
    "lam": ("tp",),
    # xlstm
    "wup": ("dp", "tp"),
    "wdown": ("tp", "dp"),
    "wif": ("tp", None),
    "bif": (None,),
    "wz": ("dp", "tp"),
    "rz": (None, None, None, None),
    "bz": (None,),
    # norms / misc
    "scale": (None,),
    "bias": (None,),
    "ngroups": (),
    "b": (None,),
    "w": ("dp", "tp"),  # generic linear
    # msda / detr extras
    "query_emb": (None, None),
    "ref_points": (None, None),
    "level_emb": (None, None),
    "pos_emb": (None, None),
}


def _leaf_logical(path: Tuple[str, ...], ndim: int) -> Tuple[Optional[str], ...]:
    name = path[-1]
    if name.startswith("experts_"):
        # (E, d, ff) or (E, ff, d): EP over 'ep' when divisible (checked at
        # resolution time via hint degradation); orientation by suffix.
        if name.endswith("_wi") or name.endswith("_wg"):
            base = ("ep", "dp", None)
        else:
            base = ("ep", None, "dp")
    elif name in _NAME_RULES:
        base = _NAME_RULES[name]
    else:
        base = (None,) * ndim
    if len(base) > ndim:
        base = base[-ndim:] if ndim else ()
    # stacked/scanned params: leading period dims replicate
    return (None,) * (ndim - len(base)) + tuple(base)


def param_specs(params, mesh: Optional[Mesh] = None, *, moe_experts: int = 0):
    """Pytree of PartitionSpec matching ``params``.

    ``moe_experts``: #experts, used to pick EP vs TP-MoE per mesh size.
    """
    mesh = mesh or current_mesh()

    def one(path, leaf):
        names = tuple(
            k.key if hasattr(k, "key") else str(getattr(k, "idx", k)) for k in path
        )
        ndim = getattr(leaf, "ndim", 0)
        logical = _leaf_logical(names, ndim)
        name = names[-1] if names else ""
        if name.startswith("experts_") and mesh is not None:
            ep_ax = resolve_axis("ep", mesh)
            if not _divisible(moe_experts, ep_ax, mesh):
                # TP-MoE fallback: shard d_ff instead of experts
                if name.endswith("_wi") or name.endswith("_wg"):
                    logical = (None,) * (ndim - 3) + (None, "dp", "tp")
                else:
                    logical = (None,) * (ndim - 3) + (None, "tp", "dp")
        if mesh is None:
            return P()
        axes = []
        for dim, a in enumerate(logical):
            phys = resolve_axis(a, mesh)
            if phys is not None and not _divisible(leaf.shape[dim], phys, mesh):
                phys = None
            axes.append(phys)
        return P(*axes)

    return jax.tree_util.tree_map_with_path(one, params)


def named_sharding_tree(params, mesh: Optional[Mesh] = None, *, moe_experts: int = 0):
    mesh = mesh or current_mesh()
    specs = param_specs(params, mesh, moe_experts=moe_experts)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
