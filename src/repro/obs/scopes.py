"""The names of the device work: ``jax.named_scope`` names and Pallas
kernel names, and where an op of a compiled program belongs.

The program wraps its layers in named scopes (``with
jax.named_scope(scopes.ENCODER):``) and names its Pallas kernels.  The
names cost nothing at run time: they reach the compiled HLO only as op
metadata, ``metadata={op_name="jit(step)/jvp(encoder)/while/body/..."}``.
A profiler trace's device op events carry no op metadata, only the HLO
instruction's name (``%fusion.373``), which is unique in its module; so
a profile is attributed by joining each event's instruction to its
op_name in ``compiled.as_text()`` and passing that to :func:`layer_of`
(``docs/observability.md``, "Device scopes").

Scopes, outermost first:

* ``encoder``, ``decoder``: the two layer scans and their prologues;
  ``self_attn`` (decoder) and ``ffn`` inside their layer bodies;
  ``heads``: final norm, class and box heads.
* ``msda_proj``: an MSDA module's offset, attention-weight, value and
  output projections.
* ``msda_fwd``: the MSDA op's forward, around ``msda_tables`` (corner
  rows and weights), ``msda_slab`` (the grouped, padded value slabs),
  ``msda_kernel`` (each Pallas launch: kernel ``msda_gather``) and
  ``msda_reduce`` (the launches' sum and the output transpose).
* ``msda_bwd``: the MSDA op's custom VJP, around ``msda_slab`` (the
  cotangent's layout), ``msda_kernel`` (kernel ``msda_scatter``) and
  ``msda_grad_unpack`` (grad slabs and weight-table grads unpacked).
* ``matching``, ``loss``: the detection loss; ``optimizer``: the
  mixed-precision copies of weights and gradients, clipping and AdamW.
* BEVFormer (``repro.models.bevformer``), inside ``encoder``:
  ``bev_temporal`` (the can_bus MLP, the ego shift of the reference
  points and the rotation of the previous BEV), ``bev_tsa`` (temporal
  self-attention and its projections), ``bev_sca`` (spatial
  cross-attention) and, inside it, ``sca_rebatch`` (the pillars'
  projection into the cameras, the hit mask, the packing of each
  camera's hit queries, the scatter back and the division by the
  cameras hit).
"""
from __future__ import annotations

import re
from typing import Tuple

ENCODER = "encoder"
DECODER = "decoder"
SELF_ATTN = "self_attn"
FFN = "ffn"
HEADS = "heads"
MATCHING = "matching"
LOSS = "loss"
OPTIMIZER = "optimizer"
MSDA_PROJ = "msda_proj"
MSDA_FWD = "msda_fwd"
MSDA_BWD = "msda_bwd"
MSDA_TABLES = "msda_tables"
MSDA_SLAB = "msda_slab"
MSDA_KERNEL = "msda_kernel"
MSDA_REDUCE = "msda_reduce"
MSDA_GRAD_UNPACK = "msda_grad_unpack"
BEV_TEMPORAL = "bev_temporal"
BEV_TSA = "bev_tsa"
BEV_SCA = "bev_sca"
SCA_REBATCH = "sca_rebatch"

# Pallas kernel names (``pl.pallas_call(name=...)``): the HLO custom call
# and its trace event are named after them, and JAX puts them in the
# kernel's op_name too
GATHER_KERNEL = "msda_gather"
SCATTER_KERNEL = "msda_scatter"
KERNELS = {"fwd": GATHER_KERNEL, "bwd": SCATTER_KERNEL}

SCOPES = (ENCODER, DECODER, SELF_ATTN, FFN, HEADS, MATCHING, LOSS,
          OPTIMIZER, MSDA_PROJ, MSDA_FWD, MSDA_BWD, MSDA_TABLES, MSDA_SLAB,
          MSDA_KERNEL, MSDA_REDUCE, MSDA_GRAD_UNPACK, GATHER_KERNEL,
          SCATTER_KERNEL, BEV_TEMPORAL, BEV_TSA, BEV_SCA, SCA_REBATCH)
# the MSDA op as a whole, and its XLA work around the kernels
MSDA_OPS = (MSDA_FWD, MSDA_BWD)
MSDA_XLA = (MSDA_TABLES, MSDA_SLAB, MSDA_REDUCE, MSDA_GRAD_UNPACK)

# JAX's name-stack components that mark the forward recomputed inside the
# backward pass under ``jax.checkpoint``
_RECOMPUTED = "rematted_computation"
_WRAPPED = re.compile(r"([\w.-]+)\((.*)\)")


def _unwrap(component: str) -> Tuple[str, Tuple[str, ...]]:
    """``transpose(jvp(encoder))`` -> ``("encoder", ("transpose", "jvp"))``:
    the name inside JAX's transformation wrappers (``jit(...)``,
    ``jvp(...)``, ``transpose(...)``, ``vmap(...)``) and the wrappers."""
    wrappers = []
    m = _WRAPPED.fullmatch(component)
    while m:
        wrappers.append(m.group(1))
        component = m.group(2)
        m = _WRAPPED.fullmatch(component)
    return component, tuple(wrappers)


def layer_of(op_name: str) -> Tuple[Tuple[str, ...], str]:
    """``(scopes, direction)`` of an HLO op's ``op_name``.

    ``scopes`` are the known scopes on its name stack in nesting order,
    JAX's wrappers and the components they add (``while``/``body``,
    ``checkpoint``, ``closed_call``, the primitive's name) stripped;
    empty for an op under no known scope.  ``direction`` is ``"bwd"``
    inside ``msda_bwd``, and for JAX's own autodiff of anything else (a
    ``transpose(...)`` on the name stack, which covers the transpose of
    the table math in ``msda_fwd/msda_tables``) unless the op is the
    forward recomputed there under remat; otherwise ``"fwd"``.
    """
    scopes, transposed, recomputed = [], False, False
    for component in op_name.split("/"):
        name, wrappers = _unwrap(component)
        transposed = transposed or "transpose" in wrappers
        recomputed = recomputed or name == _RECOMPUTED
        if name in SCOPES:
            scopes.append(name)
    if MSDA_BWD in scopes or (transposed and not recomputed):
        return tuple(scopes), "bwd"
    return tuple(scopes), "fwd"
