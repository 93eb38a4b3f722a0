"""MSDA backend registry: name -> executor builder.

A *backend* is a strategy for executing one :class:`~repro.kernels.plan.MsdaSpec`
worth of multi-scale deformable attention.  Builders are registered under a
string name and invoked exactly once per plan (see ``plan.msda_plan``); the
returned executor is a differentiable callable ``exec(value, loc, attn)``
whose VJP wiring was committed at build time.

Builder protocol::

    def builder(spec: MsdaSpec, tuning: PlanTuning) -> Callable:
        ...

Built-in backends (registered on first use, from ``repro.kernels.plan``):

* ``"ref"``    — pure-jnp oracle (the conformance suite's ground truth).
* ``"pallas"`` — the xMSDA Pallas kernels (fwd + custom-VJP bwd), one
  launch per direction over the packed pyramid; tuning decides the
  launch's ``block_q`` and the per-level slab dtypes.
* ``"cpu"``    — CPU-vectorised fused-gather path (vmapped batched
  gather, no Pallas; see ``repro.kernels.msda_cpu``).

Third parties add backends with::

    from repro.kernels import registry

    @registry.backend("my-npu")
    def _build(spec, tuning):
        return my_executor

Every registered backend is automatically exercised by the cross-backend
conformance suite (``tests/conformance.py``), which parametrizes fwd and
VJP parity against ``"ref"`` over ``list_backends()`` x dtype policies.

``"auto"`` is reserved: at plan time it resolves to ``"pallas"`` on TPU,
``"cpu"`` on CPU hosts, and the portable ``"ref"`` oracle anywhere else
(see :func:`resolve_backend`).
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax

BackendBuilder = Callable  # (spec, tuning) -> executor

_BACKENDS: Dict[str, BackendBuilder] = {}
_RESERVED = ("auto",)


class UnknownBackendError(ValueError):
    """Raised when a plan names a backend nobody registered."""


def register_backend(name: str, builder: BackendBuilder, *, overwrite: bool = False) -> BackendBuilder:
    """Register ``builder`` under ``name``; returns the builder (decorator-safe)."""
    if name in _RESERVED:
        raise ValueError(f"backend name {name!r} is reserved")
    if name in _BACKENDS and not overwrite:
        raise ValueError(f"backend {name!r} already registered (overwrite=True to replace)")
    _BACKENDS[name] = builder
    return builder


def backend(name: str, *, overwrite: bool = False):
    """Decorator form of :func:`register_backend`."""

    def deco(builder: BackendBuilder) -> BackendBuilder:
        return register_backend(name, builder, overwrite=overwrite)

    return deco


def unregister_backend(name: str) -> None:
    _BACKENDS.pop(name, None)


def resolve_backend(name: str) -> str:
    """``"auto"`` -> concrete backend for the current jax platform.

    TPU gets the Pallas kernels; CPU gets the vectorised ``"cpu"``
    backend: faster forward than the ``"ref"`` oracle (no per-corner
    transposes or gather-side masks; ~1.2x at the paper-scale CPU
    benchmark) and train parity (backward is scatter-bound for both).
    Anything else (GPU, plugins) keeps the portable ``"ref"`` oracle —
    the cpu backend's gather granularity is tuned to XLA:CPU cache
    behaviour and is unmeasured elsewhere.  ``"ref"`` stays the
    conformance target everywhere.
    """
    if name == "auto":
        platform = jax.default_backend()
        if platform == "tpu":
            return "pallas"
        if platform == "cpu":
            return "cpu"
        return "ref"
    return name


def get_backend(name: str) -> BackendBuilder:
    """Look up a registered builder; raises :class:`UnknownBackendError`."""
    _ensure_defaults()
    name = resolve_backend(name)
    try:
        return _BACKENDS[name]
    except KeyError:
        raise UnknownBackendError(
            f"unknown MSDA backend {name!r}; registered: {sorted(_BACKENDS)}"
        ) from None


def list_backends() -> Tuple[str, ...]:
    _ensure_defaults()
    return tuple(sorted(_BACKENDS))


def _ensure_defaults() -> None:
    """Import the plan module so the built-in backends self-register."""
    if not {"ref", "pallas", "cpu"} <= set(_BACKENDS):
        import repro.kernels.plan  # noqa: F401  (registers on import)
