"""Production meshes.

Mesh construction is a FUNCTION (importing this module never touches
jax device state).  Axes:

* single-pod: ``(data=16, model=16)`` — one v5e-256 pod;
* multi-pod:  ``(pod=2, data=16, model=16)`` — 512 chips; 'pod' extends
  the data-parallel dimension across the DCN boundary (gradient
  reduction is hierarchical: reduce-scatter intra-pod over ICI, then
  all-reduce inter-pod over the slow links, where int8 error-feedback
  compression is available — see optim/grad_compression.py).
"""
from __future__ import annotations

import jax

# Per-chip peaks for the roofline, keyed by jax.Device.device_kind.
# TPU v5e ("TPU v5 lite"): Google Cloud documentation, "TPU v5e" —
# 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s of inter-chip
# interconnect (per link: 50 GB/s).
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def peaks(device_kind: str) -> dict:
    """Peaks of ``device_kind``; a kind not in :data:`PEAKS` is an error,
    not a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak figures for device kind {device_kind!r}; add them, "
            "with their source, to repro.launch.mesh.PEAKS") from None


def _auto_mesh(shape, axes):
    # Auto axes: the MSDA plans and rules.hint shard through shard_map and
    # with_sharding_constraint, which Explicit axes (jax.make_mesh's
    # default) would turn into assertions
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh():
    """1-device mesh with production axis names (CPU tests)."""
    return _auto_mesh((1, 1), ("data", "model"))


def parse_mesh_shape(token: str):
    """'1'/'none'/'local' -> None (no mesh); 'DPxTP' (e.g. '2x2') -> (dp, tp).

    The one parser for mesh-shape CLI tokens (``launch/serve.py
    --mesh``, ``benchmarks/sweep.py --mesh-shapes``) — raises ValueError
    naming the offending token so callers can report-and-continue.
    """
    if token in ("1", "none", "local"):
        return None
    parts = str(token).lower().split("x")
    if len(parts) != 2 or not all(p.isdigit() and int(p) > 0 for p in parts):
        raise ValueError(
            f"bad mesh shape {token!r}: use '1' (no mesh) or 'DPxTP' like 2x2")
    return int(parts[0]), int(parts[1])


def make_mesh_2d(dp: int, tp: int):
    """(data=dp, model=tp) mesh over the first dp*tp local devices.

    The small-mesh constructor behind the 2D (dp x tp) MSDA sharding
    tests and the benchmark sweep's mesh axis: on a host split into N
    virtual CPU devices it yields a real multi-device mesh whose
    collectives (ring ppermute, psum) actually execute, and on TPU it is
    just a sub-slice mesh.  Raises if fewer than dp*tp devices exist.
    """
    n = dp * tp
    devs = jax.devices()
    if len(devs) < n:
        raise ValueError(f"mesh {dp}x{tp} needs {n} devices, have {len(devs)}")
    import numpy as np

    return jax.sharding.Mesh(
        np.asarray(devs[:n]).reshape(dp, tp), ("data", "model"))


def chips(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
