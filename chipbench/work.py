"""Operations and bytes that the algorithm needs, from shapes alone.

These are the yardstick's counts, not the program's: they count what
multi-scale deformable attention (MSDA) must compute and move for a
call, whatever implements it.  Saved corners, corner tables, padding and
recomputation never count.  A family counts its own model's FLOPs
(``flops_per_image`` of ``chipbench/families/<family>.py``) and names the
MSDA calls of its step (``MsdaCalls``).

MSDA at one sampling point of one head reads four corner rows of
``head_dim`` channels and blends them:

* forward, per channel: four corner multiply-adds (bilinear) and one
  multiply-add by the attention weight: 10 FLOPs;
* VJP, per channel: the four corner multiply-adds of the sampled value
  (for the attention-weight gradient) and its multiply-add with the
  cotangent, four multiply-adds scattering into the value gradient, and
  two corner-difference multiply-adds per axis for the location
  gradient: 8 + 2 + 8 + 8 = 26 FLOPs.

Bytes are the operands and results at the dtypes the call receives:
value, attention weights and output in the operand dtype, locations in
float32.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Sequence, Tuple

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}
FWD_FLOPS_PER_CHANNEL = 10
BWD_FLOPS_PER_CHANNEL = 26


class MsdaCalls(NamedTuple):
    """One committed MSDA plan's calls in one step of a cell."""

    plan: Any  # the plan: its ``spec`` and ``launches_per_call()``
    per_step: int  # calls of the plan in one step (one per layer)
    fwd_runs: int = 1  # runs of its forward per call (2 under remat)


def _msda_sizes(levels: Sequence[Sequence[int]], batch: int, queries: int,
                heads: int, head_dim: int, points: int, dtype: str):
    it = ITEMSIZE[dtype]
    L = len(levels)
    pixels = sum(h * w for h, w in levels)
    samples = batch * queries * heads * L * points
    value = batch * pixels * heads * head_dim * it
    loc = samples * 2 * 4
    attn = samples * it
    out = batch * queries * heads * head_dim * it
    return samples * head_dim, value, loc, attn, out


def msda_fwd_work(levels, batch, queries, heads, head_dim, points,
                  dtype) -> Tuple[float, float]:
    """(FLOPs, bytes) of one forward MSDA call."""
    chans, value, loc, attn, out = _msda_sizes(
        levels, batch, queries, heads, head_dim, points, dtype)
    return float(FWD_FLOPS_PER_CHANNEL * chans), float(value + loc + attn + out)


def msda_bwd_work(levels, batch, queries, heads, head_dim, points,
                  dtype) -> Tuple[float, float]:
    """(FLOPs, bytes) of one MSDA VJP: reads the three operands and the
    cotangent, writes the three gradients."""
    chans, value, loc, attn, out = _msda_sizes(
        levels, batch, queries, heads, head_dim, points, dtype)
    return (float(BWD_FLOPS_PER_CHANNEL * chans),
            float(2 * (value + loc + attn) + out))


def least_seconds(flops: float, nbytes: float, peak: dict) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_flops = flops / peak["bf16_flop_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
