"""Operations and bytes that the algorithm needs, from shapes alone.

These are the yardstick's counts, not the program's: they count what
multi-scale deformable attention (MSDA) and the Deformable-DETR model
must compute and move for a call, whatever implements it.  Saved
corners, corner tables, padding and recomputation never count.

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

from typing import Sequence, Tuple

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}
FWD_FLOPS_PER_CHANNEL = 10
BWD_FLOPS_PER_CHANNEL = 26


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


def model_forward_flops(cfg: dict) -> float:
    """Model FLOPs of one image's forward pass (multiply-add = 2 FLOPs).

    Counts the encoder and decoder projections, the FFNs, decoder
    self-attention, MSDA interpolation and the heads.  Norms, softmaxes,
    activations and the matching cost are left out: they are a few
    FLOPs per element beside these.
    """
    d, ff = cfg["d_model"], cfg["d_ff"]
    H, P = cfg["num_heads"], cfg["num_points"]
    L = len(cfg["levels"])
    S = sum(h * w for h, w in cfg["levels"])
    nq, C = cfg["num_queries"], cfg["num_classes"]
    hd = cfg["head_dim"]
    hlp = H * L * P

    def msda_module(q_tokens: int) -> float:
        return (2 * S * d * d                       # value projection
                + 2 * q_tokens * d * hlp * 2        # sampling offsets
                + 2 * q_tokens * d * hlp            # attention weights
                + FWD_FLOPS_PER_CHANNEL * q_tokens * hlp * hd  # interpolation
                + 2 * q_tokens * d * d)             # output projection

    def ffn(tokens: int) -> float:
        return 2 * tokens * d * ff * 2

    enc = cfg["encoder_layers"] * (msda_module(S) + ffn(S))
    self_attn = 4 * 2 * nq * d * d + 2 * 2 * nq * nq * d
    dec = cfg["decoder_layers"] * (self_attn + msda_module(nq) + ffn(nq))
    heads = (2 * nq * d * 2                         # reference points
             + 2 * nq * d * C                       # class logits
             + 2 * nq * d * d + 2 * nq * d * 4)     # box MLP
    return float(enc + dec + heads)


def flops_per_image(cfg: dict, mode: str) -> float:
    """A training image counts its forward and backward (3x forward);
    recomputation does not count.  An inference image counts 1x."""
    fwd = model_forward_flops(cfg)
    return 3.0 * fwd if mode == "train" else fwd
