"""Config system: model configs, input-shape cells, and the registry.

Every assigned architecture lives in its own ``src/repro/configs/<id>.py``
module that instantiates a :class:`ModelConfig` and registers it.  The
full configs are exercised only through the AOT dry-run
(ShapeDtypeStruct, no allocation); smoke tests use :func:`reduced`.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field, replace
from typing import Optional, Sequence, Tuple

# --------------------------------------------------------------------------
# Sub-configs
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    # capacity factor bounds tokens routed per expert (train-time dispatch)
    capacity_factor: float = 1.25
    router_jitter: float = 0.0


@dataclass(frozen=True)
class EncoderConfig:
    """Encoder stack for enc-dec (whisper) archs.

    The modality frontend (conv-over-mel) is a STUB per the assignment:
    ``input_specs`` provides precomputed frame embeddings of shape
    ``(batch, num_frames, d_model)``.
    """

    num_layers: int
    num_frames: int  # fixed encoder sequence length (1500 for whisper)


@dataclass(frozen=True)
class VisionConfig:
    """VLM frontend stub: precomputed patch embeddings + MSDA resampler.

    ``levels`` are the multi-scale feature-map sizes of the (stub) CLIP
    pyramid that the MSDA resampler pools into ``num_visual_tokens``.
    """

    num_visual_tokens: int
    vision_dim: int
    levels: Tuple[Tuple[int, int], ...] = ((32, 32), (16, 16), (8, 8))
    msda_points: int = 4
    msda_heads: int = 8
    # serving: variable incoming pyramids are padded into this ladder of
    # fixed bucket geometries (fractions of ``levels``), bounding the
    # plan cache and the set of compiled prefill programs
    # (serving.batcher.default_buckets).
    bucket_scales: Tuple[float, ...] = (1.0, 0.75, 0.5)


@dataclass(frozen=True)
class MSDAConfig:
    """Multi-scale deformable attention config (the paper's op).

    ``backend`` / ``tune`` / ``vmem_budget`` feed straight into the
    plan/execute API (``repro.kernels.plan.msda_plan``): the backend is
    resolved through the registry and block planning runs once per
    static geometry — heuristically, or measured when ``tune="autotune"``.
    """

    levels: Tuple[Tuple[int, int], ...]
    num_points: int = 4
    num_heads: int = 8
    # kernel backend: 'auto' | 'pallas' | 'cpu' | 'ref' | any registered
    backend: str = "auto"
    save_sampled: bool = True  # train mode: stash gathered corners for bwd
    # block planning: 'heuristic' (paper Fig. 7 VMEM model) | 'autotune'
    # (measure candidates once, persist winners per device kind)
    tune: str = "heuristic"
    # per-core VMEM budget for block planning; 0 = default for the
    # device kind (plan.default_vmem_budget)
    vmem_budget: int = 0
    # shard queries (not heads) over 'tp' in the encoder's huge-Q layers
    query_parallel: bool = True
    # distribution family when a mesh is installed: 'auto' walks the
    # ladder (and autotune races 1D vs 2D), '1d' pins the classic
    # query/head/batch ladder, '2d' forces dp x tp query tiling
    sharding: str = "auto"
    # grad_value reduction for query-sharded plans: 'auto' (-> ring),
    # 'ring' (ppermute ring over tp), 'psum' (shard_map transpose
    # all-reduce — ablation / parity baseline)
    grad_reduce: str = "auto"
    # msda dtype policy — the planned precision axis:
    #   'follow'   value-slab dtype tracks the operand dtype (default)
    #   'float32'  force fp32 slabs
    #   'bfloat16' bf16 slabs + fp32 accumulation (half the VMEM
    #              residency, so block planning widens the vec-len)
    #   'auto'     tune='autotune' races fp32 vs bf16 per level and
    #              persists the winner per device kind
    # (mapped to spec fields by repro.kernels.plan.resolve_dtype_policy)
    dtype_policy: str = "follow"
    # top-k attention-weight point pruning (LOSSY — DEFA-style):
    #   'off'   dense MSDA, bitwise-identical to pre-sparsity plans
    #   'topk'  pin the pruned executor (keep sparsity_k cells/query)
    #   'auto'  tune='autotune' races pruned vs dense; heuristic stays
    #           dense (a lossy mode is never picked untimed)
    sparsity: str = "off"
    # cells kept per query under 'topk'; 0 -> ceil(levels*points / 2)
    sparsity_k: int = 0
    # plan-time query ordering (bitwise-neutral to outputs):
    #   'identity' leave queries in raster order
    #   'morton'   Z-curve-permute queries at the executor boundary
    #              (engages only on encoder layouts where Q == S)
    #   'auto'     tune='autotune' races morton vs identity
    query_order: str = "identity"

    def __post_init__(self):
        # mirror of plan.DTYPE_POLICIES keys — kept local so the config
        # layer stays importable without jax / the kernel stack
        if self.dtype_policy not in ("follow", "float32", "bfloat16", "auto"):
            raise ValueError(
                f"unknown msda dtype_policy {self.dtype_policy!r}; one of "
                "'follow' | 'float32' | 'bfloat16' | 'auto'")
        if self.sharding not in ("auto", "1d", "2d"):
            raise ValueError(
                f"unknown msda sharding {self.sharding!r}; one of "
                "'auto' | '1d' | '2d'")
        if self.grad_reduce not in ("auto", "ring", "psum"):
            raise ValueError(
                f"unknown msda grad_reduce {self.grad_reduce!r}; one of "
                "'auto' | 'ring' | 'psum'")
        if self.sparsity not in ("off", "topk", "auto"):
            raise ValueError(
                f"unknown msda sparsity {self.sparsity!r}; one of "
                "'off' | 'topk' | 'auto'")
        if self.sparsity_k < 0:
            raise ValueError(
                f"msda sparsity_k must be >= 0, got {self.sparsity_k}")
        if self.query_order not in ("identity", "morton", "auto"):
            raise ValueError(
                f"unknown msda query_order {self.query_order!r}; one of "
                "'identity' | 'morton' | 'auto'")


# --------------------------------------------------------------------------
# ModelConfig
# --------------------------------------------------------------------------

FAMILIES = ("dense", "moe", "hybrid", "ssm", "audio", "vlm", "vision")


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # one of FAMILIES
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // num_heads
    qkv_bias: bool = False
    tie_embeddings: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    norm_type: str = "rmsnorm"  # rmsnorm | layernorm
    act: str = "silu"  # silu (gated) | gelu (dense ff)
    gated_mlp: bool = True
    moe: Optional[MoEConfig] = None
    encoder: Optional[EncoderConfig] = None
    vision: Optional[VisionConfig] = None
    msda: Optional[MSDAConfig] = None
    # hybrid (recurrentgemma): repeating per-layer block kinds
    block_pattern: Tuple[str, ...] = ("attn",)  # attn|rglru|slstm|mlstm|local
    window: int = 0  # sliding-window size for 'local' attention blocks
    # ssm extras
    lru_width: int = 0  # rglru recurrence width (0 -> d_model)
    # int8 KV cache (serving): halves cache HBM; enabled automatically by
    # the dry-run when the bf16 cache would not fit the mesh (see
    # launch/dryrun.py), or explicitly per config
    kv_quant: bool = False
    dtype: str = "bfloat16"
    # max positions (rope table sizing at trace time is dynamic; informational)
    max_seq_len: int = 524288
    source: str = ""  # provenance note

    def __post_init__(self):
        if self.family not in FAMILIES:
            raise ValueError(f"unknown family {self.family!r}")
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        if self.num_heads % max(self.num_kv_heads, 1) != 0:
            raise ValueError("num_heads must be divisible by num_kv_heads")

    # -- derived quantities -------------------------------------------------
    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    def layer_kinds(self) -> Tuple[str, ...]:
        """Expanded per-layer block kinds (len == num_layers)."""
        pat = self.block_pattern
        return tuple(pat[i % len(pat)] for i in range(self.num_layers))

    def param_count(self) -> int:
        """Analytic parameter count (embedding + blocks + head)."""
        d, dff, v = self.d_model, self.d_ff, self.vocab_size
        emb = v * d
        head = 0 if self.tie_embeddings else v * d
        total = emb + head + d  # final norm
        kinds = self.layer_kinds()
        for kind in kinds:
            total += 2 * d  # two pre-norms (approx; some blocks have one)
            if kind in ("attn", "local"):
                total += d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
                if self.qkv_bias:
                    total += self.q_dim + 2 * self.kv_dim
            elif kind == "rglru":
                w = self.lru_width or d
                total += 2 * d * w + w * d + 2 * w * 4  # conv/gates approx
            elif kind in ("slstm", "mlstm"):
                # xlstm blocks carry their own up/down projections
                up = 2 * d
                total += d * up * 2 + up * d + 4 * up * up // 4
            if kind in ("slstm", "mlstm"):
                pass  # no separate FFN (d_ff == 0)
            elif self.moe is not None:
                total += d * self.moe.num_experts  # router
                total += self.moe.num_experts * (3 if self.gated_mlp else 2) * d * dff
            elif dff:
                total += (3 if self.gated_mlp else 2) * d * dff
        if self.encoder is not None:
            # encoder stack (self-attn + ff) + decoder cross-attn already in kinds? no:
            enc = self.encoder.num_layers * (
                2 * d + 2 * (d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d) // 2
                + (3 if self.gated_mlp else 2) * d * dff
            )
            # decoder cross-attention per layer
            enc += self.num_layers * (d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d + d)
            total += enc
        if self.vision is not None:
            vc = self.vision
            total += vc.vision_dim * d  # projector
        return total

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only top-k experts)."""
        if self.moe is None:
            return self.param_count()
        d, dff = self.d_model, self.d_ff
        per_expert = (3 if self.gated_mlp else 2) * d * dff
        dense = self.param_count() - self.num_layers * self.moe.num_experts * per_expert
        return dense + self.num_layers * self.moe.top_k * per_expert


# --------------------------------------------------------------------------
# Input-shape cells
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # 'train' | 'prefill' | 'decode'


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
    # extra, paper-native: deformable-DETR at the paper's 1024x1024 eval
    # scale (sum HW = 87296 pixel queries); not part of the 40 LM cells
    "detr_1k": ShapeConfig("detr_1k", 87296, 64, "train"),
}


def supports_shape(cfg: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Whether an (arch, shape) cell is runnable; reason if not.

    ``long_500k`` needs sub-quadratic attention: only archs whose
    per-token state is bounded (recurrent / sliding-window) run it.
    """
    if shape.name == "detr_1k":
        if cfg.family != "vision":
            return False, "detr_1k is the vision detector's own cell"
        return True, ""
    if shape.name == "long_500k":
        kinds = set(cfg.layer_kinds())
        quadratic = "attn" in kinds
        if quadratic:
            return False, "full quadratic attention — long_500k skipped per assignment"
    if cfg.family == "vision":
        return False, "vision detector runs its own detr_1k cell"
    return True, ""


# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------

_REGISTRY: dict[str, ModelConfig] = {}


def register(cfg: ModelConfig) -> ModelConfig:
    if cfg.name in _REGISTRY:
        raise ValueError(f"duplicate config {cfg.name}")
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    _ensure_loaded()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}") from None


def list_configs() -> Sequence[str]:
    _ensure_loaded()
    return sorted(_REGISTRY)


_LOADED = False


def _ensure_loaded() -> None:
    global _LOADED
    if _LOADED:
        return
    _LOADED = True
    # import every sibling config module so it registers itself
    import importlib
    import pkgutil

    import repro.configs as pkg

    for m in pkgutil.iter_modules(pkg.__path__):
        if m.name not in ("base",):
            importlib.import_module(f"repro.configs.{m.name}")


# --------------------------------------------------------------------------
# Reduced configs for CPU smoke tests
# --------------------------------------------------------------------------


def reduced(cfg: ModelConfig) -> ModelConfig:
    """Tiny same-family config for single-CPU smoke tests."""
    pat = cfg.block_pattern
    n_layers = max(len(pat), 2)
    kw = dict(
        name=cfg.name + "-smoke",
        num_layers=n_layers,
        d_model=64,
        num_heads=4,
        num_kv_heads=min(cfg.num_kv_heads, 2) or 1,
        head_dim=16,
        d_ff=0 if cfg.d_ff == 0 else 128,
        vocab_size=512,
        lru_width=64 if cfg.lru_width else 0,
        window=min(cfg.window, 32) if cfg.window else 0,
        dtype="float32",
        max_seq_len=256,
    )
    if cfg.moe is not None:
        kw["moe"] = replace(cfg.moe, num_experts=4, top_k=min(cfg.moe.top_k, 2))
    if cfg.encoder is not None:
        kw["encoder"] = EncoderConfig(num_layers=2, num_frames=16)
    if cfg.vision is not None:
        kw["vision"] = VisionConfig(
            num_visual_tokens=8, vision_dim=32, levels=((8, 8), (4, 4)), msda_points=2, msda_heads=2
        )
    if cfg.msda is not None:
        kw["msda"] = replace(cfg.msda, levels=((8, 8), (4, 4)), num_points=2, num_heads=2)
    smoke = replace(cfg, **kw)
    # bypass registry (smoke configs are ephemeral)
    return smoke
