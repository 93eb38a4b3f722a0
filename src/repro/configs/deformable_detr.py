"""deformable-detr: the paper's own host architecture (extra, paper-native).

Six-layer MSDA encoder over a 5-level pyramid from a (stub) Swin
backbone at 1024x1024 input — the exact workload of the paper's
evaluation (sum HW = 87296, d=256, 8 heads, 4 points) — plus a 6-layer
deformable decoder with 300 object queries.
"""
from repro.configs.base import MSDAConfig, ModelConfig, register

PAPER_LEVELS = ((256, 256), (128, 128), (64, 64), (32, 32), (16, 16))

CONFIG = register(ModelConfig(
    name="deformable-detr",
    family="vision",
    num_layers=6,            # encoder layers (decoder mirrors with 6)
    d_model=256,
    num_heads=8,
    num_kv_heads=8,
    d_ff=1024,
    vocab_size=91,           # COCO classes as the 'vocab' (detection head)
    head_dim=32,
    gated_mlp=False,
    act="gelu",
    norm_type="layernorm",
    # plan/execute knobs: backend resolved once through the registry;
    # tune="autotune" measures the launch's block_q candidates and persists
    # winners per device kind (see repro.kernels.plan.msda_plan).
    # dtype_policy="auto" defers the per-level fp32-vs-bf16 slab choice
    # to the autotune race — which only runs under tune="autotune" (flip
    # it on a real fleet; the default heuristic planning keeps the
    # operand dtype, so this knob is a no-op until then).  When the race
    # does pick bf16 for the 256x256 level its slab halves to ~4 MiB and
    # block re-planning widens the encoder's vec-len; accumulation stays
    # fp32 either way.
    # sharding="auto": on a real mesh the 87k-query encoder clears the
    # 2D threshold (87040 / 16 = 5440 queries per shard on a 4x4 slice),
    # so its plan commits dp x tp query tiling with ring-reduced
    # grad_value slabs; the 300-query decoder stays on the 1D ladder.
    msda=MSDAConfig(levels=PAPER_LEVELS, num_points=4, num_heads=8,
                    backend="auto", tune="heuristic", vmem_budget=0,
                    query_parallel=True, dtype_policy="auto",
                    sharding="auto", grad_reduce="auto"),
    source="arXiv:2010.04159 (Deformable DETR) + paper §3 input spec",
))
