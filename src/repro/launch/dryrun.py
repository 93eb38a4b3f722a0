import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
)
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: builds the
production mesh from 512 placeholder host devices, lowers the real
``train_step`` / ``prefill`` / ``serve_step`` with ShapeDtypeStruct
inputs (no allocation), compiles through the GSPMD partitioner, and
extracts:

* ``memory_analysis()``   — per-device bytes (proves it fits 16 GB HBM);
* ``cost_analysis()``     — HLO FLOPs / bytes for the roofline terms;
* collective bytes        — parsed from the post-SPMD HLO text
  (all-gather / all-reduce / reduce-scatter / all-to-all /
  collective-permute operand sizes + wire-byte estimates).

Results are merged into ``experiments/dryrun_results.json`` so the
sweep is resumable cell by cell:

    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import re
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES, ShapeConfig, get_config, list_configs, supports_shape
from repro.launch import hlo_analysis, mesh as mesh_lib
from repro.optim import adamw
from repro.sharding import rules
from repro.train import loop as train_loop
from repro.train import state as train_state

RESULTS_PATH = os.path.join(os.path.dirname(__file__), "../../../experiments/dryrun_results.json")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2, "u8": 1,
    "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")


# --------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins, no allocation)
# --------------------------------------------------------------------------


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def input_specs(cfg, shape: ShapeConfig) -> Dict[str, Any]:
    """Model inputs for one cell as ShapeDtypeStructs (+ logical specs)."""
    B, S = shape.global_batch, shape.seq_len
    dt = cfg.dtype
    if shape.kind == "train":
        if cfg.family == "audio":
            return {
                "frames": (_sds((B, cfg.encoder.num_frames, cfg.d_model), dt), P("dp", None, None)),
                "tokens": (_sds((B, S), "int32"), P("dp", None)),
                "targets": (_sds((B, S), "int32"), P("dp", None)),
            }
        if cfg.family == "vlm":
            from repro.models import vlm as vlm_mod

            sv = vlm_mod.pyramid_len(cfg.vision)
            return {
                "pyramid": (_sds((B, sv, cfg.vision.vision_dim), dt), P("dp", None, None)),
                "tokens": (_sds((B, S), "int32"), P("dp", None)),
                "targets": (_sds((B, S), "int32"), P("dp", None)),
            }
        if cfg.family == "vision":
            sp = sum(h * w for h, w in cfg.msda.levels)
            return {
                "pyramid": (_sds((B, sp, cfg.d_model), dt), P("dp", None, None)),
                "labels": (_sds((B, 20), "int32"), P("dp", None)),
                "boxes": (_sds((B, 20, 4), "float32"), P("dp", None, None)),
            }
        return {
            "tokens": (_sds((B, S), "int32"), P("dp", None)),
            "targets": (_sds((B, S), "int32"), P("dp", None)),
        }
    if shape.kind == "prefill":
        out = {"tokens": (_sds((B, S), "int32"), P("dp", None))}
        if cfg.family == "audio":
            out["frames"] = (_sds((B, cfg.encoder.num_frames, cfg.d_model), dt), P("dp", None, None))
        if cfg.family == "vlm":
            from repro.models import vlm as vlm_mod

            sv = vlm_mod.pyramid_len(cfg.vision)
            out["pyramid"] = (_sds((B, sv, cfg.vision.vision_dim), dt), P("dp", None, None))
        return out
    if shape.kind == "decode":
        return {"token": (_sds((B,), "int32"), P("dp"))}
    raise ValueError(shape.kind)


def _resolve(mesh, logical_spec: P, shape=None) -> NamedSharding:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def nshard(ax):
        t = 1
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            t *= sizes[a]
        return t

    axes = []
    for i, a in enumerate(logical_spec):
        phys = rules.resolve_axis(a, mesh) if isinstance(a, str) else a
        if phys is not None and shape is not None and shape[i] % nshard(phys) != 0:
            phys = None  # degrade to replicated (e.g. batch=1 long_500k)
        axes.append(phys)
    return NamedSharding(mesh, P(*axes))


# --------------------------------------------------------------------------
# cache sharding (decode/prefill cells)
# --------------------------------------------------------------------------


def cache_specs(cache_shapes, mesh, batch: int, capacity: int):
    """Generic cache sharding: batch axis -> dp, capacity axis -> model (SP).

    Works uniformly across KV caches (incl. MQA kv=1, where head-sharding
    would idle the model axis — sequence-sharding the cache is the
    scalable choice), ring buffers, recurrent states.
    """
    dp = rules.resolve_axis("dp", mesh)
    tp = rules.resolve_axis("tp", mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def nshard(ax):
        if ax is None:
            return 1
        t = 1
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            t *= sizes[a]
        return t

    def one(leaf):
        spec = [None] * leaf.ndim
        used_b = used_c = False
        for i, dim in enumerate(leaf.shape):
            if not used_b and dim == batch and dim % nshard(dp) == 0:
                spec[i] = dp
                used_b = True
            elif not used_c and dim == capacity and dim % nshard(tp) == 0:
                spec[i] = tp
                used_c = True
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(one, cache_shapes)


# --------------------------------------------------------------------------
# per-cell build: (fn, args, in_shardings, donate)
# --------------------------------------------------------------------------


def _microbatches(cfg, shape: ShapeConfig, mesh) -> int:
    """Grad-accumulation factor: bound per-device microbatch activations."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = sizes.get("data", 1) * sizes.get("pod", 1)
    per_dev = max(shape.global_batch // dp, 1)
    # 1 sequence per device per microbatch for wide models (remat-saved
    # per-layer inputs scale with d_model x layers) and for enc-dec
    # (whisper re-encodes 1500 frames per microbatch), 2 for narrow LMs
    target = 2 if (shape.seq_len <= 4096 and cfg.d_model < 5120
                   and cfg.family != "audio") else 1
    n = max(1, per_dev // target)
    while shape.global_batch % n:
        n -= 1
    return n


def build_cell(cfg, shape: ShapeConfig, mesh):
    """Returns (jitted_fn, args, meta) ready to .lower()."""
    specs = input_specs(cfg, shape)
    args_sds = {k: v[0] for k, v in specs.items()}
    args_sharding = {k: _resolve(mesh, v[1], v[0].shape) for k, v in specs.items()}

    params_shape = jax.eval_shape(lambda: train_state.init_model(jax.random.PRNGKey(0), cfg))
    moe_e = cfg.moe.num_experts if cfg.moe else 0
    pspecs = rules.param_specs(params_shape, mesh, moe_experts=moe_e)
    psharding = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)

    if shape.kind == "train":
        nm = _microbatches(cfg, shape, mesh)
        step = train_loop.make_train_step(
            cfg, num_microbatches=nm, param_specs=psharding
        )
        # >100B params: bf16 optimizer states (fp32 AdamW state alone is
        # 14.7 GB/chip for grok-1 at 256 chips) — standard at this scale
        n_params = sum(l.size for l in jax.tree.leaves(params_shape))
        opt_dtype = jnp.bfloat16 if n_params > 100e9 else jnp.float32
        state_shape = jax.eval_shape(
            lambda: train_state.TrainState(
                params=params_shape,
                opt=adamw.init_adamw(params_shape, state_dtype=opt_dtype),
                step=jnp.zeros((), jnp.int32),
            )
        )
        opt_sharding = train_state.TrainState(
            params=psharding,
            opt=type(state_shape.opt)(
                m=psharding, v=psharding, count=NamedSharding(mesh, P())
            ),
            step=NamedSharding(mesh, P()),
        )
        fn = jax.jit(
            step,
            in_shardings=(opt_sharding, args_sharding),
            donate_argnums=(0,),
        )
        return fn, (state_shape, args_sds), {"microbatches": nm}

    from repro.serving.engine import make_serve_fns

    # serving deployments load bf16 weights; declare the served params so
    # (fp32 masters are a training artifact — grok decode: 4.9 GB/chip
    # of fp32 params for no benefit)
    params_shape = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16)
        if l.dtype == jnp.float32 and l.ndim >= 2 else l,
        params_shape,
    )
    prefill, decode = make_serve_fns(cfg)
    B, S = shape.global_batch, shape.seq_len

    if shape.kind == "prefill":
        cap = S + (cfg.vision.num_visual_tokens if cfg.family == "vlm" else 0)
        fn = jax.jit(
            lambda params, inputs: prefill(params, **inputs, capacity=cap),
            in_shardings=(psharding, args_sharding),
        )
        return fn, (params_shape, args_sds), {}

    # decode: auto-enable the int8 KV cache when the bf16 cache alone
    # would crowd the chips (qwen1.5-32B MHA: 21.5 GB/chip at bf16)
    meta_kv = {}
    if cfg.family in ("dense", "moe", "hybrid", "vlm") and shape.kind == "decode":
        slots = sum(
            S if k == "attn" else min(cfg.window, S) if k == "local" else 0
            for k in cfg.layer_kinds()
        )
        cache_gb = 2 * B * slots * cfg.num_kv_heads * cfg.head_dim * 2 \
            / mesh_lib.chips(mesh) / 1e9
        if cache_gb > 6.0:
            import dataclasses

            cfg = dataclasses.replace(cfg, kv_quant=True)
            meta_kv = {"kv_quant": True, "bf16_cache_gb_per_chip": round(cache_gb, 1)}
    if cfg.family == "audio":
        from repro.models import whisper as wh

        cache_shape = jax.eval_shape(
            lambda p, f, t: wh.whisper_prefill(p, cfg, f, t, S),
            params_shape,
            _sds((B, cfg.encoder.num_frames, cfg.d_model), cfg.dtype),
            _sds((B, 8), "int32"),
        )[1]
    elif cfg.family == "vlm":
        from repro.models import lm as lm_mod

        cache_shape = jax.eval_shape(
            lambda: lm_mod.init_cache(cfg, B, S, jnp.dtype(cfg.dtype))
        )
    else:
        from repro.models import lm as lm_mod

        cache_shape = jax.eval_shape(
            lambda: lm_mod.init_cache(cfg, B, S, jnp.dtype(cfg.dtype))
        )
    csharding = cache_specs(cache_shape, mesh, B, S)
    fn = jax.jit(
        lambda params, cache, token: decode(params, cache, token),
        in_shardings=(psharding, csharding, args_sharding["token"]),
        donate_argnums=(1,),
    )
    return fn, (params_shape, cache_shape, args_sds["token"]), meta_kv


# --------------------------------------------------------------------------
# analytic model FLOPs (the roofline's "useful compute" reference)
# --------------------------------------------------------------------------


def model_flops(cfg, shape: ShapeConfig) -> float:
    """6 * N_active * tokens (x1 for inference kinds, fwd only => 2*N*D)."""
    n = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    per_tok = 6.0 * n if shape.kind == "train" else 2.0 * n
    return per_tok * tokens


# --------------------------------------------------------------------------
# run one cell
# --------------------------------------------------------------------------


def run_cell(arch: str, shape_name: str, mesh_kind: str, *, verbose: bool = True) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = supports_shape(cfg, shape)
    cell = {"arch": arch, "shape": shape_name, "mesh": mesh_kind}
    if not ok:
        cell.update(status="skip", reason=reason)
        return cell

    mesh = mesh_lib.make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    try:
        with rules.use_mesh(mesh):
            fn, args, meta = build_cell(cfg, shape, mesh)
            lowered = fn.lower(*args)
            t_lower = time.time() - t0
            t1 = time.time()
            compiled = lowered.compile()
            t_compile = time.time() - t1
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
    except Exception as e:  # noqa: BLE001 — any failure here is a finding
        cell.update(status="error", error=f"{type(e).__name__}: {e}"[:2000],
                    t=time.time() - t0)
        return cell

    n_chips = mesh_lib.chips(mesh)
    ana = hlo_analysis.analyze(hlo)
    flops_nominal = float(cost.get("flops", -1.0)) if cost else -1.0
    memd = {}
    for attr in ("temp_size_in_bytes", "argument_size_in_bytes", "output_size_in_bytes",
                 "alias_size_in_bytes", "generated_code_size_in_bytes"):
        memd[attr] = getattr(mem, attr, None)

    # roofline terms (per-chip HLO numbers vs per-chip peaks)
    pk = mesh_lib.peaks(mesh.devices.flat[0].device_kind)
    t_compute = ana["flops"] / pk["flops_bf16"]
    t_memory = ana["mem_bytes"] / pk["hbm_bw"]
    t_coll = ana["collectives"]["wire_bytes"] / pk["ici_bw"]
    mflops = model_flops(cfg, shape)
    cell.update(
        status="ok",
        meta=meta,
        t_lower=round(t_lower, 2),
        t_compile=round(t_compile, 2),
        flops_per_device=ana["flops"],
        flops_nominal_costanalysis=flops_nominal,
        mem_bytes_per_device=ana["mem_bytes"],
        collectives=ana["collectives"],
        model_flops_global=mflops,
        useful_flops_ratio=mflops / max(ana["flops"] * n_chips, 1.0),
        roofline={
            "compute_s": t_compute,
            "memory_s": t_memory,
            "collective_s": t_coll,
            "bottleneck": max(
                ("compute", t_compute), ("memory", t_memory), ("collective", t_coll),
                key=lambda kv: kv[1],
            )[0],
        },
        memory=memd,
        n_chips=n_chips,
    )
    if verbose:
        print(json.dumps(cell, indent=None, default=str)[:600])
    return cell


def load_results() -> Dict[str, Any]:
    path = os.path.abspath(RESULTS_PATH)
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return {}


def save_result(cell: Dict[str, Any]) -> None:
    path = os.path.abspath(RESULTS_PATH)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    results = load_results()
    key = f"{cell['arch']}|{cell['shape']}|{cell['mesh']}"
    results[key] = cell
    with open(path, "w") as f:
        json.dump(results, f, indent=1, default=str)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=("single", "multi", "both"))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true", help="re-run cached cells")
    args = ap.parse_args()

    meshes = ("single", "multi") if args.mesh == "both" else (args.mesh,)
    archs = list_configs() if args.all or args.arch is None else [args.arch]
    shapes = list(SHAPES) if args.all or args.shape is None else [args.shape]
    done = load_results()
    for arch in archs:
        for shape_name in shapes:
            for mk in meshes:
                key = f"{arch}|{shape_name}|{mk}"
                if not args.force and done.get(key, {}).get("status") == "ok":
                    print(f"[cached] {key}")
                    continue
                print(f"[dryrun] {key} ...", flush=True)
                cell = run_cell(arch, shape_name, mk)
                save_result(cell)
                done[key] = cell
                print(f"  -> {cell['status']} "
                      f"(lower {cell.get('t_lower', '-')}s compile {cell.get('t_compile', '-')}s)",
                      flush=True)


if __name__ == "__main__":
    main()
