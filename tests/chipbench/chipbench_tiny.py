"""A checkout root for the benchmark at a size a CPU test can hold.

The test size keeps every width of ``deformable-detr`` (d=256, 8 heads
of 32, 4 points, FFN 1024, 300 queries, 91 classes) and cuts the pyramid
to two levels (16x16, 8x8) and the depth to 3+3 layers.

It holds a ``BENCHMARK.json`` with one tiny training cell and one tiny
inference cell, their configuration, traffic and limits files, copies
of the benchmark's metric readers and model families, and a link to the
program's ``src``.
"""
from __future__ import annotations

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:  # the benchmark package sits at the checkout root
    sys.path.insert(0, REPO)

TINY_CONFIG = {
    "name": "tiny-detr", "family": "deformable-detr", "source": "test",
    "registered": "deformable-detr",
    "levels": [[16, 16], [8, 8]], "d_model": 256, "num_heads": 8,
    "head_dim": 32, "num_points": 4, "encoder_layers": 3, "decoder_layers": 3,
    "d_ff": 1024, "num_queries": 300, "num_classes": 91, "act": "gelu",
    "norm_eps": 1e-05, "dtype": "bfloat16",
}
DATA = {"distinct_batches": 3, "num_targets": 3, "feature_std": 0.05,
        "signature": 2.0, "box_low": 0.2, "box_high": 0.8}
TRAIN = {"mode": "train", "batch": 2, **DATA, "peak_lr": 2e-4,
         "warmup_steps": 0, "total_steps": 184800, "weight_decay": 1e-4,
         "clip_norm": 0.1, "checked_steps": 2}
INFER = {"mode": "infer", "batch": 2, **DATA, "checked_batches": 2}
# Limits of the test size, set like a cell's (PERF.md, section 6): from
# the program's largest reading over seeds 11..22 (lower) and the
# smallest of the float8 control, or of a fault that reads 10x the lower
# (a state left unchanged: 3x; it reads 1), on the CPU:
#   first_loss_rel_gap  lower 1.06e-3, upper 2.11e-2 (half batch)
#   grad_median_leaf_gap lower 0.0432, upper 1 (state unchanged)
#   change_leaf_gap     lower 0.0365,  upper 1 (state unchanged)
#   class_bias_grad_gap lower 2.61e-3, upper 1.125e-2 (control)
#   logit_gap           lower 0.0472,  upper 0.371 (control)
#   box_gap             lower 4.96e-3, upper 0.0453 (control)
# each limit at lower^0.4 * upper^0.6.
LIMITS = {
    "tiny-train": {"first_loss_rel_gap": 0.0064, "grad_median_leaf_gap": 0.28,
                   "change_leaf_gap": 0.27, "class_bias_grad_gap": 0.0063},
    "tiny-infer": {"logit_gap": 0.16, "box_gap": 0.019},
}
CONTROL_SEEDS = list(range(11, 23))


def bench_json() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    return {
        **real,
        "configs": [{"name": "tiny-detr", "source": "test",
                     "file": "chipbench/configs/tiny-detr.json",
                     "reduced": [], "why": "test"}],
        "workloads": [
            {"name": "tiny-train", "config": "tiny-detr", "traffic": "tiny-train",
             "chips": 1, "why": "test"},
            {"name": "tiny-infer", "config": "tiny-detr", "traffic": "tiny-infer",
             "chips": 1, "why": "test"},
        ],
        "per_layer": [{k: v for k, v in m.items() if k != "workloads"}
                      for m in real["per_layer"]],
    }


def make_root(path: str) -> str:
    """Write the tiny checkout under ``path``; returns ``path``."""
    cb = os.path.join(path, "chipbench")
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(cb, sub), exist_ok=True)
    for sub in ("metrics", "families"):
        shutil.copytree(os.path.join(REPO, "chipbench", sub),
                        os.path.join(cb, sub), dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))

    def dump(rel, obj):
        with open(os.path.join(path, rel), "w") as f:
            json.dump(obj, f)

    dump("BENCHMARK.json", bench_json())
    dump("chipbench/configs/tiny-detr.json", TINY_CONFIG)
    dump("chipbench/traffic/tiny-train.json", TRAIN)
    dump("chipbench/traffic/tiny-infer.json", INFER)
    for name, lim in LIMITS.items():
        dump(f"chipbench/limits/{name}.json", lim)
    src = os.path.join(path, "src")
    if not os.path.exists(src):
        os.symlink(os.path.join(REPO, "src"), src)
    return path


CACHE_KEYS = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
              "jax_persistent_cache_min_entry_size_bytes",
              "jax_persistent_cache_min_compile_time_secs")


class kept_cache_config:
    """A run turns JAX's persistent compilation cache on in its checkout;
    give the test process its settings back afterwards."""

    def __enter__(self):
        import jax

        self.saved = {k: getattr(jax.config, k) for k in CACHE_KEYS}
        return self

    def __exit__(self, *exc):
        import jax
        from jax.experimental.compilation_cache import compilation_cache

        for k, v in self.saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        return False
