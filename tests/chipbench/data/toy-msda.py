"""A toy model family, added to a checkout by new files alone
(``test_chipbench_family.py`` writes it to ``chipbench/families/``).

The model is a stack of MSDA self-attention layers over a two-level
pyramid: per layer ``x += MSDA(x, x)``, every pixel a query whose
reference point is its own centre, through the program's
``repro.core.msda.msda_attention``.  The check compares the window's
outputs with a plain float32 MSDA written here, which imports nothing of
the program: ``out_gap``, the widest gap of an output over the
reference's root mean square.
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

from chipbench import checks, harness, work

HIGHEST = "highest"


def program_config(cfg: dict):
    from repro.configs.base import MSDAConfig

    return MSDAConfig(levels=tuple(tuple(l) for l in cfg["levels"]),
                      num_points=cfg["num_points"],
                      num_heads=cfg["num_heads"])


def _sizes(cfg):
    H, L, P = cfg["num_heads"], len(cfg["levels"]), cfg["num_points"]
    return cfg["d_model"], H, L, P, sum(h * w for h, w in cfg["levels"])


def _weights(seed: int, cfg: dict) -> dict:
    """Every layer's MSDA weights, stacked, in one jitted call."""
    import jax
    import jax.numpy as jnp

    d, H, L, P, _ = _sizes(cfg)
    n, hlp = cfg["layers"], H * L * P

    def lecun(k, shape):
        return jax.random.normal(k, shape, jnp.float32) / math.sqrt(shape[-2])

    def build(key):
        k = jax.random.split(key, 5)
        return {"value_proj": lecun(k[0], (n, d, d)),
                "out_proj": lecun(k[1], (n, d, d)),
                "w_offsets": lecun(k[2], (n, d, 2 * hlp)),
                "b_offsets": jax.random.normal(k[3], (n, 2 * hlp)),
                "w_weights": lecun(k[4], (n, d, hlp)),
                "b_weights": jnp.zeros((n, hlp), jnp.float32)}

    return jax.jit(build)(jax.random.fold_in(jax.random.PRNGKey(seed), 1))


def _inputs(seed: int, cfg: dict, tr: dict):
    import jax

    d, _, _, _, S = _sizes(cfg)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 2)
    xs = jax.jit(lambda k: jax.random.normal(
        k, (tr["distinct_batches"], tr["batch"], S, d)) * tr["feature_std"])(key)
    return [xs[i] for i in range(tr["distinct_batches"])]


def _centres(levels):
    out = []
    for h, w in levels:
        gy, gx = np.meshgrid((np.arange(h) + 0.5) / h, (np.arange(w) + 0.5) / w,
                             indexing="ij")
        out.append(np.stack([gx, gy], -1).reshape(h * w, 2))
    return np.concatenate(out).astype(np.float32)


def infer_cell(ctx: dict) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core import msda as msda_mod

    cfg, tr, mcfg = ctx["cfg"], ctx["traffic"], ctx["mcfg"]
    d, H, _, _, S = _sizes(cfg)
    params = _weights(ctx["seed"], cfg)
    xs = _inputs(ctx["seed"], cfg, tr)
    refs = jnp.broadcast_to(jnp.asarray(_centres(cfg["levels"])),
                            (tr["batch"], S, 2))

    def forward(p, x):
        for i in range(cfg["layers"]):
            lp = {k: v[i] for k, v in p.items()}
            x = x + msda_mod.msda_attention(lp, mcfg, x, x, refs)
        return x

    forward = ctx["hooks"].get("forward_fn", lambda f: f)(forward)
    t0 = time.perf_counter()
    compiled = jax.jit(forward).lower(params, xs[0]).compile()
    harness.log(f"forward compiled in {time.perf_counter() - t0:.3f}s")
    plans = {"msda": msda_mod.attention_plan(
        mcfg, num_queries=S, head_dim=d // H, dtype=xs[0].dtype)}
    harness.report_program(compiled, plans)
    ctx["plans"] = plans
    jax.block_until_ready([compiled(params, x) for x in xs])

    answers = {}

    def call(i):
        k = i % len(xs)
        answers[k] = compiled(params, xs[k])
        return answers[k]

    ctx["setup_s"] = harness.setup_s()
    run = ctx["window"](call, 0)
    ctx["memory_peak_bytes"] = harness.memory_peak(ctx["devices"])
    got = {k: np.asarray(v, np.float32) for k, v in answers.items()}
    bad = sum(1 for v in got.values() if not np.isfinite(v).all())
    del answers, compiled
    gc.collect()
    return {
        "images": run["calls"] * tr["batch"], "window_s": run["window_s"],
        "calls": run["calls"], "failed": bad * tr["batch"],
        "program": {"answers": got},
        "inputs": {"params": params, "xs": xs,
                   "answered": checks.checked_sample(
                       ctx["seed"], sorted(got), tr["checked_batches"])},
    }


CELLS = {"infer": infer_cell}


def msda_calls(cfg: dict, mode: str, plans: dict):
    return [work.MsdaCalls(plans["msda"], cfg["layers"])]


def flops_per_image(cfg: dict, mode: str) -> float:
    d, H, L, P, S = _sizes(cfg)
    hlp = H * L * P
    layer = (2 * S * d * d * 2 + 2 * S * d * hlp * 3
             + work.FWD_FLOPS_PER_CHANNEL * S * hlp * (d // H))
    return float(cfg["layers"] * layer)


# --------------------------------------------------------------------------
# the plain reference, in float32
# --------------------------------------------------------------------------


def _ref_msda(p, levels, H, P, x, centres):
    import jax
    import jax.numpy as jnp

    B, S, d = x.shape
    L, D = len(levels), d // H
    mm = lambda a, b: jnp.matmul(a, b, precision=HIGHEST)  # noqa: E731
    value = mm(x, p["value_proj"]).reshape(B, S, H, D)
    off = (mm(x, p["w_offsets"]) + p["b_offsets"]).reshape(B, S, H, L, P, 2)
    aw = jax.nn.softmax((mm(x, p["w_weights"]) + p["b_weights"]).reshape(
        B, S, H, L * P), axis=-1).reshape(B, S, H, L, P)
    bi = jnp.arange(B)[:, None, None, None]
    hi = jnp.arange(H)[None, None, :, None]
    out, start = 0.0, 0
    for l, (h, w) in enumerate(levels):
        v = value[:, start:start + h * w]
        start += h * w
        px = centres[None, :, None, None, 0] + off[:, :, :, l, :, 0] / w
        py = centres[None, :, None, None, 1] + off[:, :, :, l, :, 1] / h
        px, py = px * w - 0.5, py * h - 0.5
        x0, y0 = jnp.floor(px), jnp.floor(py)
        lx, ly = px - x0, py - y0
        for dx, dy, wt in ((0, 0, (1 - lx) * (1 - ly)), (1, 0, lx * (1 - ly)),
                           (0, 1, (1 - lx) * ly), (1, 1, lx * ly)):
            xi = x0.astype(jnp.int32) + dx
            yi = y0.astype(jnp.int32) + dy
            inside = (xi >= 0) & (xi < w) & (yi >= 0) & (yi < h)
            rows = v[bi, jnp.clip(yi, 0, h - 1) * w + jnp.clip(xi, 0, w - 1), hi]
            cw = wt * inside * aw[:, :, :, l]
            out = out + jnp.sum(rows * cw[..., None], axis=3)
    return mm(out.reshape(B, S, d), p["out_proj"])


def _ref_forward(params, cfg, x):
    centres = _centres(cfg["levels"])
    levels = [tuple(l) for l in cfg["levels"]]
    for i in range(cfg["layers"]):
        lp = {k: v[i] for k, v in params.items()}
        x = x + _ref_msda(lp, levels, cfg["num_heads"], cfg["num_points"],
                          x, centres)
    return x


def compare(mode, cfg, traffic, prog, inputs, hooks=None):
    import jax

    fwd = jax.jit(lambda p, x: _ref_forward(p, cfg, x))
    ref = {"answers": {k: np.asarray(fwd(inputs["params"], inputs["xs"][k]),
                                     np.float32)
                       for k in inputs["answered"]}}
    if hooks and "on_reference" in hooks:
        hooks["on_reference"](ref, inputs)
    gap = float("inf") if not ref["answers"] else 0.0
    for k, want in ref["answers"].items():
        if k not in prog["answers"]:
            gap = float("inf")
            break
        rms = float(np.sqrt(np.mean(np.square(want))))
        gap = max(gap, float(np.max(np.abs(prog["answers"][k] - want))) / rms)
    numbers = {"out_gap": gap}
    return numbers, numbers
