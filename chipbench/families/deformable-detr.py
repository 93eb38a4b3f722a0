"""The Deformable-DETR family: a configuration file with ``"family":
"deformable-detr"`` runs the program's DETR training step or forward
pass, from weights and detection batches made from the seed
(``chipbench/weights.py``, ``chipbench/generate.py``), and is checked
against the plain float32 Deformable-DETR (``chipbench/reference.py``).

The comparison that decides `correct`.

Training cells: the reference runs the same checked steps from the same
weights on the same batches.  Compared:

* ``first_loss_rel_gap``: the relative gap of the first step's loss;
* ``grad_median_leaf_gap``: the median over leaves of the gap between the
  norms of the first step's gradient as the optimiser gets it, before
  its global-norm clipping (the program's worked out from its
  first-moment state after one step and the pre-clip norm its step
  reports), each measured against the reference's norm of that leaf or
  of the median leaf, whichever is larger;
* ``change_leaf_gap``: by the worst leaf, the same for each leaf's change
  over the checked steps, leaving out leaves whose reference gradient is
  under a thousandth of the median leaf's (they move by round-off);
* ``class_bias_grad_gap``: the norm of the difference between the
  program's and the reference's first gradient of the class head's bias,
  over the larger of the reference's norm of that leaf and of the median
  leaf.  That gradient is the loss's gradient with respect to the class
  logits of the first forward pass, summed over queries and images: it
  reads the whole forward pass, and lower-precision rounding of it, which
  the norms above average away.  Queries whose matching costs tie are
  alike, so it hardly moves when the matching picks the other one.

The greedy matching of the loss makes the rest unsteady: at
initialisation the queries' costs for a target lie within rounding of
each other, so a bf16 program picks another query on some seeds, and
every later step and the worst leaf's gradient move with it.  Those
readings (``loss_rel_gap`` over the checked steps, ``grad_leaf_gap`` by
the worst leaf) are printed beside the compared ones, not compared.

Inference cells: the window's answers (a sample, drawn from the seed, of
the distinct batches it served) against the reference's forward pass:

* ``logit_gap``: the widest gap of a class logit, over the reference
  logits' root mean square;
* ``box_gap``: the widest gap of a box coordinate (boxes lie in [0, 1]).
"""
from __future__ import annotations

import gc
import inspect
import math
import statistics
import time
from typing import Any, Dict

import numpy as np

from chipbench import checks, generate, harness, reference, weights, work
from chipbench.harness import BenchError, log

CLASS_BIAS = ("class_head", "b")
CLASS_BIAS_KEY = "".join(f"[{k!r}]" for k in CLASS_BIAS)


# --------------------------------------------------------------------------
# the program under test
# --------------------------------------------------------------------------


def program_config(cfg: dict):
    """The program's ModelConfig for a configuration file: the registered
    configuration with every size the file states."""
    import dataclasses

    from repro.configs.base import get_config

    if cfg["decoder_layers"] != cfg["encoder_layers"]:
        raise BenchError("the program builds as many decoder layers as "
                         "encoder layers")
    if cfg["num_queries"] != 300:
        raise BenchError("the program fixes 300 object queries")
    base = get_config(cfg["registered"])
    msda = dataclasses.replace(
        base.msda, levels=tuple(tuple(l) for l in cfg["levels"]),
        num_points=cfg["num_points"], num_heads=cfg["num_heads"])
    return dataclasses.replace(
        base, d_model=cfg["d_model"], num_heads=cfg["num_heads"],
        num_kv_heads=cfg["num_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["d_ff"], num_layers=cfg["encoder_layers"],
        vocab_size=cfg["num_classes"], act=cfg["act"],
        norm_eps=cfg["norm_eps"], dtype=cfg["dtype"], msda=msda)


def check_layout(params, mcfg) -> None:
    """The benchmark's weights must have the program's parameter layout."""
    import jax

    from repro.core import deformable_transformer as dt

    want = jax.eval_shape(lambda k: dt.init_detr(k, mcfg),
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda x: (x.shape, str(x.dtype)), params)
    want = jax.tree.map(lambda x: (x.shape, str(x.dtype)), want)
    if got != want:
        raise BenchError("benchmark weights do not match the program's "
                         "parameter layout")


def committed_plans(mcfg, train: bool, dtype: str) -> Dict[str, Any]:
    """The MSDA plans the compiled entry committed: fetching them again
    must hit the plan cache."""
    from repro.core import deformable_transformer as dt
    from repro.kernels import plan as plan_mod

    misses = plan_mod.plan_cache_info()["misses"]
    plans = dt.msda_plans(mcfg, dtype=dtype, train=train)
    if plan_mod.plan_cache_info()["misses"] != misses:
        raise BenchError("the reported MSDA plans are not the ones the "
                         "compiled entry committed")
    return plans


def msda_calls(cfg: dict, mode: str, plans: Dict[str, Any]):
    """One MSDA call per layer, encoder and decoder; the encoder's
    forward runs twice per call in training (recomputed under remat)."""
    return [work.MsdaCalls(plans["encoder"], cfg["encoder_layers"],
                           2 if mode == "train" else 1),
            work.MsdaCalls(plans["decoder"], cfg["decoder_layers"])]


# --------------------------------------------------------------------------
# the cells
# --------------------------------------------------------------------------


def train_cell(ctx: dict) -> dict:
    """The jitted, donated training step as the training launcher builds
    it (no mesh), compiled ahead of time; the first ``checked_steps``
    steps through the window's own call, then the window."""
    import jax
    import jax.numpy as jnp

    from repro.optim import adamw
    from repro.train import loop as train_loop
    from repro.train.state import TrainState

    cfg, tr, mcfg = ctx["cfg"], ctx["traffic"], ctx["mcfg"]
    params = weights.make(ctx["seed"], cfg)
    check_layout(params, mcfg)
    params0 = jax.tree.map(jnp.copy, params)
    batches = generate.make(ctx["seed"], cfg, tr)
    state = TrainState(params=params, opt=adamw.init_adamw(params),
                       step=jnp.zeros((), jnp.int32))
    step = train_loop.make_train_step(
        mcfg, num_microbatches=1, peak_lr=tr["peak_lr"],
        warmup_steps=tr["warmup_steps"], total_steps=tr["total_steps"],
        weight_decay=tr["weight_decay"], clip_norm=tr["clip_norm"])
    step = ctx["hooks"].get("train_step_fn", lambda f: f)(step)
    t0 = time.perf_counter()
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        state, batches[0]).compile()
    log(f"train step lowered and compiled (or loaded) in "
        f"{time.perf_counter() - t0:.3f}s")
    plans = committed_plans(mcfg, True, str(batches[0]["pyramid"].dtype))
    harness.report_program(compiled, plans)
    ctx["plans"] = plans

    # the step leaves AdamW's first-moment decay at the optimiser's default
    b1 = inspect.signature(adamw.adamw_update).parameters["b1"].default
    n_checked = tr["checked_steps"]
    losses, first_grads, class_bias_grad = [], None, None
    for i in range(n_checked):
        state, m = compiled(state, batches[i])
        losses.append(m["loss"])
        if i == 0:
            # m after one step is (1 - b1) times the clipped gradient
            clip = min(1.0, tr["clip_norm"] / max(float(m["grad_norm"]), 1e-9))
            first_grads = {k: v / (1 - b1) / clip
                           for k, v in checks.leaf_norms(state.opt.m).items()}
            cb = state.opt.m[CLASS_BIAS[0]][CLASS_BIAS[1]]
            class_bias_grad = np.asarray(cb, np.float32) / (1 - b1) / clip
    jax.block_until_ready(state)
    changes = checks.change_norms(state.params, params0)
    losses = [float(x) for x in losses]

    box = {"state": state}
    window_losses = []

    def call(i):
        box["state"], m = compiled(box["state"], batches[i % len(batches)])
        window_losses.append(m["loss"])
        return m["loss"]

    ctx["setup_s"] = harness.setup_s()
    run = ctx["window"](call, n_checked)
    bad = sum(1 for l in window_losses if not math.isfinite(float(l)))
    ctx["memory_peak_bytes"] = harness.memory_peak(ctx["devices"])
    del box, state, compiled
    gc.collect()
    return {
        "images": run["calls"] * tr["batch"], "window_s": run["window_s"],
        "calls": run["calls"], "failed": bad * tr["batch"],
        "program": {"losses": losses, "first_grads": first_grads,
                    "class_bias_grad": class_bias_grad, "changes": changes},
        "inputs": {"params0": params0, "batches": batches[:n_checked]},
    }


def infer_cell(ctx: dict) -> dict:
    """The jitted forward pass (encoder then decoder, ``train=False``),
    compiled ahead of time, warmed on every distinct batch, then the
    window; the window's answers for each batch are kept for the check."""
    import jax

    from repro.core import deformable_transformer as dt

    cfg, tr, mcfg = ctx["cfg"], ctx["traffic"], ctx["mcfg"]
    params = weights.make(ctx["seed"], cfg, served=True)
    check_layout(jax.tree.map(lambda x: x.astype("float32"), params), mcfg)
    batches = generate.make(ctx["seed"], cfg, tr)
    pyrs = [b["pyramid"] for b in batches]

    def forward(p, x):
        memory = dt.encode_pyramid(p, mcfg, x, train=False, remat=False)
        return dt.decode_queries(p, mcfg, memory, train=False)

    forward = ctx["hooks"].get("forward_fn", lambda f: f)(forward)
    t0 = time.perf_counter()
    compiled = jax.jit(forward).lower(params, pyrs[0]).compile()
    log(f"forward lowered and compiled (or loaded) in "
        f"{time.perf_counter() - t0:.3f}s")
    plans = committed_plans(mcfg, False, str(pyrs[0].dtype))
    harness.report_program(compiled, plans)
    ctx["plans"] = plans
    jax.block_until_ready([compiled(params, x) for x in pyrs])

    answers: Dict[int, Any] = {}

    def call(i):
        k = i % len(pyrs)
        answers[k] = compiled(params, pyrs[k])
        return answers[k]

    ctx["setup_s"] = harness.setup_s()
    run = ctx["window"](call, 0)
    ctx["memory_peak_bytes"] = harness.memory_peak(ctx["devices"])
    got = {k: (np.asarray(v[0], np.float32), np.asarray(v[1], np.float32))
           for k, v in answers.items()}
    bad = sum(1 for lg, bx in got.values()
              if not (np.isfinite(lg).all() and np.isfinite(bx).all()))
    del answers, compiled
    gc.collect()
    return {
        "images": run["calls"] * tr["batch"], "window_s": run["window_s"],
        "calls": run["calls"], "failed": bad * tr["batch"],
        "program": {"answers": got},
        "inputs": {"params": params, "batches": batches,
                   "answered": checks.checked_sample(
                       ctx["seed"], sorted(got), tr["checked_batches"])},
    }


CELLS = {"train": train_cell, "infer": infer_cell}


# --------------------------------------------------------------------------
# the work counts
# --------------------------------------------------------------------------


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
                + work.FWD_FLOPS_PER_CHANNEL * q_tokens * hlp * hd  # interpolation
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


# --------------------------------------------------------------------------
# the check
# --------------------------------------------------------------------------


def ref_block_q(batch: int) -> int:
    return max(512, 8192 // batch)


def reference_train(cfg, traffic, inputs, pr=reference.FLOAT32) -> dict:
    """The reference's readings of the checked steps."""
    losses, g, params = reference.train_steps(
        inputs["params0"], cfg, inputs["batches"], traffic, pr,
        ref_block_q(traffic["batch"]))
    return {"losses": [float(x) for x in losses],
            "first_grads": checks.leaf_norms(g),
            "class_bias_grad": np.asarray(g[CLASS_BIAS[0]][CLASS_BIAS[1]],
                                          np.float32),
            "changes": checks.change_norms(params, inputs["params0"])}


def train_readings(prog: dict, ref: dict) -> Dict[str, float]:
    """The compared numbers, and beside them the median leaf's gaps and
    the worst leaves, for the record of a calibration."""
    if len(prog["losses"]) != len(ref["losses"]):
        loss_gap = float("inf")
    else:
        loss_gap = max(abs(p - r) / abs(r) for p, r in
                       zip(prog["losses"], ref["losses"]))
    g = checks.leaf_gaps(prog["first_grads"], ref["first_grads"],
                         ref["first_grads"])
    c = checks.leaf_gaps(prog["changes"], ref["changes"],
                         checks.moving_leaves(ref["first_grads"]))
    med = statistics.median(ref["first_grads"].values())
    cb = float(np.linalg.norm(prog["class_bias_grad"] - ref["class_bias_grad"])
               / max(ref["first_grads"][CLASS_BIAS_KEY], med, 1e-30))
    return {
        "class_bias_grad_gap": cb,
        "loss_rel_gap": loss_gap,
        "first_loss_rel_gap": abs(prog["losses"][0] - ref["losses"][0])
        / abs(ref["losses"][0]),
        "grad_leaf_gap": max(g.values()),
        "change_leaf_gap": max(c.values()),
        "grad_median_leaf_gap": statistics.median(g.values()),
        "change_median_leaf_gap": statistics.median(c.values()),
        "grad_worst_leaf": max(g, key=g.get),
        "change_worst_leaf": max(c, key=c.get),
    }


TRAIN_COMPARED = ("first_loss_rel_gap", "grad_median_leaf_gap", "change_leaf_gap",
                  "class_bias_grad_gap")


def train_numbers(prog: dict, ref: dict) -> Dict[str, float]:
    r = train_readings(prog, ref)
    return {k: r[k] for k in TRAIN_COMPARED}


def reference_infer(cfg, traffic, inputs, pr=reference.FLOAT32) -> dict:
    import jax

    params = jax.tree.map(lambda x: x.astype("float32"), inputs["params"])
    fwd = reference.jitted(reference.forward_fn, cfg, {}, pr,
                           ref_block_q(traffic["batch"]))
    out = {}
    for k in inputs["answered"]:
        lg, bx = fwd(params, inputs["batches"][k]["pyramid"])
        out[k] = (np.asarray(lg, np.float32), np.asarray(bx, np.float32))
    return {"answers": out}


def infer_numbers(prog: dict, ref: dict) -> Dict[str, float]:
    lg_gap = bx_gap = 0.0
    want = ref["answers"]
    got = prog["answers"]
    if not want or not set(want) <= set(got):
        return {"logit_gap": float("inf"), "box_gap": float("inf")}
    for k, (rl, rb) in want.items():
        pl, pb = got[k]
        rms = float(np.sqrt(np.mean(np.square(rl))))
        lg_gap = max(lg_gap, float(np.max(np.abs(pl - rl))) / rms)
        bx_gap = max(bx_gap, float(np.max(np.abs(pb - rb))))
    return {"logit_gap": lg_gap, "box_gap": bx_gap}


REFERENCE = {"train": reference_train, "infer": reference_infer}
NUMBERS = {"train": train_numbers, "infer": infer_numbers}
READINGS = {"train": train_readings, "infer": infer_numbers}


def compare(mode, cfg, traffic, prog, inputs, hooks=None):
    """(numbers, every reading beside them)."""
    ref = REFERENCE[mode](cfg, traffic, inputs)
    if hooks and "on_reference" in hooks:
        hooks["on_reference"](ref, inputs)
    return NUMBERS[mode](prog, ref), READINGS[mode](prog, ref)


def control(mode, cfg, traffic, inputs, ref) -> Dict[str, float]:
    """The control's readings against the reference ``ref``: the
    reference computed in float8 (e4m3), the step below the
    configuration's bfloat16, in the program's place."""
    low = REFERENCE[mode](cfg, traffic, inputs, reference.FLOAT8)
    return READINGS[mode](low, ref)
