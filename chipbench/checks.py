"""The comparison that decides `correct`.

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

import statistics
from typing import Dict, List

import numpy as np

from chipbench import reference

QUIET_LEAF = 1e-3
CLASS_BIAS = ("class_head", "b")
CLASS_BIAS_KEY = "".join(f"[{k!r}]" for k in CLASS_BIAS)


def leaf_norms(tree) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in xs])([x for _, x in flat])
    return {jax.tree_util.keystr(p): float(n) for (p, _), n in zip(flat, norms)}


def change_norms(new, old) -> Dict[str, float]:
    import jax

    return leaf_norms(jax.tree.map(lambda a, b: a.astype("float32") - b,
                                   new, old))


def ref_block_q(batch: int) -> int:
    return max(512, 8192 // batch)


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float], keys) -> Dict[str, float]:
    """Per leaf, the gap of the norms over the reference's norm of that
    leaf or of the median leaf, whichever is larger."""
    keys = list(keys)
    med = statistics.median(ref[k] for k in keys)
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in keys}


def worst_leaf(prog: Dict[str, float], ref: Dict[str, float], keys) -> float:
    return max(leaf_gaps(prog, ref, keys).values())


def moving_leaves(ref_grads: Dict[str, float]) -> List[str]:
    med = statistics.median(ref_grads.values())
    return [k for k, v in ref_grads.items() if v >= QUIET_LEAF * med]


def reference_train(cfg, traffic, inputs, pr=reference.FLOAT32) -> dict:
    """The reference's readings of the checked steps."""
    losses, g, params = reference.train_steps(
        inputs["params0"], cfg, inputs["batches"], traffic, pr,
        ref_block_q(traffic["batch"]))
    return {"losses": [float(x) for x in losses], "first_grads": leaf_norms(g),
            "class_bias_grad": np.asarray(g[CLASS_BIAS[0]][CLASS_BIAS[1]],
                                          np.float32),
            "changes": change_norms(params, inputs["params0"])}


def train_readings(prog: dict, ref: dict) -> Dict[str, float]:
    """The compared numbers, and beside them the median leaf's gaps and
    the worst leaves, for the record of a calibration."""
    if len(prog["losses"]) != len(ref["losses"]):
        loss_gap = float("inf")
    else:
        loss_gap = max(abs(p - r) / abs(r) for p, r in
                       zip(prog["losses"], ref["losses"]))
    g = leaf_gaps(prog["first_grads"], ref["first_grads"], ref["first_grads"])
    c = leaf_gaps(prog["changes"], ref["changes"],
                  moving_leaves(ref["first_grads"]))
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


def train_numbers(prog: dict, ref: dict) -> Dict[str, float]:
    r = train_readings(prog, ref)
    return {k: r[k] for k in TRAIN_COMPARED}


TRAIN_COMPARED = ("first_loss_rel_gap", "grad_median_leaf_gap", "change_leaf_gap",
                  "class_bias_grad_gap")


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


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> dict:
    """Every number against its limit; a missing limit or a number that
    is not finite fails."""
    out, ok = {}, True
    for name, val in numbers.items():
        lim = limits.get(name)
        good = lim is not None and np.isfinite(val) and val <= lim
        ok &= bool(good)
        out[name] = {"value": val, "limit": lim}
    return {"correct": ok, "checks": out}
