"""What every family's comparison shares: the verdict and the generic
readings it is made of.

A family (``chipbench/families/<family>.py``) compares what its timed
path produced with its own plain reference and hands back its numbers;
``judge`` holds each against the cell's limit
(``chipbench/limits/<workload>.json``).  The rest are readings that any
family with a training step or a window of answers can use: leaf norms
of a parameter tree and of its change, their gaps against the
reference's, and the sample of answers a check compares.
"""
from __future__ import annotations

import statistics
from typing import Dict, List

import numpy as np

QUIET_LEAF = 1e-3


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


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float], keys) -> Dict[str, float]:
    """Per leaf, the gap of the norms over the reference's norm of that
    leaf or of the median leaf, whichever is larger."""
    keys = list(keys)
    med = statistics.median(ref[k] for k in keys)
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in keys}


def moving_leaves(ref_grads: Dict[str, float]) -> List[str]:
    """Leaves whose reference gradient is at least a thousandth of the
    median leaf's; the others move by round-off alone."""
    med = statistics.median(ref_grads.values())
    return [k for k, v in ref_grads.items() if v >= QUIET_LEAF * med]


def checked_sample(seed: int, answered: List[int], k: int) -> List[int]:
    """The window's answers the check compares: ``k`` of the distinct
    batches it served, drawn from the seed."""
    rng = np.random.default_rng(seed)
    return sorted(rng.choice(answered, size=min(k, len(answered)),
                             replace=False).tolist())


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
