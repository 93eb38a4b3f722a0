"""Faults planted in the timed path, to show that `correct` catches them.

Each wraps the ``deformable-detr`` family's training step or forward
pass before it is compiled (the run's ``train_step_fn`` / ``forward_fn``
hooks, which ``chipbench/families/deformable-detr.py`` applies).  The
tests drive whole runs with them at a size a CPU holds; the calibration
reads them on the chip at a cell's own size.  The cells run on one chip,
so an exchange between chips left out does not arise.
"""
from __future__ import annotations

import jax.numpy as jnp


def _half(batch):
    return {k: v[: v.shape[0] // 2] for k, v in batch.items()}


def state_unchanged(step):
    """The step returns its state as it came."""
    def f(state, batch):
        return state, step(state, batch)[1]
    return f


def half_batch_train(step):
    """Half of the batch left out; the loss is the mean over the rest."""
    return lambda state, batch: step(state, _half(batch))


def loss_altered(step):
    """The step's loss, its answer, is 5% off where it is produced."""
    def f(state, batch):
        new, m = step(state, batch)
        return new, {**m, "loss": m["loss"] * 1.05}
    return f


def half_batch_infer(forward):
    """Half of the batch left out; its answers are the other half's."""
    def f(params, x):
        lg, bx = forward(params, x[: x.shape[0] // 2])
        return jnp.concatenate([lg, lg]), jnp.concatenate([bx, bx])
    return f


def box_altered(forward):
    """One box coordinate of one answer is 0.1 off where it is produced."""
    def f(params, x):
        lg, bx = forward(params, x)
        return lg, bx.at[0, 0, 0].add(0.1)
    return f


TRAIN = {"state_unchanged": state_unchanged,
         "half_batch": half_batch_train, "loss_altered": loss_altered}
INFER = {"half_batch": half_batch_infer, "box_altered": box_altered}


def hooks(mode: str, name: str) -> dict:
    """The run hooks that plant fault ``name`` in a ``mode`` cell."""
    if mode == "train":
        return {"train_step_fn": TRAIN[name]}
    return {"forward_fn": INFER[name]}
