"""The ``deformable-detr`` family's traffic generator
(``chipbench/families/deformable-detr.py``): synthetic detection
batches.

It copies the semantics, dtypes and shapes of the program's
``detection`` data source: a float32 pyramid of N(0, std) features over
every pixel of every level, ``num_targets`` boxes with centres and sizes
uniform in [box_low, box_high], labels uniform in [1, num_classes), and
at each box centre of each level a bump of ``signature`` on the channel
``label % d_model`` (the pattern the encoder can learn to pool).

All ``distinct_batches`` batches are made on the device from the seed in
one jitted call; the run cycles through them.  The parameters live in a
traffic file (``chipbench/traffic/<name>.json``).
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp


def _make(key, cfg: dict, tr: dict) -> Dict[str, jax.Array]:
    n, B, T = tr["distinct_batches"], tr["batch"], tr["num_targets"]
    d, C = cfg["d_model"], cfg["num_classes"]
    S = sum(h * w for h, w in cfg["levels"])
    kb, kl, kp = jax.random.split(key, 3)
    boxes = jax.random.uniform(kb, (n, B, T, 4), jnp.float32,
                               tr["box_low"], tr["box_high"])
    labels = jax.random.randint(kl, (n, B, T), 1, C, jnp.int32)
    pyr = jax.random.normal(kp, (n, B, S, d), jnp.float32) * tr["feature_std"]
    sig = tr["signature"] * jax.nn.one_hot(labels % d, d, dtype=jnp.float32)
    ni = jnp.arange(n)[:, None, None]
    bi = jnp.arange(B)[None, :, None]
    offset = 0
    for h, w in cfg["levels"]:
        cx = jnp.clip(jnp.floor(boxes[..., 0] * w).astype(jnp.int32), 0, w - 1)
        cy = jnp.clip(jnp.floor(boxes[..., 1] * h).astype(jnp.int32), 0, h - 1)
        pyr = pyr.at[ni, bi, offset + cy * w + cx].add(sig)
        offset += h * w
    return {"pyramid": pyr, "labels": labels, "boxes": boxes}


def make(seed: int, cfg: dict, tr: dict) -> List[Dict[str, jax.Array]]:
    """The distinct batches for ``seed``, each a dict of device arrays."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 2)
    allb = jax.jit(lambda k: _make(k, cfg, tr))(key)
    return [{k: v[i] for k, v in allb.items()}
            for i in range(tr["distinct_batches"])]
