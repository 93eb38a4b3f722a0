"""Training step factory: microbatched grad accumulation + AdamW.

``make_train_step(cfg, ...)`` returns a pure jittable
``(state, batch) -> (state, metrics)``:

* the global batch is split into ``num_microbatches`` slices scanned
  with accumulated grads (bounds activation memory; with scanned layers
  + remat this is what makes the 32B-130B train cells fit);
* **mixed precision**: fp32 master params are cast to the config's
  compute dtype ONCE before the microbatch loop, so FSDP all-gathers
  move bf16, not fp32 (§Perf iteration 2: halves gather wire bytes);
* **sharded accumulation**: the fp32 grad accumulator carries the
  parameter PartitionSpecs, so per-microbatch grads are reduce-scattered
  into shards instead of living as full all-reduced tensors (§Perf
  iteration 2: ~2x collective-term win on MoE cells);
* AdamW with warmup-cosine LR, global-norm clip, decoupled decay — all
  operating on the sharded fp32 master state.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.obs import scopes
from repro.optim import adamw, schedule
from repro.train.state import TrainState, loss_fn


def _split_micro(batch: Dict[str, jax.Array], n: int):
    def f(x):
        B = x.shape[0]
        assert B % n == 0, (B, n)
        return x.reshape(n, B // n, *x.shape[1:])

    return jax.tree.map(f, batch)


def make_train_step(
    cfg,
    *,
    num_microbatches: int = 1,
    peak_lr: float = 3e-4,
    warmup_steps: int = 100,
    total_steps: int = 10000,
    weight_decay: float = 0.1,
    clip_norm: float = 1.0,
    remat: bool = True,
    param_specs: Optional[Any] = None,  # PartitionSpec tree (sharded accum)
) -> Callable[[TrainState, Dict[str, jax.Array]], Tuple[TrainState, Dict[str, jax.Array]]]:
    lf = loss_fn(cfg)
    compute_dtype = jnp.dtype(cfg.dtype)

    def cast_param(p):
        if p.ndim >= 2 and p.dtype == jnp.float32 and compute_dtype != jnp.float32:
            return p.astype(compute_dtype)
        return p

    def constrain(tree):
        if param_specs is None:
            return tree
        return jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(x, s), tree, param_specs
        )

    def train_step(state: TrainState, batch: Dict[str, jax.Array]):
        # the optimizer scope holds the mixed-precision copies too: the
        # compute-dtype weights and the fp32 gradients
        with jax.named_scope(scopes.OPTIMIZER):
            params_c = constrain(jax.tree.map(cast_param, state.params))

        def micro_loss(p, mb):
            return lf(p, mb, remat=remat)

        if num_microbatches > 1:
            micro = _split_micro(batch, num_microbatches)

            def one_micro(carry, mb):
                gacc, lacc = carry
                loss, grads = jax.value_and_grad(micro_loss)(params_c, mb)
                # grads arrive in compute dtype, already reduce-scattered by
                # the FSDP backward; accumulate into the sharded fp32 buffer
                with jax.named_scope(scopes.OPTIMIZER):
                    gacc = constrain(
                        jax.tree.map(lambda a, g: a + g.astype(jnp.float32), gacc, grads)
                    )
                with jax.named_scope(scopes.LOSS):
                    lacc = lacc + loss
                return (gacc, lacc), None

            with jax.named_scope(scopes.OPTIMIZER):
                zeros = constrain(
                    jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
                )
            (gsum, lsum), _ = jax.lax.scan(one_micro, (zeros, jnp.float32(0.0)), micro)
            with jax.named_scope(scopes.OPTIMIZER):
                grads = jax.tree.map(lambda g: g / num_microbatches, gsum)
            with jax.named_scope(scopes.LOSS):
                loss = lsum / num_microbatches
        else:
            loss, grads = jax.value_and_grad(micro_loss)(params_c, batch)
            with jax.named_scope(scopes.OPTIMIZER):
                grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)

        with jax.named_scope(scopes.OPTIMIZER):
            lr = schedule.warmup_cosine(
                state.step, peak_lr=peak_lr, warmup_steps=warmup_steps,
                total_steps=total_steps
            )
            new_params, new_opt, gnorm = adamw.adamw_update(
                grads, state.opt, state.params,
                lr=lr, weight_decay=weight_decay, clip_norm=clip_norm,
            )
            new_state = TrainState(params=new_params, opt=new_opt, step=state.step + 1)
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        return new_state, metrics

    return train_step
