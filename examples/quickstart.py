"""Quickstart: plan once, execute many — the xMSDA plan/execute API.

The paper's lesson is that MSDA gets fast when the static problem
geometry is exploited *ahead of time*.  The API mirrors that:

1. describe the problem once (``MsdaSpec``),
2. build a plan (``msda_plan`` — backend registry + block planning +
   VJP wiring, all committed here),
3. execute the plan per batch (``plan(value, loc, attn)``).

    PYTHONPATH=src python examples/quickstart.py
"""
import time

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.kernels.plan import MsdaSpec, msda_plan, plan_cache_info
from repro.kernels.ref import msda_grid_sample_baseline, msda_ref

# a small multi-scale feature pyramid: 3 levels, 2 heads x 16 dims
levels = ((32, 32), (16, 16), (8, 8))
B, Q, H, D, P = 2, 500, 2, 16, 4
S = sum(h * w for h, w in levels)

key = jax.random.PRNGKey(0)
kv, kl, ka = jax.random.split(key, 3)
value = jax.random.normal(kv, (B, S, H, D))                      # (B, S, H, D)
loc = jax.random.uniform(kl, (B, Q, H, len(levels), P, 2))       # in [0, 1]
attn = jax.nn.softmax(
    jax.random.normal(ka, (B, Q, H, len(levels), P)).reshape(B, Q, H, -1)
).reshape(B, Q, H, len(levels), P)

# 1-2) spec + plan: every hardware-aware decision happens HERE, once.
spec = MsdaSpec(spatial_shapes=levels, num_heads=H, head_dim=D,
                num_points=P, num_queries=Q, dtype="float32")
plan = msda_plan(spec, backend="pallas")   # or "ref", "auto", tune="autotune"
print(plan.describe())                     # per-level block_q / slabs / VMEM
print("registered backends:", registry.list_backends())

# 3) execute — same MMCV conventions as the one-shot op
out_pal = plan(value, loc, attn)
out_ref = msda_ref(value, levels, loc, attn)                    # fused oracle
out_base = msda_grid_sample_baseline(value, levels, loc, attn)  # paper "Baseline"
print("baseline vs ref  max err:", float(jnp.abs(out_base - out_ref).max()))
print("pallas   vs ref  max err:", float(jnp.abs(out_pal - out_ref).max()))

# plans are cached by spec: an identical spec returns the SAME object and
# never re-runs block planning (serving processes call clear_plans())
assert msda_plan(spec, backend="pallas") is plan
print("plan cache:", plan_cache_info())

# it differentiates (custom VJP wired at plan time; train=True saves the
# gathered corners for a gather-free backward phase 1)
train_plan = msda_plan(MsdaSpec(spatial_shapes=levels, num_heads=H, head_dim=D,
                                num_points=P, num_queries=Q, dtype="float32",
                                train=True), backend="pallas")
grads = jax.grad(
    lambda v, l, a: jnp.sum(train_plan(v, l, a) ** 2), argnums=(0, 1, 2)
)(value, loc, attn)
print("grad shapes:", [g.shape for g in grads])

# the adaptive block plan (paper Fig. 7): one block for the launch over
# the packed pyramid; a bigger pyramid leaves room for a smaller block
print("block plan:", plan.block_q)

# -- the dtype-policy knob: mixed precision is a PLANNED axis -------------
# bf16 slabs halve VMEM residency (the planner widens block_q for it);
# accumulation stays fp32 inside the kernels, so error doesn't grow with
# Q.  slab_dtype="auto" + tune="autotune" races fp32 vs bf16 per level
# and persists the winner per device kind.  Model configs expose this as
# MSDAConfig.dtype_policy ('follow' | 'float32' | 'bfloat16' | 'auto').
bf16_plan = msda_plan(MsdaSpec(spatial_shapes=levels, num_heads=H, head_dim=D,
                               num_points=P, num_queries=Q, dtype="float32",
                               slab_dtype="bfloat16"), backend="pallas")
print(bf16_plan.describe())  # note the slab_dt column + accum=float32
err = jnp.abs(bf16_plan(value, loc, attn) - out_ref).max()
print("bf16-slab vs fp32 ref max err:", float(err), "(bf16 tolerance tier)")

# -- the backend matrix ---------------------------------------------------
# every registered backend executes the same plan contract; "auto" picks
# pallas on TPU and the vectorised "cpu" backend elsewhere (padded-slab
# per-corner gathers, head-major layout — faster forward than the "ref"
# oracle; backward is scatter-bound for both, so train is parity).
# tests/conformance.py checks fwd+VJP parity for every (backend, policy).
for name in registry.list_backends():
    p = msda_plan(spec, backend=name)
    e = jnp.abs(p(value, loc, attn) - out_ref).max()
    print(f"backend {name:8s} gather={p.level_report()[0]['gather']:<11s} "
          f"max err vs ref: {float(e):.2e}")

# -- warm-start serving: persist plans + AOT-compile across restarts -----
# A serving process saves its warmed plans (specs + autotune winners) to
# a versioned store; a RESTARTED process rebuilds the identical plan set
# with zero autotune timing runs, then AOT-compiles the executors at
# boot (jit(...).lower().compile()) so the first request never traces.
# serving.persistence.enable_jax_compilation_cache(dir) additionally
# makes those boot compiles disk hits on a restart.
import os
import tempfile

from repro.serving import aot
from repro.serving.persistence import PlanStore

store = PlanStore(os.path.join(tempfile.mkdtemp(), "plans.json"))
store.save_plans([plan, train_plan])
# --- imagine a process restart here ---
report = store.restore()          # seeds winners, rebuilds plans, 0 races
warm_plan = report.plans[0]
assert warm_plan.describe() == plan.describe()
executor = aot.compile_plan_executor(warm_plan, batch_size=B)  # boot-time
with aot.probe() as probe:
    out_warm = executor(value, loc, attn)                      # request-time
print(f"warm-start: {len(report.plans)} plans restored, "
      f"request-time traces={probe.traces} (AOT), "
      f"max err vs ref: {float(jnp.abs(out_warm - out_ref).max()):.2e}")

# CPU timing: fused vs materialising baseline
f_ref = jax.jit(lambda v, l, a: msda_ref(v, levels, l, a))
f_base = jax.jit(lambda v, l, a: msda_grid_sample_baseline(v, levels, l, a))
for name, f in (("fused", f_ref), ("baseline", f_base)):
    jax.block_until_ready(f(value, loc, attn))
    t = time.perf_counter()
    for _ in range(20):
        jax.block_until_ready(f(value, loc, attn))
    print(f"{name:9s}: {(time.perf_counter() - t) / 20 * 1e3:.2f} ms/call")
