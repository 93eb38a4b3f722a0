"""Records the small chip trace that ``test_chipbench_trace.py`` reads.

    python3 tests/chipbench/record_trace.py OUT.xplane.pb

Run from the root of a checkout on a TPU host.  It drives a small MSDA
forward and VJP through the program's Pallas plan (two levels, 256
queries, 8 heads of 32, 4 points) under the benchmark's own window and
spans, three calls back to back, and writes the profiler's xplane.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp

    from chipbench.run import Window
    from repro.kernels.plan import MsdaSpec, msda_plan

    if jax.devices()[0].platform != "tpu":
        print("record_trace.py needs a TPU", file=sys.stderr)
        return 1
    spec = MsdaSpec(spatial_shapes=((32, 32), (16, 16)), num_heads=8,
                    head_dim=32, num_points=4, num_queries=256,
                    dtype="float32", train=True)
    plan = msda_plan(spec, backend="pallas", tune="heuristic")
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    S = 32 * 32 + 16 * 16
    value = jax.random.normal(k[0], (1, S, 8, 32))
    loc = jax.random.uniform(k[1], (1, 256, 8, 2, 4, 2))
    attn = jax.nn.softmax(jax.random.normal(k[2], (1, 256, 8, 8)), -1).reshape(
        1, 256, 8, 2, 4)

    @jax.jit
    def step(v, l, a):
        out, vjp = jax.vjp(plan, v, l, a)
        return vjp(jnp.ones_like(out))

    jax.block_until_ready(step(value, loc, attn))
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    Window(0.005).drive(lambda i: step(value, loc, attn), 0)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))[0]
    shutil.copy(src, out)
    shutil.rmtree(tmp)
    print(f"wrote {out} ({os.path.getsize(out)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
