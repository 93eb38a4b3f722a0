#!/usr/bin/env python3
"""Chip smoke: the paper-width Deformable-DETR training step on a TPU.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # query-sharded step on a 2x2 mesh

One chip: trains ``deformable-detr`` at its registered full width (5-level
pyramid 256x256..16x16, 87,296 encoder queries, d=256, 8 heads, 4 points,
6+6 layers, bf16) for a few steps through the training launcher
(``repro.launch.train.run``) with random weights and synthetic detection
batches made from ``--seed``.  The MSDA kernels run compiled by Mosaic:
every committed plan must be ``backend=pallas`` with ``interpret=False``
and the compiled step must hold ``tpu_custom_call``.  Then it checks the
Pallas kernels against the ``ref`` oracle on the chip, fwd and VJP, within
the bf16 tolerances of ``tests/conformance.py``: the full pyramid over a
query subset, the full 300-query decoder spec, and an inference plan's
regathering backward on a small pyramid.

``--four-chips`` runs only the sharded path: the same step on a 2x2 mesh
(MSDA queries sharded over all four chips), compared loss by loss with a
one-chip run of the same seed and batch on device 0, in this process.

Everything runs in this one process, which holds the chip(s).  The last
line of standard output is ``{"ok": true, "device": {...}}``; any failure
prints no such line and exits non-zero — as does a host without a TPU.
Timings printed here are smoke timings, not measurements.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
ARCH = "deformable-detr"
# batch the paper-width train step fits on one v5e (16 GB HBM): the
# compiled step's memory_analysis() puts B=1 at ~10.4 GiB of temporaries
# (bf16 saved corners of one encoder layer alone are ~3.6 GB per image),
# so B=2 does not fit
BATCH = 1


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"[chip-smoke] {msg}", flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2-mesh step vs one chip")
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


class CacheEvents:
    """Counts JAX's persistent-compilation-cache hits and misses."""

    def __init__(self, monitoring):
        self.hits = self.misses = 0
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def train(seed: int, steps: int, mesh: str, cache: CacheEvents):
    """One in-process run of the training launcher; returns its result
    and the cache hits/misses its compiles saw."""
    from repro.launch import train as train_launch

    h0, m0 = cache.hits, cache.misses
    res = train_launch.run([
        "--arch", ARCH, "--steps", str(steps), "--batch", str(BATCH),
        "--seed", str(seed), "--mesh", mesh, "--lr", "1e-4"])
    return res, cache.hits - h0, cache.misses - m0


def check_train(res, steps: int):
    for name, plan in res["plans"].items():
        log(f"plan {name}:\n{plan.describe()}")
        check(plan.backend == "pallas",
              f"{name} plan runs backend {plan.backend!r}, not pallas")
        check(plan.tuning.interpret is False,
              f"{name} plan interprets its kernels")
    compiled = res["compiled"]
    check("tpu_custom_call" in compiled.as_text(),
          "compiled train step holds no tpu_custom_call (no Pallas kernel)")
    ma = compiled.memory_analysis()
    log(f"batch={BATCH} memory_analysis: "
        f"temp={ma.temp_size_in_bytes} argument={ma.argument_size_in_bytes} "
        f"output={ma.output_size_in_bytes} alias={ma.alias_size_in_bytes} "
        f"generated_code={ma.generated_code_size_in_bytes}")
    losses = [res["losses"][s] for s in sorted(res["losses"])]
    log(f"losses: {losses}")
    log(f"step seconds (smoke timings, not metrics): {res['step_seconds']}")
    check(len(losses) == steps, f"{len(losses)} losses for {steps} steps")
    check(all(math.isfinite(l) for l in losses), f"non-finite loss: {losses}")
    return losses


def _errors(got, want, tol):
    """(max abs error, within tolerance?) under allclose(atol=rtol=tol)."""
    import numpy as np

    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    err = np.abs(g - w)
    ok = bool(np.all(np.isfinite(g)) and np.all(err <= tol + tol * np.abs(w)))
    return float(err.max()), ok


def _operands(spec, seed: int, batch: int = 1):
    """Conformance-style operands: value N(0,1), sampling locations
    straddling the border, softmaxed attention weights."""
    import jax
    import jax.numpy as jnp

    L, P, H, D = spec.num_levels, spec.num_points, spec.num_heads, spec.head_dim
    Q, S = spec.num_queries, spec.total_pixels
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    value = jax.random.normal(ks[0], (batch, S, H, D), jnp.float32)
    loc = jax.random.uniform(ks[1], (batch, Q, H, L, P, 2),
                             minval=-0.2, maxval=1.2)
    attn = jax.nn.softmax(jax.random.normal(
        ks[2], (batch, Q, H, L * P)), axis=-1).reshape(batch, Q, H, L, P)
    return value, loc, attn


def against_ref(name: str, exec_fn, spec, seed: int, fwd_tol, vjp_tol):
    """Pallas executor vs the fp32 ``ref`` oracle: max abs errors of the
    forward and of each VJP output, held to the tolerances of the spec's
    operand dtype.

    The executor gets the operands in the dtypes the model feeds it
    (value and attention weights in ``spec.dtype``, locations fp32); the
    oracle gets the same values in fp32.  Both VJPs are pulled back
    through ONE cotangent, rounded to the executor's output dtype, so a
    bf16-rounded output cannot perturb what the gradients are checked
    on."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.ref import msda_ref

    value, loc, attn = _operands(spec, seed)
    levels = spec.spatial_shapes
    tier = str(jnp.dtype(spec.dtype))
    value, attn = value.astype(tier), attn.astype(tier)
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731

    @jax.jit
    def run(v, l, a, gout):
        out_k, vjp_k = jax.vjp(exec_fn, v, l, a)
        out_r, vjp_r = jax.vjp(
            lambda v, l, a: msda_ref(f32(v), levels, l, f32(a)), v, l, a)
        g = gout.astype(out_k.dtype)
        return out_k, out_r, vjp_k(g), vjp_r(f32(g).astype(out_r.dtype))

    gout = jax.random.normal(jax.random.PRNGKey(seed + 7),
                             (value.shape[0], loc.shape[1],
                              spec.num_heads * spec.head_dim), jnp.float32)
    out_k, out_r, gk, gr = run(value, loc, attn, gout)
    tol_f, tol_v = fwd_tol[tier], vjp_tol[tier]
    fwd_err, ok = _errors(out_k, out_r, tol_f)
    parts = [f"fwd max|err|={fwd_err:.3e} (tol {tol_f})"]
    for gname, a, b in zip(("value", "loc", "attn"), gk, gr):
        e, o = _errors(a, b, tol_v)
        ok &= o
        parts.append(f"grad_{gname} max|err|={e:.3e}")
    log(f"pallas vs ref [{name}] {tier} operands: " + "  ".join(parts)
        + f" (VJP tol {tol_v})")
    check(ok, f"pallas vs ref [{name}] outside the {tier} tolerances")


def conformance(plans, seed: int):
    """Pallas vs ref on the chip: the encoder's full pyramid over a query
    subset, the committed 300-query decoder plan, and the regathering
    backward of an inference plan.  The model's plans run on their
    committed dtypes (bf16
    operands and slabs, bf16 output and grads) and are held to the bf16
    tolerances of ``tests/conformance.py``."""
    sys.path.insert(0, os.path.join(REPO, "tests"))
    from conformance import FWD_TOL, VJP_TOL

    from repro.kernels.plan import msda_plan

    enc = plans["encoder"]
    sub = dataclasses.replace(enc.spec, num_queries=2048)
    against_ref("encoder pyramid, 2048-query subset",
                msda_plan(sub, backend="pallas", tune="heuristic"), sub,
                seed, FWD_TOL, VJP_TOL)
    dec = plans["decoder"]
    against_ref("decoder, 300 queries (committed plan)", dec, dec.spec,
                seed + 1, FWD_TOL, VJP_TOL)

    # an inference plan's backward regathers its corners from the slab;
    # a small pyramid, fp32 operands
    small = dataclasses.replace(
        enc.spec, spatial_shapes=((32, 32), (16, 16), (8, 8)),
        num_queries=256, dtype="float32", slab_dtype="", train=False)
    against_ref("regather (no saved corners)",
                msda_plan(small, backend="pallas", tune="heuristic"), small,
                seed + 2, FWD_TOL, VJP_TOL)


def one_chip(args, cache: CacheEvents) -> None:
    import jax

    res, hits, misses = train(args.seed, args.steps, "1", cache)
    log(f"train step compile {res['compile_seconds']:.1f}s "
        f"(persistent cache {res['cache_dir']}: {hits} hits, "
        f"{misses} misses{' -> cache hit' if hits and not misses else ''})")
    check_train(res, args.steps)
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    t0 = time.perf_counter()
    conformance(res["plans"], args.seed)
    log(f"conformance phase {time.perf_counter() - t0:.1f}s")


def four_chips(args, cache: CacheEvents) -> None:
    import jax

    check(len(jax.devices()) >= 4,
          f"--four-chips needs 4 devices, have {len(jax.devices())}")
    one, _, _ = train(args.seed, args.steps, "1", cache)
    l1 = check_train(one, args.steps)
    four, _, _ = train(args.seed, args.steps, "2x2", cache)
    l4 = check_train(four, args.steps)
    for name, plan in four["plans"].items():
        log(f"{name} sharding: {plan.sharding_report()}")
    diffs = [abs(a - b) for a, b in zip(l1, l4)]
    log(f"losses one chip {l1} vs 2x2 mesh {l4}: |diff| {diffs}")
    check(all(d <= 1e-3 * max(1.0, abs(a)) for d, a in zip(diffs, l1)),
          "2x2-mesh losses diverge from the one-chip run")
    for i, d in enumerate(jax.devices()[:4]):
        stats = d.memory_stats() or {}
        log(f"device {i} peak_bytes_in_use={stats.get('peak_bytes_in_use')}")


def main(argv=None) -> int:
    args = parse_args(argv)
    if not os.path.isdir(os.path.join(REPO, "src", "repro")):
        print("chip_smoke.py: no src/repro next to this script; run it from "
              "a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(REPO, "src"))
    try:
        import jax
        import jax.monitoring

        from repro.serving import persistence

        persistence.enable_jax_compilation_cache()
        cache = CacheEvents(jax.monitoring)
        dev = jax.devices()[0]
        if dev.platform != "tpu":
            raise SmokeFailure(f"JAX finds no TPU (platform {dev.platform!r})")
        log(f"device {dev.device_kind} x{len(jax.devices())}, "
            f"jax {jax.__version__}")
        (four_chips if args.four_chips else one_chip)(args, cache)
    except Exception as e:  # any failure: report, no result line
        traceback.print_exc()
        log(f"FAIL: {type(e).__name__}: {e}")
        return 1
    devs = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
