"""The chip benchmark: one cell, one seed, one measured window.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the
cell asks for.  The cell (``BENCHMARK.json``) names a configuration and a
traffic mix; both are data files found by name (``chipbench/catalog.py``).

A run makes the weights and a few distinct batches on the device from
the seed, builds the program's entry for the cell (the jitted, donated
training step, or the jitted forward pass), compiles it once and warms
it, then drives it closed loop, back to back, for ``--seconds``.  Set-up
(imports, weights, batches, compile or cache load, warm-up and, for
training, the first steps that the check reads) is ``setup_s``.

``correct`` comes from a comparison with the plain float32 reference
(``chipbench/reference.py``), run once the window has closed and the
program's state is freed, against the limits of
``chipbench/limits/<workload>.json``.  Every number compared is printed
beside its limit, as the last lines on standard error and under the
result's last key, ``checks``.

The last line of standard output is the result.  A host without the TPU
chips the cell asks for exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import shutil
import sys
import time
from typing import Any, Callable, Dict, List, Optional

T_START = time.perf_counter()

from chipbench import (  # noqa: E402
    catalog, checks, generate, peaks, trace, weights, work)



class BenchError(Exception):
    pass


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: a "
                         "directory in the checkout, removed once read)")
    return ap.parse_args(argv)


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


def report_program(compiled, plans) -> None:
    """Earlier output lines: plans, memory analysis, Pallas launches."""
    for name, plan in plans.items():
        log(f"plan {name}:\n{plan.describe()}")
    ma = compiled.memory_analysis()
    mem = {k: int(getattr(ma, k)) for k in (
        "temp_size_in_bytes", "argument_size_in_bytes", "output_size_in_bytes",
        "alias_size_in_bytes", "generated_code_size_in_bytes")}
    log(f"memory_analysis: {json.dumps(mem)}")
    text = compiled.as_text()
    n_custom = text.count('custom_call_target="tpu_custom_call"')
    log(f"Pallas launch sites in the compiled step (tpu_custom_call ops in "
        f"its HLO, each inside the layer loop where it runs): {n_custom}")
    for name, plan in plans.items():
        log(f"plan {name} launches per call: {plan.launches_per_call()}")


# --------------------------------------------------------------------------
# the cells
# --------------------------------------------------------------------------


class Window:
    """Closed loop, back to back: dispatch the next call, then wait for
    the one before it, so the device always has the next call queued."""

    def __init__(self, seconds: float):
        self.seconds = seconds

    def drive(self, call: Callable[[int], Any], start: int) -> Dict[str, Any]:
        import jax

        done, pending, i = 0, None, start
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench.window"):
            while True:
                with jax.profiler.TraceAnnotation("chipbench.dispatch"):
                    out = call(i)
                i += 1
                if pending is not None:
                    with jax.profiler.TraceAnnotation("chipbench.wait"):
                        jax.block_until_ready(pending)
                    done += 1
                pending = out
                if time.perf_counter() - t0 >= self.seconds:
                    break
            with jax.profiler.TraceAnnotation("chipbench.wait"):
                jax.block_until_ready(pending)
            done += 1
        return {"calls": done, "window_s": time.perf_counter() - t0}


def train_cell(ctx: dict) -> dict:
    """The jitted, donated training step as the training launcher builds
    it (no mesh), compiled ahead of time; the first ``checked_steps``
    steps through the window's own call, then the window."""
    import jax
    import jax.numpy as jnp

    import inspect

    import numpy as np

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
    report_program(compiled, plans)
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
            cb = state.opt.m[checks.CLASS_BIAS[0]][checks.CLASS_BIAS[1]]
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

    ctx["setup_s"] = time.perf_counter() - T_START
    run = ctx["window"](call, n_checked)
    bad = sum(1 for l in window_losses if not math.isfinite(float(l)))
    ctx["memory_peak_bytes"] = memory_peak(ctx)
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
    report_program(compiled, plans)
    ctx["plans"] = plans
    jax.block_until_ready([compiled(params, x) for x in pyrs])

    answers: Dict[int, Any] = {}

    def call(i):
        k = i % len(pyrs)
        answers[k] = compiled(params, pyrs[k])
        return answers[k]

    ctx["setup_s"] = time.perf_counter() - T_START
    run = ctx["window"](call, 0)
    ctx["memory_peak_bytes"] = memory_peak(ctx)
    import numpy as np

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
                   "answered": checked_sample(ctx["seed"], sorted(got),
                                              tr["checked_batches"])},
    }


def checked_sample(seed: int, answered: List[int], k: int) -> List[int]:
    """The window's answers the check compares: ``k`` of the distinct
    batches it served, drawn from the seed."""
    import numpy as np

    rng = np.random.default_rng(seed)
    return sorted(rng.choice(answered, size=min(k, len(answered)),
                             replace=False).tolist())


CELLS = {"train": train_cell, "infer": infer_cell}


def memory_peak(ctx) -> int:
    vals = []
    for d in ctx["devices"]:
        stats = d.memory_stats() or {}
        vals.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(vals) if vals else 0


# --------------------------------------------------------------------------
# tracing and the per-layer metrics
# --------------------------------------------------------------------------


class TracedWindow(Window):
    """The window under the profiler; keeps the trace directory."""

    def __init__(self, seconds: float, trace_dir: str):
        super().__init__(seconds)
        self.trace_dir = trace_dir

    def drive(self, call, start):
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        jax.profiler.start_trace(self.trace_dir)
        try:
            return super().drive(call, start)
        finally:
            jax.profiler.stop_trace()


class TraceRun:
    """What a per-layer metric's reader sees."""

    def __init__(self, tr, cfg, traffic, plans, peak, images, calls,
                 window_s, lo, hi):
        self.trace, self.cfg, self.traffic = tr, cfg, traffic
        self.plans, self.peak = plans, peak
        self.images, self.calls, self.window_s = images, calls, window_s
        self.lo, self.hi = lo, hi
        self.trace_window_s = (hi - lo) * 1e-9
        self.busy_s = trace.device_busy_s(tr)
        self.mode = traffic["mode"]
        self.images_per_s = images / window_s

    def device_events(self):
        """Device ops of the first traced chip."""
        plane = trace.first_device(self.trace)
        return self.trace.device_ops[plane] if plane else []

    def msda_calls(self):
        """(spec, calls per step) of each MSDA plan the step runs: one
        call per layer, encoder and decoder."""
        n = self.cfg["encoder_layers"]
        return [(self.plans["encoder"].spec, n),
                (self.plans["decoder"].spec, self.cfg["decoder_layers"])]

    def msda_launches(self, direction: str) -> int:
        """Pallas kernel launches of one call in ``direction`` ('fwd' or
        'bwd'): each plan's launches per MSDA call times its layers, the
        encoder's forward twice in training (recomputed under remat)."""
        n = 0
        for name, layers in (("encoder", self.cfg["encoder_layers"]),
                             ("decoder", self.cfg["decoder_layers"])):
            runs = 2 if (self.mode == "train" and direction == "fwd"
                         and name == "encoder") else 1
            n += self.plans[name].launches_per_call()[direction] * layers * runs
        return n

    def msda_kernel_seconds(self, direction: str) -> float:
        """Device seconds of the MSDA kernel events of ``direction`` in
        the trace, told apart by their operands (``chipbench/trace.py``).
        Their count has to be what the committed plans launch in the
        traced calls: any other count means that events of another kernel
        are counted, or some of this one's are not, and the run fails."""
        operands = 3 if direction == "fwd" else 4
        seconds, n = trace.kernel_seconds(self.device_events(), operands)
        want = self.calls * self.msda_launches(direction)
        if n != want:
            raise BenchError(
                f"the trace holds {n} Pallas kernel events with {operands} "
                f"operands; the committed plans launch {want} MSDA {direction} "
                f"kernels in {self.calls} calls")
        return seconds

    def msda_work(self, direction: str):
        """(FLOPs, bytes) the algorithm needs for every MSDA call of
        the traced window in ``direction`` ('fwd' or 'bwd')."""
        fn = work.msda_fwd_work if direction == "fwd" else work.msda_bwd_work
        flops = nbytes = 0.0
        for spec, per_step in self.msda_calls():
            f, b = fn(spec.spatial_shapes, self.traffic["batch"],
                      spec.num_queries, spec.num_heads, spec.head_dim,
                      spec.num_points, spec.dtype)
            flops += f * per_step * self.calls
            nbytes += b * per_step * self.calls
        return flops, nbytes


def per_layer_metrics(ctx, res, trace_dir) -> dict:
    t = trace.load(trace.find_xplane(trace_dir))
    lo, hi = t.window()
    run = TraceRun(t, ctx["cfg"], ctx["traffic"], ctx["plans"], ctx["peak"],
                   res["images"], res["calls"], res["window_s"], lo, hi)
    out = {}
    for m in ctx["per_layer"]:
        val = catalog.metric_reader(m["name"], ctx["root"])(run)
        if val is not None:
            out[m["name"]] = {"value": val, "unit": m["unit"]}
    bd = trace.breakdown(t, lo, hi) if t.device_ops else None
    return {"metrics": out, "busy_s": run.busy_s,
            "window_s": run.trace_window_s, "breakdown": bd}


# --------------------------------------------------------------------------
# the run
# --------------------------------------------------------------------------


def find_devices(require_tpu: bool, chips: int):
    import jax

    devs = jax.devices()
    if require_tpu:
        if devs[0].platform != "tpu":
            raise BenchError(f"JAX finds no TPU (platform "
                             f"{devs[0].platform!r})")
        if len(devs) < chips:
            raise BenchError(f"the cell needs {chips} chips, JAX finds "
                             f"{len(devs)}")
    return devs[:chips]


def run(args, root: str = catalog.ROOT, require_tpu: bool = True,
        hooks: Optional[dict] = None) -> dict:
    bench = catalog.benchmark(root)
    cell = catalog.workload(bench, args.workload)
    cfg = catalog.config(bench, cell["config"], root)
    traffic = catalog.traffic(cell["traffic"], root)
    lim = catalog.limits(args.workload, root)
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise BenchError(f"the program (src/repro) is not in {root}")
    if src not in sys.path:
        sys.path.insert(0, src)

    from repro.serving import persistence

    cache = persistence.enable_jax_compilation_cache(
        os.path.join(root, ".jax_cache"))
    devs = find_devices(require_tpu, cell["chips"])
    kind = devs[0].device_kind
    peak = peaks.peaks(kind) if require_tpu else None
    log(f"workload {args.workload}: config {cell['config']}, traffic "
        f"{cell['traffic']}, device {kind} x{len(devs)}, compile cache {cache}")

    mode = traffic["mode"]
    trace_dir = None
    if args.trace:
        trace_dir = args.trace_dir or os.path.join(root, ".chipbench",
                                                   "trace", args.workload)
        window = TracedWindow(args.seconds, trace_dir)
    else:
        window = Window(args.seconds)
    compiles = CompileCounter()
    ctx = {"cfg": cfg, "traffic": traffic, "mcfg": program_config(cfg),
           "seed": args.seed, "hooks": hooks or {}, "devices": devs,
           "peak": peak, "root": root,
           "per_layer": catalog.per_layer(bench, args.workload),
           "window": compiles.around(window.drive)}
    res = CELLS[mode](ctx)
    log(f"window: {res['calls']} calls, {res['images']} images in "
        f"{res['window_s']:.6f}s; set-up {ctx['setup_s']:.6f}s; "
        f"compiles inside the window: {compiles.inside}")
    if compiles.inside:
        raise BenchError(f"{compiles.inside} compiles inside the window")

    t0 = time.perf_counter()
    numbers, readings = checks.compare(mode, cfg, traffic, res["program"],
                                       res["inputs"], hooks=ctx["hooks"])
    log(f"reference and comparison took {time.perf_counter() - t0:.3f}s")
    log(f"readings: {json.dumps(readings)}")
    verdict = checks.judge(numbers, lim)

    device = {"platform": devs[0].platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": ctx["memory_peak_bytes"]}
    result: Dict[str, Any] = {
        "correct": verdict["correct"], "attempted": res["images"],
        "failed": res["failed"],
    }
    if args.trace:
        pl = per_layer_metrics(ctx, res, trace_dir)
        if not args.trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
        result["metrics"] = pl["metrics"]
        device.update(busy_s=pl["busy_s"], window_s=pl["window_s"])
        result["device"] = device
        if pl["breakdown"] is not None:
            result["breakdown"] = pl["breakdown"]
    else:
        values = {"images_per_s": res["images"] / res["window_s"],
                  "setup_s": ctx["setup_s"]}
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in catalog.end_to_end(bench, args.workload)}
        result["device"] = device
    result["checks"] = verdict["checks"]
    return result


class CompileCounter:
    """Counts backend compiles that start inside the measured window."""

    def __init__(self):
        import jax.monitoring

        self.inside = 0
        self._on = False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, *_a, **_k):
        if self._on and name.endswith("backend_compile_duration"):
            self.inside += 1

    def around(self, drive):
        def wrapped(call, start):
            self._on = True
            try:
                return drive(call, start)
            finally:
                self._on = False
        return wrapped


def main(argv=None, *, root: str = catalog.ROOT, require_tpu: bool = True,
         hooks: Optional[dict] = None) -> int:
    args = parse_args(argv)
    try:
        result = run(args, root, require_tpu, hooks)
    except Exception as e:  # any failure: say why, print no result
        import traceback

        traceback.print_exc()
        print(f"[chipbench] FAIL: {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)
        return 1
    for name, c in result["checks"].items():
        print(f"[chipbench] check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
