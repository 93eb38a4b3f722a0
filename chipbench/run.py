"""The chip benchmark: one cell, one seed, one measured window.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the
cell asks for.  The cell (``BENCHMARK.json``) names a configuration and a
traffic mix; both are data files found by name (``chipbench/catalog.py``).
The configuration names its model family, a module found by name too
(``chipbench/families/<family>.py``), which owns all this harness does
not know of a model.

The family's entry for the traffic's mode makes the weights and inputs
on the device from the seed, builds the program's compiled entry (for
Deformable-DETR, the jitted, donated training step or the jitted forward
pass), warms it, then drives this harness's window: closed loop, back to
back, for ``--seconds``.  Set-up (imports, weights, inputs, compile or
cache load, warm-up and, for training, the first steps that the check
reads) is ``setup_s``.

``correct`` comes from the family's comparison with its plain float32
reference, run once the window has closed and the program's state is
freed, against the limits of ``chipbench/limits/<workload>.json``.
Every number compared is printed beside its limit, as the last lines on
standard error and under the result's last key, ``checks``.

The last line of standard output is the result.  A host without the TPU
chips the cell asks for exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from typing import Any, Callable, Dict, Optional

# first: set-up (``setup_s``) runs from the harness's import
from chipbench.harness import BenchError, log
from chipbench import catalog, checks, peaks, trace, work


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
# the window
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
    """What a per-layer metric's reader sees: the parsed trace of the
    window, the cell, and what the family says of the traced step (its
    MSDA calls, ``msda``, a list of ``work.MsdaCalls``, and its model
    FLOPs per image).  ``trace_dir`` is where the profile lies."""

    def __init__(self, tr, cfg, traffic, msda, peak, images, calls,
                 window_s, lo, hi, flops_per_image=None, trace_dir=None):
        self.trace, self.cfg, self.traffic = tr, cfg, traffic
        self.msda, self.peak = msda, peak
        self.images, self.calls, self.window_s = images, calls, window_s
        self.lo, self.hi = lo, hi
        self.flops_per_image, self.trace_dir = flops_per_image, trace_dir
        self.trace_window_s = (hi - lo) * 1e-9
        self.busy_s = trace.device_busy_s(tr)
        self.mode = traffic["mode"]
        self.images_per_s = images / window_s

    def device_events(self):
        """Device ops of the first traced chip."""
        plane = trace.first_device(self.trace)
        return self.trace.device_ops[plane] if plane else []

    def msda_launches(self, direction: str) -> int:
        """Pallas kernel launches of one step in ``direction`` ('fwd' or
        'bwd'): each plan's launches per MSDA call times its calls per
        step, times the runs of its forward per call."""
        return sum(c.plan.launches_per_call()[direction] * c.per_step
                   * (c.fwd_runs if direction == "fwd" else 1)
                   for c in self.msda)

    def msda_work(self, direction: str):
        """(FLOPs, bytes) the algorithm needs for every MSDA call of
        the traced window in ``direction`` ('fwd' or 'bwd')."""
        fn = work.msda_fwd_work if direction == "fwd" else work.msda_bwd_work
        flops = nbytes = 0.0
        for c in self.msda:
            spec = c.plan.spec
            f, b = fn(spec.spatial_shapes, self.traffic["batch"],
                      spec.num_queries, spec.num_heads, spec.head_dim,
                      spec.num_points, spec.dtype)
            flops += f * c.per_step * self.calls
            nbytes += b * c.per_step * self.calls
        return flops, nbytes


def per_layer_metrics(ctx, res, trace_dir) -> dict:
    t = trace.load(trace.find_xplane(trace_dir))
    lo, hi = t.window()
    cfg, fam, mode = ctx["cfg"], ctx["family"], ctx["traffic"]["mode"]
    run = TraceRun(t, cfg, ctx["traffic"],
                   fam.msda_calls(cfg, mode, ctx["plans"]), ctx["peak"],
                   res["images"], res["calls"], res["window_s"], lo, hi,
                   flops_per_image=fam.flops_per_image(cfg, mode),
                   trace_dir=trace_dir)
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
    fam = catalog.family(cfg, root)
    mode = traffic["mode"]
    if mode not in fam.CELLS:
        raise BenchError(f"family {cfg['family']!r} has no {mode!r} cell")

    from repro.serving import persistence

    cache = persistence.enable_jax_compilation_cache(
        os.path.join(root, ".jax_cache"))
    devs = find_devices(require_tpu, cell["chips"])
    kind = devs[0].device_kind
    peak = peaks.peaks(kind) if require_tpu else None
    log(f"workload {args.workload}: config {cell['config']}, traffic "
        f"{cell['traffic']}, device {kind} x{len(devs)}, compile cache {cache}")

    trace_dir = None
    if args.trace:
        trace_dir = args.trace_dir or os.path.join(root, ".chipbench",
                                                   "trace", args.workload)
        window = TracedWindow(args.seconds, trace_dir)
    else:
        window = Window(args.seconds)
    compiles = CompileCounter()
    ctx = {"cfg": cfg, "traffic": traffic, "family": fam,
           "mcfg": fam.program_config(cfg), "seed": args.seed,
           "hooks": hooks or {}, "devices": devs, "peak": peak, "root": root,
           "per_layer": catalog.per_layer(bench, args.workload),
           "window": compiles.around(window.drive)}
    res = fam.CELLS[mode](ctx)
    log(f"window: {res['calls']} calls, {res['images']} images in "
        f"{res['window_s']:.6f}s; set-up {ctx['setup_s']:.6f}s; "
        f"compiles inside the window: {compiles.inside}")
    if compiles.inside:
        raise BenchError(f"{compiles.inside} compiles inside the window")

    t0 = time.perf_counter()
    numbers, readings = fam.compare(mode, cfg, traffic, res["program"],
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
