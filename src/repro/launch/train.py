"""Training driver: mesh setup, elastic plans, fault-tolerant loop.

CPU-scale usage (reduced config, real optimization):
    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --smoke \
        --steps 50 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt

The loop itself lives in :class:`repro.training.TrainingHarness`:
checkpointed restart, deterministic fault injection (``--faults
host_loss@20,corrupt_ckpt@35`` or a seeded ``--fault-seed`` schedule),
and step-time telemetry (``--bench-out BENCH_train.json``).  ``--mesh
DPxTP`` + ``--plan-store`` restore MSDA plans elastically: a store
written on a different topology re-races only the mesh-keyed autotune
axes and persists the new winners (``repro.training.elastic``).

``--train-smoke`` is the CI entry point: a short DETR run under the
4-virtual-device host that injects one mid-step preemption, kills and
resumes the loop, asserts bitwise loss continuity + elastic re-race
behaviour, and writes ``BENCH_train.json`` at the repo root.
"""
from __future__ import annotations

import argparse
import os
import tempfile
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import get_config, reduced
from repro.data.pipeline import DataConfig, Pipeline
from repro.launch.mesh import make_mesh_2d, parse_mesh_shape
from repro.serving import persistence
from repro.sharding import rules
from repro.train import loop as train_loop
from repro.train import state as train_state
from repro.training import (
    FaultSchedule, HarnessConfig, StepTimeRecorder, TrainingHarness,
    recover_plans)


def _mesh_from_arg(token: str):
    shape = parse_mesh_shape(token)
    return None if shape is None else make_mesh_2d(*shape)


def _data_config(cfg, args) -> DataConfig:
    if cfg.family == "vision":
        return DataConfig(
            global_batch=args.batch, seq_len=args.seq,
            vocab_size=cfg.vocab_size, seed=args.seed, source="detection",
            levels=tuple(cfg.msda.levels), feat_dim=cfg.d_model)
    return DataConfig(
        global_batch=args.batch, seq_len=args.seq, vocab_size=cfg.vocab_size,
        seed=args.seed, source=args.data, path=args.data_path)


def _tokens_per_step(cfg, args) -> int:
    if cfg.family == "vision":
        # detection: encoder pixel-queries processed per step
        return args.batch * sum(h * w for h, w in cfg.msda.levels)
    return args.batch * args.seq


def _warm_plans(cfg, mesh, recorder, plan_store: str) -> Dict[str, Any]:
    """Commit MSDA plans before the first trace; elastic via the store."""
    if cfg.msda is None:
        return {}
    from repro.core import deformable_transformer as dt
    from repro.kernels import plan as plan_mod
    from repro.serving.persistence import PlanStore

    if plan_store:
        rep = recover_plans(plan_store, mesh=mesh)
        for line in rep.reraced:
            print(f"[train] elastic re-race: {line}")
            recorder.record_event("replan", step=0, latency_s=rep.recovery_s,
                                  detail=line)
        for line in rep.skipped:
            print(f"[train] plan store skipped: {line}")
    plans = dt.msda_plans(cfg, dtype=cfg.dtype, train=True, mesh=mesh)
    for name, plan in plans.items():
        print(f"[train] msda plan ({name}):\n{plan.describe()}")
    if plan_store:
        n = PlanStore(plan_store).save_plans(
            list(plans.values()),
            meta={"writer": "launch.train",
                  "mesh": None if mesh is None else plan_mod.mesh_token(mesh)})
        print(f"[train] plan store: persisted {n} plans -> {plan_store}")
    return plans


def _build_harness(cfg, args, mesh, recorder, faults=None,
                   ckpt_dir=None, total_steps=None) -> TrainingHarness:
    """The training loop over ``cfg``.  With a ``mesh`` the step is
    traced under it (the MSDA plans shard their launches over it), the
    state is replicated and the batch split over the data axis."""
    pipe = Pipeline(_data_config(cfg, args))
    steps = total_steps if total_steps is not None else args.steps
    step = train_loop.make_train_step(
        cfg, num_microbatches=args.microbatches, peak_lr=args.lr,
        warmup_steps=max(steps // 10, 1), total_steps=steps,
    )
    put_state = put_batch = lambda t: t  # noqa: E731
    jit_kw = {}
    if mesh is not None:
        replicated = NamedSharding(mesh, P())
        # a batch the data axis does not divide is replicated instead
        by_data = (NamedSharding(mesh, P("data"))
                   if args.batch % dict(mesh.shape)["data"] == 0
                   else replicated)
        put_state = lambda t: jax.device_put(t, replicated)  # noqa: E731
        put_batch = lambda t: jax.device_put(t, by_data)  # noqa: E731
        # the loop feeds each step's state to the next: pin it replicated
        shapes = jax.eval_shape(lambda: train_state.init_state(
            jax.random.PRNGKey(args.seed), cfg))
        jit_kw["out_shardings"] = (
            jax.tree.map(lambda _: replicated, shapes), replicated)
    jitted = jax.jit(step, donate_argnums=(0,), **jit_kw)

    def step_fn(state, batch):
        return _traced(mesh, jitted, state, batch)

    step_fn.lower = lambda *a: _traced(mesh, jitted.lower, *a)

    def batch_fn(step: int):
        return put_batch(
            {k: jnp.asarray(v) for k, v in pipe.batch(step).items()})

    def init_fn():
        return put_state(
            train_state.init_state(jax.random.PRNGKey(args.seed), cfg))

    hcfg = HarnessConfig(
        total_steps=steps, ckpt_every=args.ckpt_every,
        ckpt_dir=ckpt_dir if ckpt_dir is not None else args.ckpt_dir,
        keep_last=args.keep_last, max_restarts=args.max_restarts)
    return TrainingHarness(step_fn=step_fn, batch_fn=batch_fn,
                           init_fn=init_fn, config=hcfg, faults=faults,
                           telemetry=recorder)


def _traced(mesh, fn, *args):
    if mesh is None:
        return fn(*args)
    with rules.use_mesh(mesh):
        return fn(*args)


def compile_step(harness: TrainingHarness):
    """AOT-compile the harness's step for its state and first batch and
    install the executable: ``(compiled, seconds)``.  The compiled
    program is what a caller inspects (``memory_analysis()``,
    ``as_text()``); the loop then runs it without a second compile."""
    state = harness.init_fn()
    batch = harness.batch_fn(0)
    t0 = time.perf_counter()
    compiled = harness.step_fn.lower(state, batch).compile()
    seconds = time.perf_counter() - t0
    harness.step_fn = compiled
    return compiled, seconds


def _parse_faults(args) -> "FaultSchedule | None":
    if args.faults:
        return FaultSchedule.from_spec(args.faults)
    if args.fault_seed is not None:
        return FaultSchedule.generate(args.fault_seed, args.steps,
                                      n_faults=args.fault_count)
    return None


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deformable-detr")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--keep-last", type=int, default=3)
    ap.add_argument("--max-restarts", type=int, default=8)
    ap.add_argument("--data", default="synthetic")
    ap.add_argument("--data-path", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default="1", help="'1' (no mesh) or DPxTP")
    ap.add_argument("--plan-store", default=None,
                    help="elastic MSDA plan store (restored + persisted)")
    ap.add_argument("--faults", default=None,
                    help="deterministic schedule, e.g. 'host_loss@20,preempt@35'")
    ap.add_argument("--fault-seed", type=int, default=None,
                    help="seeded random fault schedule")
    ap.add_argument("--fault-count", type=int, default=2)
    ap.add_argument("--bench-out", default=None,
                    help="write BENCH_train.json telemetry here")
    ap.add_argument("--train-smoke", action="store_true",
                    help="self-asserting CI smoke (see module docstring)")
    ap.add_argument("--trace-out", default=None,
                    help="stream obs spans (plan builds, autotune races, "
                         "recoveries, per-step timings) to this JSONL file")
    ap.add_argument("--trace-level", type=int, default=3,
                    help="span verbosity exported to --trace-out (1-4; "
                         "4 adds per-step spans)")
    ap.add_argument("--metrics-out", default=None,
                    help="dump the obs metrics registry at exit "
                         "(.json -> JSON, else Prometheus text)")
    return ap.parse_args(argv)


def run(argv=None) -> Dict[str, Any]:
    """The training driver, in-process: parse ``argv``, train, return
    what happened — the config, the committed MSDA plans, the compiled
    step (with its compile seconds), per-step seconds and losses."""
    args = parse_args(argv)
    from repro import obs

    cache_dir = persistence.enable_jax_compilation_cache()
    print(f"[train] compilation cache: {cache_dir}")
    if args.trace_out:
        obs.enable_trace(args.trace_out, level=args.trace_level)

    def _export() -> None:
        if args.metrics_out:
            print(f"[train] metrics -> {obs.write_metrics(args.metrics_out)}")
        if args.trace_out:
            obs.disable_trace()
            print(f"[train] trace -> {args.trace_out}")

    if args.train_smoke:
        try:
            train_smoke(args)
        finally:
            _export()
        return {}

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
    mesh = _mesh_from_arg(args.mesh)
    recorder = StepTimeRecorder(
        tokens_per_step=_tokens_per_step(cfg, args),
        config={"arch": args.arch, "smoke": bool(args.smoke),
                "steps": args.steps, "batch": args.batch,
                "mesh": args.mesh, "seed": args.seed})
    plans = _warm_plans(cfg, mesh, recorder, args.plan_store)
    harness = _build_harness(cfg, args, mesh, recorder,
                             faults=_parse_faults(args))
    compiled, compile_s = compile_step(harness)
    print(f"[train] step compiled in {compile_s:.1f}s")
    t0 = time.time()
    out = harness.run()
    dt_s = time.time() - t0
    for rec in out["recovery_log"]:
        print(f"[train] recovered: {rec['kind']} at step {rec['failed_step']} "
              f"-> resumed from {rec['resumed_from']}"
              + (f" (skipped corrupt ckpts {rec['ckpt_skipped']})"
                 if rec["ckpt_skipped"] else ""))
    losses = out["losses"]
    if losses:
        first, last = min(losses), max(losses)
        if first > 0:
            print(f"[train] restored step {first}")
        print(f"[train] loss {losses[first]:.4f} -> {losses[last]:.4f} "
              f"over {out['final_step']} steps "
              f"({out['restarts']} restarts, {dt_s:.1f}s)")
    print(f"[train] done: {out['final_step']} steps")
    if args.bench_out:
        path = recorder.write(args.bench_out)
        print(f"[train] wrote telemetry -> {path}")
    _export()
    return {"cfg": cfg, "mesh": mesh, "plans": plans, "compiled": compiled,
            "compile_seconds": compile_s, "losses": losses,
            "step_seconds": [r["wall_s"] for r in recorder.steps],
            "final_step": out["final_step"], "restarts": out["restarts"],
            "cache_dir": cache_dir}


def main() -> None:
    run()


# --------------------------------------------------------------------------
# CI train-smoke: kill-and-resume + elastic re-race, self-asserting
# --------------------------------------------------------------------------


def train_smoke(args) -> None:
    """Short DETR run proving the whole recovery story on 4 CPU devices.

    Legs (each asserts; any failure exits non-zero for CI):
      1. reference run — uninterrupted, records the loss trajectory;
      2. faulted run — one injected mid-step preemption; must recover
         and reproduce the reference losses BITWISE;
      3. kill-and-resume — the loop is stopped at step k and a fresh
         harness (fresh process, simulated) resumes from the checkpoint;
         continuation losses must equal the reference bitwise;
      4. elastic re-race — an autotuned plan store built on a 2x2 mesh
         restores onto 1x4: only the mesh-keyed axes re-race
         (raced_local == 0), new winners persist, and a second 1x4
         restore does ZERO timing runs.
    Writes the faulted run's ``BENCH_train.json`` trajectory.
    """
    cfg = reduced(get_config("deformable-detr"))
    args.steps, args.batch, args.ckpt_every = 10, 4, 3
    args.keep_last, args.max_restarts, args.microbatches = 10, 4, 1
    args.lr, args.seed = 1e-3, 0
    work = tempfile.mkdtemp(prefix="train_smoke_")
    bench_out = args.bench_out or "BENCH_train.json"

    def run(ckpt_dir, faults=None, recorder=None, total=None):
        rec = recorder or StepTimeRecorder()
        h = _build_harness(cfg, args, None, rec, faults=faults,
                           ckpt_dir=ckpt_dir, total_steps=None)
        if total is not None:
            h.config.total_steps = total
        return h.run(), rec

    # leg 1: reference trajectory
    ref, _ = run(os.path.join(work, "ref"))
    assert ref["final_step"] == args.steps and ref["restarts"] == 0
    assert len(ref["losses"]) == args.steps
    print(f"[train-smoke] reference: {args.steps} steps, "
          f"loss {ref['losses'][0]:.4f} -> {ref['losses'][args.steps - 1]:.4f}")

    # leg 2: injected mid-step preemption -> recovery + bitwise continuity
    recorder = StepTimeRecorder(
        tokens_per_step=_tokens_per_step(cfg, args),
        config={"arch": "deformable-detr", "smoke": True,
                "steps": args.steps, "batch": args.batch,
                "faults": "preempt@7"})
    faults = FaultSchedule.from_spec("preempt@7")
    faulted, recorder = run(os.path.join(work, "faulted"), faults=faults,
                            recorder=recorder)
    assert faulted["restarts"] == 1, faulted["restarts"]
    assert faulted["recovery_log"][0]["kind"] == "preempt"
    assert faulted["recovery_log"][0]["resumed_from"] == 6  # ckpt_every=3
    for s, l in ref["losses"].items():
        assert faulted["losses"][s] == l, (
            f"loss diverged at step {s}: {faulted['losses'][s]} != {l}")
    print("[train-smoke] preemption recovered; losses bitwise-identical")

    # leg 3: kill the loop at step 5, resume in a fresh harness
    kill_dir = os.path.join(work, "killed")
    half, _ = run(kill_dir, total=5)
    assert half["final_step"] == 5
    resumed, _ = run(kill_dir)  # fresh harness object = simulated restart
    assert resumed["final_step"] == args.steps
    assert min(resumed["losses"]) == 5, "resume must start at the checkpoint"
    for s in range(5, args.steps):
        assert resumed["losses"][s] == ref["losses"][s], f"diverged at {s}"
    print("[train-smoke] kill-and-resume continued bitwise from step 5")

    # leg 4: elastic plan re-race (needs the 4-device CI host)
    if len(jax.devices()) >= 4:
        from repro.kernels import plan as plan_mod
        from repro.serving.persistence import PlanStore

        os.environ.setdefault(
            "REPRO_MSDA_AUTOTUNE_CACHE", os.path.join(work, "autotune.json"))
        store_path = os.path.join(work, "plans.json")
        spec = plan_mod.MsdaSpec(
            spatial_shapes=tuple(cfg.msda.levels), num_heads=cfg.msda.num_heads,
            head_dim=cfg.d_model // cfg.msda.num_heads,
            num_points=cfg.msda.num_points,
            num_queries=sum(h * w for h, w in cfg.msda.levels),
            dtype="float32", train=True, slab_dtype="auto")
        m22, m14 = make_mesh_2d(2, 2), make_mesh_2d(1, 4)
        plan = plan_mod.msda_plan(spec, backend="cpu", tune="autotune",
                                  mesh=m22, query_parallel=True)
        PlanStore(store_path).save_plans([plan], meta={"mesh": "data2xmodel2"})
        plan_mod.clear_plans()
        plan_mod.reset_autotune_stats()
        rep = recover_plans(store_path, mesh=m14)
        assert rep.replan_count == 1 and rep.persisted, (rep.replan_count,
                                                         rep.persisted)
        assert rep.raced_local == 0, f"local axes re-raced: {rep.raced_local}"
        recorder.record_event("replan", step=0, latency_s=rep.recovery_s,
                              detail=rep.reraced[0])
        plan_mod.clear_plans()
        plan_mod.reset_autotune_stats()
        rep2 = recover_plans(store_path, mesh=m14)
        assert rep2.replan_count == 0 and rep2.raced == 0, (
            rep2.replan_count, rep2.raced)
        print(f"[train-smoke] elastic: 2x2 -> 1x4 re-raced mesh axes only "
              f"({rep.raced_mesh} races), second restore zero races")
    else:
        print("[train-smoke] <4 devices: skipping the elastic leg")

    path = recorder.write(bench_out)
    print(f"[train-smoke] OK; wrote {path}")


if __name__ == "__main__":
    main()
