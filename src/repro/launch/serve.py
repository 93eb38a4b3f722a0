"""Serving driver: continuous-batching engine on a reduced config.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
        --prompts "hello world" "the quick brown fox"

Serving-runtime extras:

    # persistent warm boot: plan store + XLA compilation cache; AOT
    # warm-up for the prompt lengths the fleet expects
    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
        --store /tmp/plans.json --compile-cache /tmp/xla-cache --warm-lengths 4 8

    # CI smoke: boot the (vlm) engine against one store path; run twice
    # with the same paths and the SECOND boot must perform zero autotune
    # timing runs, zero request-time retraces and zero new XLA cache
    # entries — the process exits non-zero otherwise.
    PYTHONPATH=src python -m repro.launch.serve --serving-smoke \
        --store /tmp/store/plans.json --compile-cache /tmp/store/xla-cache

    # CI chaos smoke: clean boot proves zero resilience overhead, then a
    # second boot under a SEEDED fault schedule (executor raises,
    # straggler ticks, boot-time store corruption) must give every
    # admitted request a typed response and drive the circuit breaker
    # through a full demote -> half-open -> close cycle.  --bench-out
    # writes the event counts BENCH_resilience.json gates.
    PYTHONPATH=src python -m repro.launch.serve --chaos-smoke \
        --store /tmp/chaos/plans.json --fault-seed 7 \
        --bench-out /tmp/chaos/BENCH_resilience.json
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.configs.base import get_config, reduced
from repro.data import tokenizer
from repro.serving.engine import Request, ServeEngine
from repro.train import state as train_state


def serving_smoke(arch: str, store_path: str, compile_cache_dir: str,
                  *, slots: int = 2, capacity: int = 64) -> dict:
    """One serving boot against a persistent store; self-asserting.

    Cold boot (no store yet): warms + autotunes the bucket plans, saves
    the store, AOT-compiles the executors (all persisted to the XLA
    compilation cache), serves a few pyramid requests.  Warm boot (store
    exists): restores the plan set — the assertions then REQUIRE zero
    autotune timing runs, zero describe drift, zero request-time
    retraces, and zero new XLA cache entries (every boot compile was a
    disk hit).  The CI serving-smoke job runs this twice.
    """
    from repro.kernels import plan as plan_mod
    from repro.serving import aot, persistence

    # enable the compilation cache BEFORE any compile (params init
    # included) so both boots persist/hit the same entry set; counted in
    # the directory in effect (JAX_COMPILATION_CACHE_DIR wins)
    cache_dir = persistence.enable_jax_compilation_cache(compile_cache_dir)
    warm = persistence.PlanStore(store_path).exists()
    cache0 = persistence.compilation_cache_entries(cache_dir)
    plan_mod.reset_autotune_stats()
    aot.reset_stats()

    cfg = reduced(get_config(arch))
    params = train_state.init_model(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, slots=slots, capacity=capacity,
                      store_path=store_path, compile_cache_dir=cache_dir,
                      dtype_policy="auto", tune="autotune")
    eng.warmup(prompt_lengths=(4,))
    boot_tune = plan_mod.autotune_stats()

    vc = cfg.vision
    half = tuple((max(1, h // 2), max(1, w // 2)) for h, w in vc.levels)
    odd = tuple((max(1, h - 2), max(1, w - 3)) for h, w in vc.levels)
    rng = np.random.default_rng(0)
    reqs = []
    for i, lv in enumerate((vc.levels, odd, half, half)):
        S = sum(h * w for h, w in lv)
        reqs.append(Request(
            rid=i, prompt=np.arange(4, dtype=np.int32) + i, max_new=4,
            pyramid=rng.standard_normal((S, vc.vision_dim)).astype(np.float32),
            levels=lv))
    with aot.probe() as probe:
        for r in reqs:
            eng.submit(r)
        eng.run()

    rr = eng.restore_report
    summary = {
        "arch": cfg.name,
        "warm_boot": warm,
        "plans": len(eng.plans),
        "restored_plans": len(rr.plans) if rr else 0,
        "seeded_winners": rr.seeded_winners if rr else 0,
        "describe_mismatches": rr.describe_mismatches if rr else [],
        "boot_autotune": boot_tune,
        "request_traces": probe.traces,
        "request_compiles": probe.compiles,
        "new_xla_cache_entries":
            persistence.compilation_cache_entries(cache_dir) - cache0,
        "completed": [len(r.out) for r in reqs],
        # aot probe counters ride inside the metrics dict so zero-retrace
        # is auditable from the uploaded artifact, not just the asserts
        "metrics": {**eng.metrics.snapshot(),
                    "aot": {"traces": probe.traces,
                            "compiles": probe.compiles,
                            "aot_calls": probe.aot_calls,
                            "boot": aot.stats()}},
    }
    print(json.dumps(summary, indent=1))
    assert all(len(r.out) == r.max_new for r in reqs), "requests incomplete"
    assert probe.traces == 0 and probe.compiles == 0, (
        f"request-time retraces: {probe}")
    if warm:
        assert boot_tune["raced"] == 0, (
            f"warm boot ran autotune timing: {boot_tune}")
        assert summary["restored_plans"] > 0, "warm boot restored no plans"
        assert not summary["describe_mismatches"], summary["describe_mismatches"]
        assert summary["new_xla_cache_entries"] == 0, (
            f"warm boot recompiled {summary['new_xla_cache_entries']} executables")
    else:
        # the no-recompilation assertion above is only meaningful if the
        # cold boot actually persisted executables — a silently-disabled
        # cache would make the warm-boot check pass vacuously
        assert summary["new_xla_cache_entries"] > 0, (
            "cold boot persisted no executables: compilation cache inert")
    eng.shutdown()
    return summary


def resilience_smoke(arch: str, store_path: str, *, fault_seed: int = 7,
                     bench_out: str = None, slots: int = 1,
                     capacity: int = 64) -> dict:
    """Chaos smoke: seeded faults, typed responses, breaker cycle.

    Three self-asserting phases (``docs/serving.md`` §Resilience):

    1. **Clean boot** — no injector: traffic must show zero sheds, zero
       transitions, zero retries, NO fallback rungs built, and zero
       request-time traces (the resilience layer is free on the healthy
       path).  This boot also persists the plan store phase 2 corrupts.
    2. **Chaos boot** — a seeded :class:`FaultSchedule` with all three
       serving kinds: ``corrupt_store`` damages the store at boot (the
       engine must cold-warm + re-persist), ``exec_raise`` arms enough
       decode failures to open the breaker and demote to the jit rung,
       ``straggler`` stalls one tick.  Admission (``max_queue=3``) sheds
       the over-submitted burst; one request carries a short deadline
       and times out.  EVERY submitted request must end with a typed
       ``ServeResponse`` — no silent drops.
    3. **Plan-breaker incident** — a second seeded schedule drives a
       :func:`repro.serving.resilience.guard_plan` breaker over the
       warmed MSDA plan through demote -> half-open probe -> close,
       TWICE with fresh guards from the same seed: both runs must make
       identical decisions (the reproducibility contract).
    """
    from repro.kernels import plan as plan_mod
    from repro.runtime.faults import (
        SERVING_FAULT_KINDS, FaultInjector, FaultSchedule)
    from repro.serving import aot, persistence, resilience

    cfg = reduced(get_config(arch))
    params = train_state.init_model(jax.random.PRNGKey(0), cfg)
    vc = cfg.vision
    rng = np.random.default_rng(0)
    policy = resilience.ResilienceConfig(
        max_queue=3, max_retries=1, breaker_threshold=2, probe_interval=2)

    def _requests(n, deadline_rid=None):
        S = sum(h * w for h, w in vc.levels)
        out = []
        for i in range(n):
            out.append(Request(
                rid=i, prompt=np.arange(4, dtype=np.int32) + i, max_new=3,
                pyramid=rng.standard_normal((S, vc.vision_dim)).astype(np.float32),
                deadline_ticks=2 if i == deadline_rid else None))
        return out

    # -- phase 1: clean boot — resilience must be free ---------------------
    eng = ServeEngine(cfg, params, slots=slots, capacity=capacity,
                      store_path=store_path, resilience=policy)
    eng.warmup(prompt_lengths=(4,))
    clean_reqs = _requests(2)
    exec0 = plan_mod.execution_telemetry()
    with aot.probe() as probe:
        for r in clean_reqs:
            eng.submit(r)
        eng.run()
    clean_state = eng.resilience_state()
    clean = {
        "request_traces": probe.traces, "request_compiles": probe.compiles,
        "sheds": clean_state["sheds"],
        "transitions": sum(len(e["transitions"])
                           for e in clean_state["executors"].values()),
        "retries": sum(e["retries"] for e in clean_state["executors"].values()),
        "rungs_built": max(len(e["rungs_built"])
                           for e in clean_state["executors"].values()),
        "new_plan_builds": (plan_mod.execution_telemetry()["plan_cache"]["misses"]
                            - exec0["plan_cache"]["misses"]),
    }
    assert all(r.response is not None and r.response.ok for r in clean_reqs), \
        "clean run: non-ok response"
    assert clean["request_traces"] == 0 and clean["request_compiles"] == 0, clean
    assert clean["sheds"] == 0 and clean["transitions"] == 0 \
        and clean["retries"] == 0 and clean["new_plan_builds"] == 0, clean
    assert clean["rungs_built"] == 1, (
        f"clean run materialised fallback rungs: {clean}")
    eng.shutdown()
    del eng

    # -- phase 2: chaos boot on the now-corruptible store ------------------
    # seeded schedule; n_faults == len(kinds) guarantees every serving
    # kind fires exactly once (kinds cycle a seeded permutation)
    sched = FaultSchedule.generate(fault_seed, 8, n_faults=3,
                                   kinds=SERVING_FAULT_KINDS)
    sched2 = FaultSchedule.generate(fault_seed, 8, n_faults=3,
                                    kinds=SERVING_FAULT_KINDS)
    assert sched.describe() == sched2.describe(), "seeded schedule drifted"
    kinds_fired = sorted(e.kind for e in sched.events.values())
    assert kinds_fired == sorted(SERVING_FAULT_KINDS), kinds_fired
    # 4 armed raises = breaker_threshold * (max_retries + 1): enough to
    # exhaust two consecutive decode calls and open the breaker
    inj = FaultInjector(sched, raise_target="decode", raise_attempts=4,
                        straggler_s=0.01)
    eng = ServeEngine(cfg, params, slots=slots, capacity=capacity,
                      store_path=store_path, resilience=policy, faults=inj)
    assert eng.boot_faults, "corrupt_store fault did not fire at boot"
    assert eng.restore_report is None, "engine restored from a corrupt store"
    assert persistence.PlanStore(store_path).load() is not None, \
        "chaos boot did not re-persist the store"
    eng.warmup(prompt_lengths=(4,))
    chaos_reqs = _requests(5, deadline_rid=2)
    for r in chaos_reqs:
        eng.submit(r)
    eng.run(max_ticks=64)
    # the burst may finish before the later scheduled ticks: keep
    # follow-up traffic flowing until every seeded fault has fired and
    # every armed raise is consumed (deterministic — the loop is a pure
    # function of the seeded schedule)
    # function of the seeded schedule).  Traffic also continues until
    # the demoted decode breaker has probed its primary and re-closed —
    # the full demote -> half-open -> close cycle on the live engine.
    extra = []
    while (inj.pending_raises or inj.schedule.events
           or eng._decode_guard.rung > 0) and len(extra) < 8:
        r = _requests(1)[0]
        r.rid = 100 + len(extra)
        extra.append(r)
        eng.submit(r)
        eng.run(max_ticks=32)
    chaos_state = eng.resilience_state()
    statuses = sorted(r.response.status if r.response else "MISSING"
                      for r in chaos_reqs)
    by_status = {s: statuses.count(s) for s in set(statuses)}
    assert "MISSING" not in by_status, (
        f"request dropped without a typed response: {by_status}")
    assert all(r.response is not None for r in extra), \
        "follow-up request dropped without a typed response"
    assert by_status.get("shed", 0) == 2, by_status  # rids 3, 4: queue at 3
    assert by_status.get("timeout", 0) >= 1, by_status  # rid 2's deadline
    decode_t = [t[0] for t in chaos_state["executors"]["decode"]["transitions"]]
    assert decode_t and decode_t[0] == "open" and decode_t[-1] == "closed" \
        and "half_open" in decode_t, (
        f"decode breaker cycle incomplete: {decode_t}")
    assert inj.pending_raises == 0, "armed executor raises left unconsumed"
    assert not inj.schedule.events, f"unfired faults: {inj.schedule.describe()}"
    m = eng.metrics.snapshot()
    assert m["stragglers"] == 1, m["stragglers"]
    eng.shutdown()
    del eng

    # -- phase 3: plan-breaker incident, twice, same seed ------------------
    from repro.serving.engine import warmup_msda_plans

    def plan_incident():
        plan_mod.clear_plans()
        plans = warmup_msda_plans(cfg)
        # pick a plan with at least one fallback rung (heuristic-built,
        # never persisted); the bottom-of-ladder ref plan has none
        plan = next(p for p in plans if p.fallback() is not None)
        s = FaultSchedule.generate(fault_seed + 1, 4, n_faults=1,
                                   kinds=("exec_raise",))
        pinj = FaultInjector(s, raise_target="plan", raise_attempts=4)
        g = resilience.guard_plan(plan, policy, injector=pinj, name="plan",
                                  engine="chaos")
        structs = aot.plan_arg_structs(plan.spec, 1)
        prng = np.random.default_rng(3)
        args = tuple(prng.standard_normal(st.shape).astype(st.dtype)
                     for st in structs)
        outcomes = []
        [pinj.begin_tick(t) for t in range(4)]  # arm the scheduled raises
        for _ in range(8):
            try:
                g.call(*args)
                outcomes.append("ok")
            except resilience.ExecutorFailure:
                outcomes.append("fail")
        return outcomes, list(g.transitions), g.rung_labels(), list(pinj.log)

    out1, trans1, rungs1, log1 = plan_incident()
    out2, trans2, rungs2, log2 = plan_incident()
    assert (out1, trans1, rungs1, log1) == (out2, trans2, rungs2, log2), (
        "plan incident is not reproducible under the same seed")
    t_kinds = [t[0] for t in trans1]
    assert t_kinds[0] == "open" and "half_open" in t_kinds \
        and t_kinds[-1] == "closed" and trans1[-1][1] == 0, trans1
    assert len(rungs1) >= 2, f"ladder never materialised: {rungs1}"

    summary = {
        "arch": cfg.name,
        "clean": clean,
        "chaos": {
            "fault_schedule": sched.describe(),
            "responses": by_status,
            "untyped_requests": statuses.count("MISSING"),
            "sheds": chaos_state["sheds"],
            "deadline_misses": chaos_state["deadline_misses"],
            "exec_errors": chaos_state["exec_errors"],
            "stragglers": chaos_state["stragglers"],
            "boot_corruptions": len(chaos_state["boot_faults"]),
            "decode_transitions": decode_t,
        },
        "plan_breaker": {
            "transitions": trans1,
            "rungs": rungs1,
            "outcomes": out1,
            "reproducible": True,
        },
    }
    print(json.dumps(summary, indent=1))
    if bench_out:
        from repro.obs import bench as obs_bench

        results = {
            "untyped_requests": 0,
            "clean_request_traces": clean["request_traces"],
            "clean_sheds": clean["sheds"],
            "clean_transitions": clean["transitions"],
            "clean_rungs_built": clean["rungs_built"],
            "responses_ok": by_status.get("ok", 0),
            "responses_shed": by_status.get("shed", 0),
            "responses_timeout": by_status.get("timeout", 0),
            "responses_error": by_status.get("error", 0),
            "boot_corruptions": len(chaos_state["boot_faults"]),
            "stragglers": chaos_state["stragglers"],
            "decode_breaker_opens": decode_t.count("open"),
            "decode_breaker_closes": decode_t.count("closed"),
            "breaker_opens": t_kinds.count("open"),
            "breaker_closes": t_kinds.count("closed"),
            "plan_rungs_exercised": len(rungs1),
        }
        gate = [
            # structural: chaos event counts are seeded + deterministic,
            # they must not grow (a drop is a structural win)
            obs_bench.gate_rule("untyped_requests", "lower", 0.0),
            obs_bench.gate_rule("clean_*", "lower", 0.0),
            obs_bench.gate_rule("responses_error", "lower", 0.0),
            obs_bench.gate_rule("responses_timeout", "lower", 0.0),
            # the recovery machinery must keep firing under the seed
            obs_bench.gate_rule("responses_ok", "higher", 0.0),
            obs_bench.gate_rule("boot_corruptions", "higher", 0.0),
            obs_bench.gate_rule("breaker_closes", "higher", 0.0),
            obs_bench.gate_rule("decode_breaker_closes", "higher", 0.0),
            obs_bench.gate_rule("plan_rungs_exercised", "higher", 0.0),
        ]
        import dataclasses as _dc

        path = obs_bench.write_bench(
            bench_out, bench="serving_resilience", results=results,
            config={"arch": cfg.name, "fault_seed": fault_seed,
                    "slots": slots, "policy": _dc.asdict(policy)},
            note="seeded chaos smoke: typed responses, breaker cycle, "
                 "boot store corruption (repro.launch.serve --chaos-smoke)",
            events=summary["chaos"]["fault_schedule"], gate=gate)
        print(f"[serve] resilience bench -> {path}")
    return summary


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--prompts", nargs="+", default=["hello world"])
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=None,
                    help="default: 4 (2 for --serving-smoke)")
    ap.add_argument("--capacity", type=int, default=None,
                    help="default: 128 (64 for --serving-smoke)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--store", default=None,
                    help="plan-store path: warm boots restore every plan "
                         "with zero autotune races")
    ap.add_argument("--compile-cache", default=None,
                    help="JAX persistent compilation cache directory "
                         "(JAX_COMPILATION_CACHE_DIR, when set, wins; "
                         "default: .jax_cache/ at the checkout root)")
    ap.add_argument("--dtype-policy", default=None,
                    choices=("follow", "float32", "bfloat16", "auto"))
    ap.add_argument("--tune", default=None, choices=("heuristic", "autotune"))
    ap.add_argument("--warm-lengths", type=int, nargs="*", default=None,
                    help="prompt lengths to AOT-compile prefill for at boot")
    ap.add_argument("--mesh", default=None, metavar="DPxTP",
                    help="build a (data=DP, model=TP) mesh and warm "
                         "DISTRIBUTED plans (e.g. 2x2; needs DP*TP local "
                         "devices); the plan store then records/restores "
                         "the sharding modes — see docs/sharding.md")
    ap.add_argument("--serving-smoke", action="store_true",
                    help="self-asserting double-boot CI smoke (see docstring)")
    ap.add_argument("--chaos-smoke", action="store_true",
                    help="self-asserting resilience smoke under a seeded "
                         "fault schedule (see docstring)")
    ap.add_argument("--fault-seed", type=int, default=7,
                    help="seed for the chaos smoke's FaultSchedule")
    ap.add_argument("--bench-out", default=None,
                    help="write the chaos smoke's BENCH_resilience payload "
                         "here (gated by tools/bench_gate.py)")
    ap.add_argument("--metrics-out", default=None,
                    help="dump the obs metrics registry at exit "
                         "(.json -> JSON, else Prometheus text)")
    ap.add_argument("--trace-out", default=None,
                    help="stream obs spans to this JSONL file")
    ap.add_argument("--trace-level", type=int, default=3,
                    help="span verbosity exported to --trace-out (1-4)")
    args = ap.parse_args()

    from repro import obs

    if args.trace_out:
        obs.enable_trace(args.trace_out, level=args.trace_level)

    def _export() -> None:
        if args.metrics_out:
            print(f"[serve] metrics -> {obs.write_metrics(args.metrics_out)}")
        if args.trace_out:
            obs.disable_trace()
            print(f"[serve] trace -> {args.trace_out}")

    from repro.serving import persistence

    # one cache rule for every mode, before the first compile
    cache_dir = persistence.enable_jax_compilation_cache(args.compile_cache)
    print(f"[serve] compilation cache: {cache_dir}")

    if args.serving_smoke:
        if not args.store:
            ap.error("--serving-smoke needs --store")
        try:
            serving_smoke(args.arch or "phi-3-vision-4.2b", args.store,
                          cache_dir,
                          slots=args.slots or 2, capacity=args.capacity or 64)
        finally:
            _export()
        return

    if args.chaos_smoke:
        if not args.store:
            ap.error("--chaos-smoke needs --store")
        try:
            resilience_smoke(args.arch or "phi-3-vision-4.2b", args.store,
                             fault_seed=args.fault_seed,
                             bench_out=args.bench_out,
                             slots=args.slots or 1,
                             capacity=args.capacity or 64)
        finally:
            _export()
        return

    if not args.arch:
        ap.error("--arch is required")
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
    mesh = None
    if args.mesh:
        from repro.launch import mesh as mesh_lib

        try:
            shape = mesh_lib.parse_mesh_shape(args.mesh)
        except ValueError as e:
            ap.error(str(e))
        if shape is not None:
            mesh = mesh_lib.make_mesh_2d(*shape)
    params = train_state.init_model(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, slots=args.slots or 4,
                      capacity=args.capacity or 128,
                      temperature=args.temperature, store_path=args.store,
                      compile_cache_dir=cache_dir,
                      dtype_policy=args.dtype_policy, tune=args.tune,
                      mesh=mesh)
    rng = np.random.default_rng(0)
    reqs = []
    for i, p in enumerate(args.prompts):
        ids = np.asarray(tokenizer.encode(p), np.int32) % cfg.vocab_size
        req = Request(rid=i, prompt=ids, max_new=args.max_new)
        if cfg.family == "vlm":
            # driver demo: synthetic pyramid at the config geometry (a
            # real frontend would pass per-image levels + features)
            vc = cfg.vision
            S = sum(h * w for h, w in vc.levels)
            req.pyramid = rng.standard_normal((S, vc.vision_dim)).astype(np.float32)
        reqs.append(req)
    warm = args.warm_lengths
    if warm is None:
        warm = sorted({len(r.prompt) for r in reqs})
    eng.warmup(prompt_lengths=tuple(warm))
    for req in reqs:
        eng.submit(req)
    eng.run()
    for req in reqs:
        print(f"[serve] request {req.rid}: {len(req.out)} tokens -> {req.out}")
    print(eng.metrics.format())
    _export()


if __name__ == "__main__":
    main()
