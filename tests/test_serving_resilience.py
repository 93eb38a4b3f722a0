"""Serving resilience: deadlines, shedding, breakers, chaos injection.

The contracts (``docs/serving.md`` §Resilience):

* every ADMITTED request terminates with a typed ``ServeResponse`` —
  sheds and deadline misses included, never a silent drop;
* ``GuardedExecutor`` retries transient failures, opens its breaker
  after K CONSECUTIVE exhausted calls, demotes one rung down its
  (lazily materialised) ladder, probes the primary on the half-open
  schedule and promotes back on success;
* the clean path is free: no fallback rungs built, no extra plan
  builds, no retraces, no breaker transitions;
* chaos is reproducible: equal seeded ``FaultSchedule``s + equal
  injector configs make IDENTICAL fault and recovery decisions.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.kernels import plan as plan_mod
from repro.runtime.faults import (
    SERVING_FAULT_KINDS,
    FaultInjector,
    FaultSchedule,
    InjectedExecutorError,
    corrupt_plan_store,
)
from repro.serving import aot, persistence
from repro.serving.engine import Request, ServeEngine
from repro.serving.resilience import (
    AdmissionController,
    ExecutorFailure,
    GuardedExecutor,
    ResilienceConfig,
    ServeResponse,
    guard_plan,
    ladder_of,
    resilience_snapshot,
)


@pytest.fixture(autouse=True)
def _isolated(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_MSDA_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    plan_mod.clear_plans()
    plan_mod.reset_autotune_stats()
    aot.reset_stats()
    yield
    plan_mod.clear_plans()


def _lm_engine(slots=2, capacity=32, **kw):
    from repro.configs.base import get_config, reduced
    from repro.models import lm

    cfg = reduced(get_config("llama3-8b"))
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    return cfg, params, ServeEngine(cfg, params, slots=slots,
                                    capacity=capacity, **kw)


def _req(rid, n=4, max_new=3, **kw):
    return Request(rid=rid, prompt=np.arange(n, dtype=np.int32) + rid,
                   max_new=max_new, **kw)


# --------------------------------------------------------------------------
# GuardedExecutor: retry, breaker, ladder, half-open probe
# --------------------------------------------------------------------------


class _Flaky:
    """Callable that fails the first ``n_failures`` invocations."""

    def __init__(self, n_failures, result="ok"):
        self.n_failures = n_failures
        self.calls = 0
        self.result = result

    def __call__(self, *a):
        self.calls += 1
        if self.calls <= self.n_failures:
            raise RuntimeError(f"flake #{self.calls}")
        return self.result


def test_retry_recovers_transient_failure():
    pol = ResilienceConfig(max_retries=2)
    flaky = _Flaky(2)
    g = GuardedExecutor("x", flaky, policy=pol)
    assert g.call() == "ok"  # 2 failures absorbed by the retry budget
    assert g.retry_count == 2 and g.state == "closed" and g.rung == 0
    assert g.transitions == []


def test_retry_exhaustion_is_typed_and_counts_toward_breaker():
    pol = ResilienceConfig(max_retries=1, breaker_threshold=3)
    g = GuardedExecutor("x", _Flaky(100), policy=pol)
    with pytest.raises(ExecutorFailure):
        g.call()
    assert g.consecutive_failures == 1 and g.state == "closed"


def test_breaker_demotes_after_k_consecutive_failures_then_recloses():
    pol = ResilienceConfig(max_retries=0, breaker_threshold=2,
                           probe_interval=2)
    primary = _Flaky(3, result="primary")  # heals after 3 failures
    backup = _Flaky(0, result="backup")
    g = GuardedExecutor("x", primary, demote_fn=ladder_of([backup]),
                        policy=pol)
    with pytest.raises(ExecutorFailure):
        g.call()  # failure 1: below threshold -> typed failure
    assert g.call() == "backup"  # failure 2 demotes; SAME call served
    assert g.state == "open" and g.rung == 1
    assert g.call() == "backup"  # calls_since_demote=1
    # 2nd call since demote: half-open probe — the primary's 3rd (and
    # last) flake fails it, so the breaker re-opens and the rung serves
    assert g.call() == "backup"
    assert g.state == "open" and g.rung == 1
    assert g.call() == "backup"  # off the probe schedule
    # next probe finds the healed primary: promote back to rung 0
    assert g.call() == "primary"
    assert g.state == "closed" and g.rung == 0
    assert [t[0] for t in g.transitions] == [
        "open", "half_open", "open", "half_open", "closed"]


def test_half_open_probe_failure_reopens():
    pol = ResilienceConfig(max_retries=0, breaker_threshold=1,
                           probe_interval=1)
    primary = _Flaky(100)
    g = GuardedExecutor("x", primary,
                        demote_fn=ladder_of([_Flaky(0, result="backup")]),
                        policy=pol)
    assert g.call() == "backup"  # immediate demote (threshold 1)
    assert g.call() == "backup"  # probe fails -> re-open -> rung serves
    states = [t[0] for t in g.transitions]
    assert states == ["open", "half_open", "open"]
    assert g.rung == 1


def test_ladder_is_lazy_and_bottoms_out():
    pol = ResilienceConfig(max_retries=0, breaker_threshold=1)
    g = GuardedExecutor("x", _Flaky(100),
                        demote_fn=ladder_of([_Flaky(100), _Flaky(100)]),
                        policy=pol)
    assert g.rung_labels() == ["_Flaky"]  # nothing materialised yet
    with pytest.raises(ExecutorFailure):
        g.call()  # walks every rung, all fail
    assert len(g.rung_labels()) == 3
    assert g.rung == 2  # parked at the bottom


# --------------------------------------------------------------------------
# admission control + typed responses
# --------------------------------------------------------------------------


def test_admission_sheds_past_bound_with_backpressure():
    adm = AdmissionController(2, engine="t")
    assert adm.admit(0) and adm.admit(1)
    assert adm.backpressure(1) == 0.5
    assert not adm.admit(2) and adm.shed_count == 1
    assert adm.backpressure(2) == 1.0


def test_serve_response_statuses_are_validated():
    with pytest.raises(ValueError, match="unknown status"):
        ServeResponse("dropped", 0)
    r = ServeResponse("ok", 1, tokens=(1, 2))
    assert r.ok and r.tokens == (1, 2)


def test_engine_sheds_over_max_queue_with_typed_response():
    _, _, eng = _lm_engine(slots=1, max_queue=2)
    eng.warmup(prompt_lengths=(4,))
    reqs = [_req(i) for i in range(4)]
    resp = [eng.submit(r) for r in reqs]
    assert resp[0] is None and resp[1] is None  # admitted
    assert resp[2].status == "shed" and resp[3].status == "shed"
    eng.run()
    assert all(r.response is not None for r in reqs)
    assert [r.response.status for r in reqs] == ["ok", "ok", "shed", "shed"]
    m = eng.metrics.snapshot()
    assert m["shed"] == 2 and m["submitted"] == 2
    assert eng.resilience_state()["sheds"] == 2


def test_engine_deadline_resolves_queued_request_as_timeout():
    _, _, eng = _lm_engine(slots=1)
    eng.warmup(prompt_lengths=(4,))
    r0 = _req(0, max_new=4)
    r1 = _req(1, max_new=2, deadline_ticks=1)  # will wait behind r0
    eng.submit(r0)
    eng.submit(r1)
    eng.run()
    assert r0.response.ok and len(r0.out) == 4
    assert r1.response.status == "timeout" and "deadline" in r1.response.detail
    assert eng.metrics.snapshot()["deadline_misses"] == 1
    # the per-request tick maps were cleaned up on resolution
    assert not eng.metrics._submit_tick and not eng.metrics._admit_tick


def test_engine_default_deadline_from_config():
    # the engine-wide default applies to queued AND in-flight requests:
    # r0 finishes within its 2 ticks; r1/r2 (queued behind it, then
    # mid-decode) inherit the default and expire
    _, _, eng = _lm_engine(
        slots=1, resilience=ResilienceConfig(deadline_ticks=2))
    eng.warmup(prompt_lengths=(4,))
    reqs = [_req(0, max_new=2)] + [_req(i, max_new=6) for i in (1, 2)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert reqs[0].response.ok
    assert all(r.response.status == "timeout" for r in reqs[1:])


# --------------------------------------------------------------------------
# chaos: injected executor faults through the live engine
# --------------------------------------------------------------------------


def _chaos_engine(spec, **inj_kw):
    inj = FaultInjector(FaultSchedule.from_spec(spec), **inj_kw)
    _, _, eng = _lm_engine(
        slots=1,
        resilience=ResilienceConfig(max_retries=1, breaker_threshold=2,
                                    probe_interval=2),
        faults=inj)
    eng.warmup(prompt_lengths=(4,))
    return eng, inj


def test_injected_decode_raises_drive_breaker_cycle():
    # 4 armed raises = threshold * (retries + 1): one exhausted call
    # (typed error), a second that demotes mid-call, then recovery
    eng, inj = _chaos_engine("exec_raise@1", raise_target="decode",
                             raise_attempts=4)
    reqs = [_req(i, max_new=3) for i in range(4)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    while eng._decode_guard.rung > 0:  # drive the half-open probe
        r = _req(100, max_new=2)
        reqs.append(r)
        eng.submit(r)
        eng.run()
    assert inj.pending_raises == 0
    assert all(r.response is not None for r in reqs), "untyped response"
    statuses = {r.response.status for r in reqs}
    assert "error" in statuses and "ok" in statuses
    t = [s for s, _ in eng._decode_guard.transitions]
    assert t[0] == "open" and "half_open" in t and t[-1] == "closed"
    assert eng.metrics.snapshot()["exec_errors"] >= 1


def test_straggler_tick_is_metered():
    eng, _ = _chaos_engine("straggler@1", straggler_s=0.0)
    for i in range(2):
        eng.submit(_req(i))
    eng.run()
    assert eng.metrics.snapshot()["stragglers"] == 1


def test_chaos_run_is_reproducible_same_seed():
    def run(seed):
        sched = FaultSchedule.generate(seed, 6, n_faults=2,
                                       kinds=("exec_raise", "straggler"))
        eng, inj = None, FaultInjector(sched, raise_target="decode",
                                      raise_attempts=2)
        _, _, eng = _lm_engine(
            slots=1,
            resilience=ResilienceConfig(max_retries=0, breaker_threshold=2,
                                        probe_interval=2),
            faults=inj)
        eng.warmup(prompt_lengths=(4,))
        reqs = [_req(i, max_new=3) for i in range(3)]
        for r in reqs:
            eng.submit(r)
        eng.run()
        return ([r.response.status for r in reqs], list(inj.log),
                list(eng._decode_guard.transitions), sched.describe())

    assert run(13) == run(13)
    # and the schedule itself is seed-sensitive
    assert FaultSchedule.generate(13, 6).describe() != \
        FaultSchedule.generate(14, 6).describe()


def test_serving_kinds_reject_unknown_and_training_kinds_are_ignored():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultSchedule.from_spec("gremlin@3")
    inj = FaultInjector(FaultSchedule.from_spec("host_loss@1"))
    ev = inj.begin_tick(1)
    assert ev.kind == "host_loss"
    assert inj.log[-1]["ignored"] is True
    assert inj.pending_raises == 0


# --------------------------------------------------------------------------
# boot-time store corruption
# --------------------------------------------------------------------------


def test_corrupt_store_at_boot_degrades_to_cold_warm_and_repersists(tmp_path):
    store = str(tmp_path / "plans.json")
    from repro.configs.base import get_config, reduced
    from repro.models import vlm

    cfg = reduced(get_config("phi-3-vision-4.2b"))
    params = vlm.init_vlm(jax.random.PRNGKey(0), cfg)
    e1 = ServeEngine(cfg, params, slots=1, capacity=64, store_path=store)
    assert persistence.PlanStore(store).exists()
    e1.shutdown()

    inj = FaultInjector(FaultSchedule.from_spec("corrupt_store@0"))
    plan_mod.clear_plans()
    e2 = ServeEngine(cfg, params, slots=1, capacity=64, store_path=store,
                     faults=inj)
    assert e2.boot_faults == [store]
    assert e2.restore_report is None  # corrupt store -> cold boot
    assert e2.plans, "cold boot warmed no plans"
    assert persistence.PlanStore(store).load() is not None, "not re-persisted"
    assert inj.log[0]["at"] == "boot"
    e2.shutdown()


def test_corrupt_plan_store_missing_path_is_noop(tmp_path):
    assert corrupt_plan_store(str(tmp_path / "absent.json")) is None
    assert corrupt_plan_store("") is None


# --------------------------------------------------------------------------
# plan degradation ladder (unit level; numeric parity in conformance.py)
# --------------------------------------------------------------------------


def test_guard_plan_demotes_down_fallback_ladder():
    from repro.kernels.plan import MsdaSpec, msda_plan

    # queries on the pixel grid (Q == S), so the Morton order engages:
    # bitwise-neutral, and its demotion stays on the same backend
    spec = MsdaSpec(spatial_shapes=((6, 4), (3, 2)), num_heads=2, head_dim=8,
                    num_points=2, num_queries=30, dtype="float32",
                    query_order="morton")
    plan = msda_plan(spec, backend="pallas", tune="heuristic")
    assert plan.tuning.query_order == "morton", "primary should be Morton"
    inj = FaultInjector(FaultSchedule.from_spec("exec_raise@1"),
                        raise_target="p", raise_attempts=2)
    inj.begin_tick(1)
    pol = ResilienceConfig(max_retries=0, breaker_threshold=2,
                           probe_interval=4)
    g = guard_plan(plan, pol, injector=inj, name="p", engine="t")
    rng = np.random.default_rng(0)
    S = sum(h * w for h, w in spec.spatial_shapes)
    v = rng.standard_normal((1, S, 2, 8)).astype(np.float32)
    loc = rng.uniform(size=(1, 30, 2, 2, 2, 2)).astype(np.float32)
    a = rng.uniform(size=(1, 30, 2, 2, 2)).astype(np.float32)
    with pytest.raises(ExecutorFailure):
        g.call(v, loc, a)  # injected raise, no retries -> failure 1
    out = g.call(v, loc, a)  # failure 2 demotes; the identity rung serves
    assert g.rung == 1 and g.state == "open"
    assert g.rung_labels() == ["pallas/morton", "pallas"]
    # the demoted rung is race-free and bitwise vs the Morton primary
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(plan(v, loc, a)))
    assert plan_mod.autotune_stats()["raced"] == 0
    snap = resilience_snapshot([g])
    assert snap["executors"]["p"]["rung"] == 1


def test_plan_ladder_never_persists_winners(tmp_path, monkeypatch):
    from repro.kernels.plan import MsdaSpec, msda_plan

    cache = tmp_path / "winners.json"
    monkeypatch.setenv("REPRO_MSDA_AUTOTUNE_CACHE", str(cache))
    spec = MsdaSpec(spatial_shapes=((6, 4),), num_heads=2, head_dim=8,
                    num_points=2, num_queries=5, dtype="float32")
    plan = msda_plan(spec, backend="pallas", tune="heuristic")
    for rung in plan.fallback_chain():
        assert rung.tune == "heuristic"
    assert not cache.exists(), "fallback build persisted an autotune winner"


# --------------------------------------------------------------------------
# clean path: resilience must be free
# --------------------------------------------------------------------------


def test_clean_run_builds_no_rungs_and_adds_no_traces():
    _, _, eng = _lm_engine(slots=2)
    eng.warmup(prompt_lengths=(4,))
    tele0 = plan_mod.execution_telemetry()
    reqs = [_req(i) for i in range(3)]
    with aot.probe() as p:
        for r in reqs:
            eng.submit(r)
        eng.run()
    assert all(r.response is not None and r.response.ok for r in reqs)
    assert p.traces == 0 and p.compiles == 0
    state = eng.resilience_state()
    assert state["sheds"] == 0
    for ex in state["executors"].values():
        assert ex["rung"] == 0 and ex["transitions"] == [] \
            and ex["retries"] == 0 and len(ex["rungs_built"]) == 1
    assert plan_mod.execution_telemetry() == tele0, \
        "resilience layer changed plan execution telemetry on a clean run"


def test_resilience_config_validates():
    with pytest.raises(ValueError, match="max_queue"):
        ResilienceConfig(max_queue=0)
    with pytest.raises(ValueError, match="breaker_threshold"):
        ResilienceConfig(breaker_threshold=0)
    with pytest.raises(ValueError, match="probe_interval"):
        ResilienceConfig(probe_interval=0)
    with pytest.raises(ValueError, match="max_retries"):
        ResilienceConfig(max_retries=-1)
    # engine max_queue kwarg overrides the config's bound
    c = dataclasses.replace(ResilienceConfig(), max_queue=7)
    assert c.max_queue == 7


def test_event_window_bounds_metrics_memory():
    from repro.serving.metrics import ServeMetrics

    m = ServeMetrics()
    for rid in range(10_000):
        m.record_submit(rid)
        m.record_admit(rid)
        m.record_tick()
        m.record_retire(rid)
    s = m.snapshot()
    assert s["retired"] == 10_000  # exact counters survive the window
    assert len(m.latency_ticks) <= m.latency_ticks.window
    assert not m._submit_tick and not m._admit_tick
    assert s["latency_ticks"]["max"] >= 0.0


def test_step_recorder_window_keeps_exact_aggregates():
    from repro.training.telemetry import StepTimeRecorder

    rec = StepTimeRecorder(window=8)
    for i in range(100):
        rec.record_step(i, 0.5)
    rec.record_event("recovery", step=50, latency_s=1.0)
    s = rec.summary()
    assert s["steps"] == 100 and s["mean_step_s"] == pytest.approx(0.5)
    assert s["total_step_wall_s"] == pytest.approx(50.0)
    assert s["recoveries"] == 1
    p = rec.payload()
    assert len(p["trajectory"]) == 8  # windowed raw rows
