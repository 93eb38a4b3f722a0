"""Serving resilience: deadlines, admission control, breakers, ladders.

The layer that turns the warmed/AOT/persisted plan machinery into
something that degrades gracefully instead of falling over
(``docs/serving.md`` §Resilience):

* **Typed responses** — every request the engine ADMITS ends with a
  :class:`ServeResponse`; load shedding and deadline misses resolve
  requests with ``"shed"`` / ``"timeout"`` statuses instead of
  dropping them, executor exhaustion resolves with ``"error"``.
* **Admission control** — the engine's queue is bounded
  (``ResilienceConfig.max_queue``); past the bound, ``submit`` sheds
  with a typed response and the backpressure gauge
  (``serve.resilience.queue_depth`` / ``backpressure``) tells the
  frontend to back off BEFORE the bound is hit.
* **Retry + circuit breaker + degradation ladder** —
  :class:`GuardedExecutor` wraps an executor callable: transient
  failures retry with backoff; ``breaker_threshold`` CONSECUTIVE
  exhausted calls open the breaker and demote one rung down the
  ladder (for MSDA plans: ``MsdaPlan.fallback()`` — sparse -> dense
  -> ref, interpreted kernels only; built race-free, never persisted
  as a winner); while demoted, the primary is probed on a half-open
  schedule every ``probe_interval`` calls and promoted back on
  success.

Every resilience event lands in the PR 8 obs registry
(``serve.resilience.*`` series + ``resilience.*`` spans).  The CLEAN
path stays zero-overhead: a guarded call in the steady state is one
Python ``try`` around the same executor — no new traces, no plan
builds, no extra ``MsdaPlan.__call__`` (fallback rungs are
materialised lazily, on first demotion), so
``plan.execution_telemetry()`` is unchanged on a fault-free run.

Chaos injection rides :class:`repro.runtime.faults.FaultInjector` —
the shared seeded ``FaultSchedule`` contract the training harness
uses, extended with serving kinds (``exec_raise`` / ``straggler`` /
``corrupt_store``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from repro.obs import registry as _obs
from repro.obs import trace as _obs_trace
from repro.runtime.faults import FaultInjector, InjectedExecutorError  # noqa: F401

RESPONSE_STATUSES = ("ok", "shed", "timeout", "error")

# breaker states (GuardedExecutor.state)
CLOSED, OPEN, HALF_OPEN = "closed", "open", "half_open"

_EVENTS = _obs.counter(
    "serve.resilience.events",
    help="resilience events by type (shed/deadline_miss/retry/...)")
_BREAKER = _obs.counter(
    "serve.resilience.breaker",
    help="circuit-breaker state transitions by executor")
_RUNG = _obs.gauge(
    "serve.resilience.rung",
    help="active degradation-ladder rung per executor (0 = primary)")
_DEPTH = _obs.gauge(
    "serve.resilience.queue_depth",
    help="admission queue depth (pending requests)")
_BACKPRESSURE = _obs.gauge(
    "serve.resilience.backpressure",
    help="admission queue fill fraction (1.0 = shedding)")


@dataclasses.dataclass(frozen=True)
class ServeResponse:
    """The typed terminal state of one request.

    ``status``: ``"ok"`` (served, ``tokens`` carries the output),
    ``"shed"`` (rejected at admission: queue full), ``"timeout"``
    (deadline exceeded — queued or mid-decode), ``"error"`` (executor
    failed past every retry and ladder rung).
    """

    status: str
    rid: int
    detail: str = ""
    tokens: Tuple[int, ...] = ()

    def __post_init__(self):
        if self.status not in RESPONSE_STATUSES:
            raise ValueError(
                f"unknown status {self.status!r}; one of {RESPONSE_STATUSES}")

    @property
    def ok(self) -> bool:
        return self.status == "ok"


@dataclasses.dataclass(frozen=True)
class ResilienceConfig:
    """Knobs for one engine's resilience layer (all host-side).

    ``max_queue`` bounds admission (sheds past it); ``deadline_ticks``
    is the default per-request deadline in engine ticks (None = no
    deadline unless the request carries one); ``max_retries`` /
    ``backoff_s`` drive retry-with-backoff (backoff doubles per
    attempt; 0.0 keeps tests instant); ``breaker_threshold`` is K —
    consecutive retry-exhausted calls before the breaker opens and
    demotes; ``probe_interval`` is the half-open schedule — while
    demoted, every Nth call probes the primary.
    """

    max_queue: int = 256
    deadline_ticks: Optional[int] = None
    max_retries: int = 2
    backoff_s: float = 0.0
    breaker_threshold: int = 3
    probe_interval: int = 4

    def __post_init__(self):
        if self.max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {self.max_queue}")
        if self.max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {self.max_retries}")
        if self.breaker_threshold < 1:
            raise ValueError(
                f"breaker_threshold must be >= 1, got {self.breaker_threshold}")
        if self.probe_interval < 1:
            raise ValueError(
                f"probe_interval must be >= 1, got {self.probe_interval}")


class ExecutorFailure(RuntimeError):
    """Every retry and every ladder rung failed for one call."""


class AdmissionController:
    """Bounded-queue admission with load shedding + backpressure.

    The engine consults :meth:`admit` with its CURRENT pending depth
    before enqueueing; past ``max_queue`` the request sheds.  The
    backpressure gauge is exported continuously so a frontend can
    shape traffic before the hard bound sheds it.
    """

    def __init__(self, max_queue: int, *, engine: str = "e?"):
        self.max_queue = int(max_queue)
        self.shed_count = 0
        self._engine = engine

    def admit(self, pending: int) -> bool:
        ok = pending < self.max_queue
        if not ok:
            self.shed_count += 1
            _EVENTS.inc(engine=self._engine, type="shed")
        self.observe(pending if ok else self.max_queue)
        return ok

    def observe(self, pending: int) -> None:
        _DEPTH.set(pending, engine=self._engine)
        _BACKPRESSURE.set(self.backpressure(pending), engine=self._engine)

    def backpressure(self, pending: int) -> float:
        return min(1.0, pending / self.max_queue)


class GuardedExecutor:
    """Retry + circuit breaker + degradation ladder around one executor.

    ``primary`` is the rung-0 callable; ``demote_fn(current) ->
    next | None`` materialises the ladder LAZILY (clean runs build
    nothing).  For MSDA plans use :func:`guard_plan`; for a fixed
    ladder use ``demote_fn=ladder_of([...])``.

    State machine (``self.state``): ``closed`` — primary serving;
    after ``breaker_threshold`` CONSECUTIVE retry-exhausted calls the
    breaker transitions to ``open`` and the active rung demotes (the
    same call then continues down the ladder — a demotion is not a
    failed request).  While any rung below primary is active, every
    ``probe_interval``-th call first transitions to ``half_open`` and
    probes the primary: success promotes straight back to rung 0
    (``closed``), failure re-``open``s and the call proceeds on the
    demoted rung.  Transitions are metered
    (``serve.resilience.breaker``), the active rung is a gauge, and
    ``self.transitions`` keeps the ordered log the reproducibility
    tests compare.
    """

    def __init__(self, name: str, primary: Callable, *,
                 demote_fn: Optional[Callable[[Callable], Optional[Callable]]] = None,
                 policy: Optional[ResilienceConfig] = None,
                 label_fn: Callable[[Callable], str] = lambda f: getattr(
                     f, "__name__", f.__class__.__name__),
                 injector: Optional[FaultInjector] = None,
                 engine: str = "e?"):
        self.name = name
        self.policy = policy or ResilienceConfig()
        self._rungs: List[Callable] = [primary]
        self._demote_fn = demote_fn
        self._ladder_done = demote_fn is None
        self._label_fn = label_fn
        self.injector = injector
        self._engine = engine
        self.rung = 0
        self.state = CLOSED
        self.consecutive_failures = 0
        self._calls_since_demote = 0
        self.retry_count = 0
        self.transitions: List[Tuple[str, int]] = []  # (state, rung) log
        _RUNG.set(0, engine=engine, executor=name)

    # -- ladder -----------------------------------------------------------
    def _materialise(self, i: int) -> Optional[Callable]:
        """Rung ``i``'s callable, building the ladder as needed."""
        while len(self._rungs) <= i and not self._ladder_done:
            nxt = self._demote_fn(self._rungs[-1])
            if nxt is None:
                self._ladder_done = True
            else:
                self._rungs.append(nxt)
        return self._rungs[i] if i < len(self._rungs) else None

    def rung_labels(self) -> List[str]:
        """Labels of the rungs materialised SO FAR (clean runs: just
        the primary — the ladder is built on demand)."""
        return [self._label_fn(r) for r in self._rungs]

    # -- state transitions ------------------------------------------------
    def _transition(self, state: str) -> None:
        self.state = state
        self.transitions.append((state, self.rung))
        _BREAKER.inc(engine=self._engine, executor=self.name, transition=state)
        _RUNG.set(self.rung, engine=self._engine, executor=self.name)
        with _obs_trace.span("resilience.breaker", level=2,
                             executor=self.name, transition=state,
                             rung=self.rung):
            pass

    # -- call path --------------------------------------------------------
    def _attempt(self, fn: Callable, rung: int, args, kwargs):
        if self.injector is not None and self.injector.should_raise(
                self.name, rung):
            raise InjectedExecutorError(
                f"injected fault: executor {self.name!r} rung {rung}")
        return fn(*args, **kwargs)

    def _try_rung(self, rung: int, args, kwargs):
        """One rung with the full retry budget; raises the last error."""
        fn = self._materialise(rung)
        assert fn is not None
        p = self.policy
        for attempt in range(p.max_retries + 1):
            try:
                return self._attempt(fn, rung, args, kwargs)
            except Exception as e:  # noqa: BLE001 — retries see everything
                if attempt >= p.max_retries:
                    raise
                self.retry_count += 1
                _EVENTS.inc(engine=self._engine, type="retry")
                with _obs_trace.span("resilience.retry", level=2,
                                     executor=self.name, rung=rung,
                                     attempt=attempt + 1,
                                     error=type(e).__name__):
                    pass
                if p.backoff_s > 0:
                    time.sleep(p.backoff_s * (2 ** attempt))

    def call(self, *args, **kwargs):
        """Execute with the full resilience stack; raises
        :class:`ExecutorFailure` only when every rung is exhausted."""
        p = self.policy
        # half-open probe: while demoted, periodically try the primary
        if self.rung > 0:
            self._calls_since_demote += 1
            if self._calls_since_demote % p.probe_interval == 0:
                self._transition(HALF_OPEN)
                try:
                    out = self._attempt(self._rungs[0], 0, args, kwargs)
                except Exception as e:  # noqa: BLE001 — probe failed
                    _EVENTS.inc(engine=self._engine, type="probe_failure")
                    with _obs_trace.span("resilience.probe", level=2,
                                         executor=self.name, ok=False,
                                         error=type(e).__name__):
                        pass
                    self._transition(OPEN)
                else:
                    self.rung = 0
                    self.consecutive_failures = 0
                    self._calls_since_demote = 0
                    _EVENTS.inc(engine=self._engine, type="probe_success")
                    self._transition(CLOSED)
                    return out
        rung = self.rung
        while True:
            try:
                out = self._try_rung(rung, args, kwargs)
            except Exception as e:  # noqa: BLE001 — rung exhausted
                _EVENTS.inc(engine=self._engine, type="exec_failure")
                self.consecutive_failures += 1
                if (self.consecutive_failures >= p.breaker_threshold
                        and self._materialise(rung + 1) is not None):
                    # demote: open the breaker, continue THIS call on
                    # the next rung with a fresh retry budget
                    rung = self.rung = rung + 1
                    self.consecutive_failures = 0
                    self._calls_since_demote = 0
                    self._transition(OPEN)
                    continue
                if self._materialise(rung + 1) is None and rung > 0:
                    # bottom of a demoted ladder still failing: give the
                    # caller the typed failure, keep the rung
                    raise ExecutorFailure(
                        f"executor {self.name!r} failed on every rung "
                        f"(last: {type(e).__name__}: {e})") from e
                if self.consecutive_failures < p.breaker_threshold:
                    raise ExecutorFailure(
                        f"executor {self.name!r} exhausted retries on rung "
                        f"{rung} ({type(e).__name__}: {e})") from e
                raise ExecutorFailure(
                    f"executor {self.name!r} failed with no rung to demote "
                    f"to ({type(e).__name__}: {e})") from e
            else:
                self.consecutive_failures = 0
                return out

    __call__ = call


def ladder_of(rungs: Sequence[Callable]) -> Callable[[Callable], Optional[Callable]]:
    """A ``demote_fn`` walking a fixed rung list (primary excluded)."""
    rungs = list(rungs)

    def demote(_current: Callable) -> Optional[Callable]:
        return rungs.pop(0) if rungs else None

    return demote


def guard_plan(plan, policy: Optional[ResilienceConfig] = None, *,
               mesh=None, injector: Optional[FaultInjector] = None,
               name: Optional[str] = None,
               engine: str = "e?") -> GuardedExecutor:
    """A per-plan circuit breaker over ``MsdaPlan.fallback()``.

    The ladder is materialised lazily — a clean run builds no fallback
    plan and adds no plan-cache traffic.  Demoted rungs are heuristic
    builds (never autotuned, never persisted as winners).
    """
    label = name or f"plan[{plan.rung_label()}|Q={plan.spec.num_queries}]"
    return GuardedExecutor(
        label, plan,
        demote_fn=lambda p: p.fallback(mesh=mesh),
        policy=policy,
        label_fn=lambda p: p.rung_label() if hasattr(p, "rung_label")
        else getattr(p, "__name__", "fn"),
        injector=injector, engine=engine)


def resilience_snapshot(guards: Sequence[GuardedExecutor],
                        admission: Optional[AdmissionController] = None
                        ) -> Dict[str, Any]:
    """Machine-readable view of one engine's resilience state — the
    block the chaos smoke asserts on and ``BENCH_resilience.json``
    gates."""
    out: Dict[str, Any] = {
        "sheds": admission.shed_count if admission else 0,
        "executors": {},
    }
    for g in guards:
        out["executors"][g.name] = {
            "state": g.state,
            "rung": g.rung,
            "rungs_built": g.rung_labels(),
            "retries": g.retry_count,
            "transitions": [list(t) for t in g.transitions],
        }
    return out
