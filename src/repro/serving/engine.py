"""Serving engine: batched prefill + decode with continuous batching.

Contract (``docs/serving.md``): all latency-shaped work — plan
construction, autotune races, XLA compilation — happens at boot, never
at request time.  Plans restore from a ``PlanStore`` (zero races; a
mesh-carrying boot passes ``mesh=`` and gets identical distributed
plans), ``warmup()`` AOT-compiles every request-shape executor, and the
``aot.probe()`` counters prove zero request-time traces.  Requests then
flow through a fixed slot pool: retire, admit (vlm: bucket-padded
pyramid batches), one fused decode per tick.

``make_serve_fns(cfg)`` returns the pure jittable pair used by both the
engine and the dry-run cells:

* ``prefill(params, prompt_inputs...) -> (logits, cache)``
* ``decode_step(params, cache, token) -> (logits, cache)``

``ServeEngine`` adds request scheduling on top: a fixed pool of batch
slots, each slot independently in {empty, prefilling, decoding}; new
requests are admitted into free slots between decode steps (continuous
batching).  Slot state is host-side; the device-side cache is a single
batched pytree so every decode step is one fused program.

The engine composes the serving-runtime subsystem:

* ``serving.aot``         — ``warmup()`` AOT-compiles the decode step,
  the prefill programs and every warmed ``MsdaPlan`` executor at boot;
  the compile-count probe then asserts zero retraces at request time.
* ``serving.persistence`` — ``store_path=`` restores the full plan set
  (specs + autotune winners) from a previous process with zero autotune
  races, and ``compile_cache_dir=`` wires JAX's persistent compilation
  cache so the boot compiles themselves are disk hits.
* ``serving.batcher``     — vlm requests carry variable image pyramids;
  a shape-bucketed front end pads them into a fixed bucket ladder so
  the bounded plan cache never churns and prefill programs are reused.
* ``serving.metrics``     — per-bucket admission/padding/latency/retire
  counters, surfaced by ``launch/serve.py``.
* ``serving.resilience``  — deadlines, bounded-queue admission with
  load shedding, retry + circuit breakers with the plan degradation
  ladder, chaos injection via ``runtime.faults.FaultInjector``
  (``faults=`` kwarg).  Every ADMITTED request terminates with a typed
  ``ServeResponse`` (``req.response``); sheds and deadline misses are
  typed too, never silent drops.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.faults import FaultInjector
from repro.serving import aot
from repro.serving import batcher as batcher_mod
from repro.serving import persistence
from repro.serving import resilience as resil_mod
from repro.serving.metrics import ServeMetrics
from repro.serving.resilience import (
    AdmissionController,
    ExecutorFailure,
    GuardedExecutor,
    ResilienceConfig,
    ServeResponse,
    ladder_of,
)

_LM_FAMILIES = ("dense", "moe", "hybrid", "ssm")


def make_serve_fns(cfg, *, dtype_policy: Optional[str] = None,
                   tune: Optional[str] = None,
                   warm_plans: bool = True) -> Tuple[Callable, Callable]:
    """The pure (prefill, decode) pair for a family.

    This is the ONE place the per-family serving closures are defined —
    the engine builds its jitted/AOT variants from the same pair, so a
    plan axis added here (dtype_policy, tune, the vlm bucketing
    ``levels``/``valid_ratios`` kwargs) reaches every consumer at once.

    ``dtype_policy``/``tune`` thread the MSDA plan axes into BOTH the
    plan warm-up and the vlm prefill itself, so the plans warmed at
    build time are byte-for-byte the specs the first prefill trace asks
    for (an override that only reached the warm-up would re-plan — and
    possibly re-race — at request time).  ``warm_plans=False`` skips the
    warm-up for callers that warm their own plan set (the engine warms
    its bucket ladder instead of the single config geometry).
    """
    if cfg.family in _LM_FAMILIES:
        from repro.models import lm

        def prefill(params, tokens, capacity):
            return lm.lm_prefill(params, cfg, tokens, capacity)

        def decode(params, cache, token):
            return lm.lm_decode_step(params, cfg, cache, token)

        return prefill, decode
    if cfg.family == "audio":
        from repro.models import whisper

        def prefill(params, frames, tokens, capacity):
            return whisper.whisper_prefill(params, cfg, frames, tokens, capacity)

        def decode(params, cache, token):
            return whisper.whisper_decode_step(params, cfg, cache, token)

        return prefill, decode
    if cfg.family == "vlm":
        from repro.models import vlm

        # Warm the MSDA resampler plan at engine-build time: backend
        # resolution + block planning (+ autotune, if configured) happen
        # here, once, instead of inside the first prefill's trace.
        if warm_plans:
            warmup_msda_plans(cfg, dtype_policy=dtype_policy, tune=tune)

        def prefill(params, pyramid, tokens, capacity, *,
                    levels=None, valid_ratios=None):
            return vlm.vlm_prefill(params, cfg, pyramid, tokens, capacity,
                                   levels=levels, valid_ratios=valid_ratios,
                                   dtype_policy=dtype_policy, tune=tune)

        def decode(params, cache, token):
            return vlm.vlm_decode_step(params, cfg, cache, token)

        return prefill, decode
    raise ValueError(f"{cfg.family} has no serving path")


def warmup_msda_plans(cfg, *, dtype_policy: Optional[str] = None,
                      tune: Optional[str] = None, buckets=None, mesh=None):
    """Pre-build every MsdaPlan a serving process will execute.

    Returns the plans (empty tuple for pure-LM families) so callers can
    log ``plan.describe()``.  Idempotent: plans are cached by spec.

    ``dtype_policy`` overrides the config's ``msda.dtype_policy`` for
    every warmed plan (e.g. force ``"bfloat16"`` slabs fleet-wide, or
    ``"auto"`` so the warm-up absorbs the autotune fp32-vs-bf16 race —
    and its winner-cache disk write — instead of the first request).
    ``tune`` similarly overrides the config's tune mode (the sweep CLI
    forces "autotune").  ``buckets`` (vlm): warm one resampler plan per
    bucket geometry instead of the config's single pyramid — the set the
    bucketed batcher actually serves.  ``mesh``: warm DISTRIBUTED plans
    (the sharding ladder — incl. the 2D dp x tp mode — commits per plan
    at warm-up, exactly like blocks and slab dtypes).
    """
    plans = []
    if getattr(cfg, "vision", None) is not None:
        from repro.core import msda as msda_mod
        from repro.models import vlm

        vc = cfg.vision
        geometries = [vc.levels] if not buckets else [b.levels for b in buckets]
        for levels in geometries:
            mc = vlm._msda_cfg(vc, levels, dtype_policy=dtype_policy)
            plans.append(msda_mod.attention_plan(
                mc, num_queries=vc.num_visual_tokens,
                head_dim=vc.vision_dim // mc.num_heads, dtype=cfg.dtype,
                dtype_policy=dtype_policy, tune=tune, mesh=mesh))
    if getattr(cfg, "msda", None) is not None:
        from repro.core import deformable_transformer as dt

        plans.extend(
            dt.msda_plans(cfg, dtype=cfg.dtype, dtype_policy=dtype_policy,
                          tune=tune, mesh=mesh).values())
    return tuple(plans)


def clear_kernel_plans() -> None:
    """Drop cached MSDA plans + their compiled ops (long-lived servers).

    The plan cache is bounded, but a server that cycles through many
    model configs can still pin compiled executors; call this between
    model swaps to release them.
    """
    from repro.kernels import plan as plan_mod

    plan_mod.clear_plans()


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (S,) int32
    max_new: int
    # vlm: per-request image pyramid, flattened (S_v, vision_dim) fp32,
    # at its own geometry — the bucketed batcher pads it for admission
    pyramid: Optional[np.ndarray] = None
    levels: Optional[Tuple[Tuple[int, int], ...]] = None  # None -> config levels
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # resilience: per-request deadline in engine ticks (None inherits the
    # engine's ResilienceConfig.deadline_ticks); every request that
    # reaches a terminal state carries its typed ServeResponse
    deadline_ticks: Optional[int] = None
    submit_tick: int = -1
    response: Optional[ServeResponse] = None


def _pow2_batches(slots: int) -> Tuple[int, ...]:
    """The fixed set of admitted batch sizes: powers of two, plus the
    full slot count — bounds the number of compiled prefill variants."""
    sizes = {slots}
    b = 1
    while b <= slots:
        sizes.add(b)
        b *= 2
    return tuple(sorted(sizes))


def _batch_quantum(mesh) -> int:
    """Smallest legal batch for mesh-carrying plans (1 without a mesh).

    The 1D sharded modes ('query', 'head', 'batch') shard BATCH over the
    dp axes, so every batch that reaches a distributed plan must be a
    multiple of the dp width — the engine quantizes its admitted batch
    ladder to it rather than letting shard_map reject a size-1 prefill
    at request time."""
    if mesh is None:
        return 1
    from repro.sharding import rules

    return rules.axis_size(rules.resolve_axis("dp", mesh), mesh)


def _quantize_batches(sizes, quantum: int, slots: int) -> Tuple[int, ...]:
    """Round each admitted batch size up to the quantum, capped at the
    slot count (slots is asserted to be a multiple of the quantum)."""
    q = max(1, int(quantum))
    out = {min(slots, -(-int(b) // q) * q) for b in sizes}
    return tuple(sorted(out))


def _diff_axis(a, b) -> int:
    """First axis where two cache-leaf avals differ (-1: no batch axis)."""
    if a.shape == b.shape:
        return -1
    for ax in range(len(a.shape)):
        if a.shape[ax] != b.shape[ax]:
            return ax
    return -1


class ServeEngine:
    """Continuous-batching engine over a fixed slot pool (LM + VLM).

    Boot sequence (everything traffic-latency-critical happens here):

    1. plans   — restored from ``store_path`` when the store exists
       (zero autotune races; winners seeded from the store), else warmed
       fresh and persisted for the next boot.
    2. ``warmup()`` — AOT-compiles decode/prefill/plan executors so the
       first request triggers no trace and no XLA compile (with
       ``compile_cache_dir`` even the boot compiles are disk hits on a
       restart).
    3. traffic — ``submit()`` + ``run()``/``step()``.  Each tick:
       retire finished slots, admit queued requests into the freed
       slots (same tick), one batched decode.
    """

    def __init__(self, cfg, params, *, slots: int = 4, capacity: int = 256,
                 temperature: float = 0.0, seed: int = 0,
                 store_path: Optional[str] = None,
                 compile_cache_dir: Optional[str] = None,
                 dtype_policy: Optional[str] = None,
                 tune: Optional[str] = None,
                 buckets=None, metrics: Optional[ServeMetrics] = None,
                 mesh=None, exact_buckets: bool = False,
                 resilience: Optional[ResilienceConfig] = None,
                 max_queue: Optional[int] = None,
                 faults: Optional[FaultInjector] = None):
        from repro.models import lm

        if cfg.family not in _LM_FAMILIES + ("vlm",):
            raise ValueError(f"{cfg.family} has no engine path")
        self.cfg, self.params = cfg, params
        self.slots = slots
        self.capacity = capacity
        self.temperature = temperature
        self.rng = np.random.default_rng(seed)
        self.metrics = metrics or ServeMetrics()
        self.is_vlm = cfg.family == "vlm"
        self._occupant: List[Optional[Request]] = [None] * slots
        # the queue itself stays a deque; the BOUND is enforced by the
        # admission controller in submit() (sheds with a typed response
        # instead of growing without limit)
        self._queue: Deque[Request] = deque()
        if max_queue is not None:
            resilience = dataclasses.replace(
                resilience or ResilienceConfig(), max_queue=max_queue)
        self.resilience = resilience or ResilienceConfig()
        self.faults = faults
        eid = self.metrics._eid
        self._admission = AdmissionController(
            self.resilience.max_queue, engine=eid)
        self._decode_guard = GuardedExecutor(
            "decode",
            lambda p, c, t: self._aot.get("decode", self._decode_jit)(p, c, t),
            # degraded rung: bypass the AOT table and run the plain jit
            # decode (still the warmed program in the steady state, but
            # immune to a poisoned AOT executable)
            demote_fn=ladder_of([lambda p, c, t: self._decode_jit(p, c, t)]),
            policy=self.resilience, injector=faults, engine=eid)
        self._prefill_guard = GuardedExecutor(
            "prefill", lambda fn, *a: fn(*a),
            policy=self.resilience, injector=faults, engine=eid)
        self._plan_guards: Dict[int, GuardedExecutor] = {}

        if compile_cache_dir:
            persistence.enable_jax_compilation_cache(compile_cache_dir)

        # -- pyramid buckets (vlm) ----------------------------------------
        self.batcher = None
        self.buckets = ()
        if self.is_vlm:
            vc = cfg.vision
            if buckets is None:
                buckets = batcher_mod.default_buckets(
                    vc.levels, getattr(vc, "bucket_scales", (1.0,)))
            self.buckets = tuple(buckets)
            # serving's contract is the bounded, boot-compiled bucket set
            # and zero request-time retraces, so the engine opts into the
            # batcher's lossy (ulp-level rescale drift) padding for
            # non-pow2 geometry->bucket ratios; exact_buckets=True flips
            # the gate to exact-geometry buckets, paying one jit-fallback
            # compile per novel geometry instead
            self.batcher = batcher_mod.PyramidBatcher(
                self.buckets, lossy_ok=not exact_buckets)

        # -- plans: restore from the store, or warm fresh + persist -------
        # The meta gate covers every axis that changes which SPECS the
        # engine serves (arch, dtype policy, tune mode, bucket ladder,
        # mesh topology): restoring a store written under different axes
        # would AOT the wrong plans and re-race the right ones on a
        # nominally warm boot.
        from repro.kernels import plan as plan_mod

        self.mesh = mesh
        self._batch_q = _batch_quantum(mesh)
        if self.is_vlm and slots % self._batch_q:
            raise ValueError(
                f"slots={slots} must be a multiple of the mesh's dp width "
                f"{self._batch_q}: distributed plans shard batch over dp")
        self._store_meta = {
            "arch": cfg.name,
            "dtype_policy": dtype_policy or "follow",
            "tune": tune or "heuristic",
            "buckets": [b.key for b in self.buckets],
            "mesh": plan_mod.mesh_token(mesh) if mesh is not None else None,
        }
        # chaos: boot-time faults (corrupt_store) fire BEFORE the store
        # is read — a damaged store must degrade to a cold warm-up +
        # re-persist, which the meta-gated load below already does
        # (PlanStore.load() returns None for unreadable JSON)
        self.boot_faults: List[str] = (
            faults.apply_boot_faults(store_path) if faults is not None else [])
        self.store = persistence.PlanStore(store_path) if store_path else None
        self.restore_report = None
        self.store_meta_mismatch = False
        self.plans = ()
        existing = self.store.load() if self.store is not None else None
        if existing is not None:
            stored_meta = existing.get("meta", {})
            # v1 stores carry no "mesh" key: treat absent as None so a
            # mesh-less boot keeps restoring its pre-2D stores unchanged
            if all(stored_meta.get(k) == v for k, v in self._store_meta.items()):
                self.restore_report = self.store.restore(mesh=mesh)
                self.plans = tuple(self.restore_report.plans)
            else:
                self.store_meta_mismatch = True
        if not self.plans:
            self.plans = warmup_msda_plans(
                cfg, dtype_policy=dtype_policy, tune=tune,
                buckets=self.buckets or None, mesh=mesh)
            # Persist only onto an empty/unreadable path: a loadable store
            # whose meta doesn't match this boot belongs to a DIFFERENTLY
            # CONFIGURED fleet (e.g. a sweep artifact) — overwriting it
            # would silently destroy the plans every correctly-configured
            # server restores from.  Pure-LM families warm no MSDA plans
            # and never write a store at all.
            if self.store is not None and self.plans and existing is None:
                self.store.save_plans(self.plans, meta=self._store_meta)

        # -- model fns + cache --------------------------------------------
        dt = jnp.dtype(cfg.dtype)
        self.cache = lm.init_cache(cfg, slots, capacity, dt)
        # per-leaf batch axis, identified structurally (B=1 vs B=2 avals)
        # so splicing never guesses which axis is the slot axis
        s1 = jax.eval_shape(lambda: lm.init_cache(cfg, 1, capacity, dt))
        s2 = jax.eval_shape(lambda: lm.init_cache(cfg, 2, capacity, dt))
        self._batch_axes = jax.tree.map(_diff_axis, s1, s2)

        # one source of truth for the family closures (plans were warmed
        # above, bucket-aware — so skip make_serve_fns' own warm-up)
        self._serve_prefill, self._decode_model = make_serve_fns(
            cfg, dtype_policy=dtype_policy, tune=tune, warm_plans=False)
        if self.is_vlm:
            self._vlm_prefill_jit: Dict[tuple, Callable] = {}
        else:
            self._prefill_model = lambda p, t: self._serve_prefill(p, t, capacity)
            self._prefill_jit = jax.jit(aot.traced(self._prefill_model, "prefill"))
        self._decode_jit = jax.jit(aot.traced(self._decode_model, "decode"))
        self._aot: Dict[Any, aot.AotExecutor] = {}
        self.plan_executors: Dict[Any, aot.AotExecutor] = {}
        self._batch_ladder = _quantize_batches(
            _pow2_batches(slots), self._batch_q, slots)

    # -- AOT warm-up -------------------------------------------------------
    def _vlm_prefill_fn(self, bucket) -> Callable:
        prefill, capacity, levels = self._serve_prefill, self.capacity, bucket.levels
        mesh = self.mesh

        def f(params, pyramid, ratios, tokens):
            if mesh is None:
                return prefill(params, pyramid, tokens, capacity,
                               levels=levels, valid_ratios=ratios)
            # install the mesh at TRACE time: attention_plan resolves
            # the mesh via rules.current_mesh(), so without this the
            # request path would silently build fresh LOCAL plans while
            # the distributed plans the boot warmed/restored never
            # serve — the zero-retrace contract requires the prefill
            # trace to fetch exactly the warmed mesh-carrying plans
            from repro.sharding import rules

            with rules.use_mesh(mesh):
                return prefill(params, pyramid, tokens, capacity,
                               levels=levels, valid_ratios=ratios)

        return f

    def _vlm_prefill(self, bucket) -> Callable:
        """Jit fallback for a bucket (counts as a request-time trace)."""
        if bucket.levels not in self._vlm_prefill_jit:
            self._vlm_prefill_jit[bucket.levels] = jax.jit(aot.traced(
                self._vlm_prefill_fn(bucket), f"prefill[{bucket.key}]"))
        return self._vlm_prefill_jit[bucket.levels]

    def warmup(self, *, prompt_lengths: Tuple[int, ...] = (),
               batch_sizes: Optional[Tuple[int, ...]] = None,
               plan_batch_sizes: Tuple[int, ...] = (1,)) -> "ServeEngine":
        """AOT-compile every request-time executor, before traffic.

        Decode is always compiled; prefill per prompt length (vlm: per
        (bucket, admitted batch size, prompt length)); plus every warmed
        MsdaPlan's standalone executor (``self.plan_executors``).  After
        this, requests matching the warmed signatures run with zero
        traces/compiles — ``aot.probe()`` proves it.
        """
        tok = jax.ShapeDtypeStruct((self.slots,), jnp.int32)
        self._aot["decode"] = aot.aot_compile(
            self._decode_model, self.params, self.cache, tok, name="decode")
        if batch_sizes is None:
            batch_sizes = _pow2_batches(self.slots)
        # admission pads to THIS ladder — it must be exactly the warmed
        # set, or a padded batch size would miss the AOT table and hit
        # the jit fallback at request time.  Quantized to the mesh's dp
        # width: sizes a distributed plan cannot execute are never
        # compiled or admitted.
        batch_sizes = _quantize_batches(batch_sizes, self._batch_q, self.slots)
        self._batch_ladder = batch_sizes
        # standalone plan executors obey the same quantum (uncapped)
        plan_batch_sizes = tuple(sorted(
            {-(-int(b) // self._batch_q) * self._batch_q
             for b in plan_batch_sizes}))
        for L in prompt_lengths:
            if self.is_vlm:
                vd = self.cfg.vision.vision_dim
                nl = len(self.cfg.vision.levels)
                for bucket in self.buckets:
                    for B in batch_sizes:
                        self._aot[("prefill", bucket.levels, B, L)] = aot.aot_compile(
                            self._vlm_prefill_fn(bucket), self.params,
                            jax.ShapeDtypeStruct((B, bucket.tokens, vd), jnp.float32),
                            jax.ShapeDtypeStruct((B, nl, 2), jnp.float32),
                            jax.ShapeDtypeStruct((B, L), jnp.int32),
                            name=f"prefill[{bucket.key}|B={B}|L={L}]")
            else:
                self._aot[("prefill", 1, L)] = aot.aot_compile(
                    self._prefill_model, self.params,
                    jax.ShapeDtypeStruct((1, L), jnp.int32), name=f"prefill[L={L}]")
        self.plan_executors = aot.compile_plan_executors(self.plans, plan_batch_sizes)
        return self

    # -- request lifecycle -------------------------------------------------
    def submit(self, req: Request) -> Optional[ServeResponse]:
        """Enqueue a request, or SHED it with a typed response.

        Returns the shed response when admission rejects (queue at
        ``resilience.max_queue``); None when accepted — the terminal
        response then lands on ``req.response`` when the request
        finishes, times out, or fails.
        """
        req.submit_tick = self.metrics.ticks
        if req.deadline_ticks is None:
            req.deadline_ticks = self.resilience.deadline_ticks
        if not self._admission.admit(self.pending):
            req.response = ServeResponse(
                "shed", req.rid,
                detail=f"queue at capacity ({self.resilience.max_queue})")
            req.done = True
            self.metrics.record_shed(req.rid)
            return req.response
        if self.is_vlm:
            if req.pyramid is None:
                raise ValueError("vlm requests need a pyramid")
            levels = req.levels or self.cfg.vision.levels
            # may reject (fits no bucket) — count only accepted requests
            self.batcher.submit(req.pyramid, levels, req,
                                group_key=len(req.prompt))
        else:
            self._queue.append(req)
        self.metrics.record_submit(req.rid)
        return None

    @property
    def pending(self) -> int:
        return len(self._queue) + (len(self.batcher) if self.batcher else 0)

    def _free_slots(self) -> List[int]:
        return [s for s, r in enumerate(self._occupant) if r is None]

    def _retire(self):
        """Free slots of finished requests — runs at the top of each
        tick, before admission, so a freed slot is re-filled and decoded
        in the SAME tick instead of idling one.  (Completion itself is
        metered at done-marking time, so metrics don't need a trailing
        tick to see the last requests finish.)"""
        for s, req in enumerate(self._occupant):
            if req is not None and req.done:
                self._occupant[s] = None

    def _finish(self, req: Request):
        req.done = True
        req.response = ServeResponse("ok", req.rid, tokens=tuple(req.out))
        self.metrics.record_retire(req.rid)

    def _fail(self, req: Request, status: str, detail: str):
        """Resolve a request with a non-ok typed response."""
        req.done = True
        req.response = ServeResponse(status, req.rid, detail=detail)
        if status == "timeout":
            self.metrics.record_deadline_miss(req.rid)
        else:
            self.metrics.record_exec_error(req.rid)

    def _deadline_expired(self, req: Request) -> bool:
        return (req.deadline_ticks is not None and req.submit_tick >= 0
                and self.metrics.ticks - req.submit_tick >= req.deadline_ticks)

    def _sweep_deadlines(self):
        """Resolve every expired request — queued, bucketed, or
        in-flight — with a typed timeout response.  Runs at the top of
        each tick, before admission, so an expired queued request is
        never admitted late."""
        expired: List[Request] = []
        if self._queue:
            keep: Deque[Request] = deque()
            for req in self._queue:
                (expired if self._deadline_expired(req) else keep).append(req)
            self._queue = keep
        if self.batcher is not None:
            expired.extend(self.batcher.expire(self._deadline_expired))
        for s, req in enumerate(self._occupant):
            if req is not None and not req.done and self._deadline_expired(req):
                expired.append(req)
                self._occupant[s] = None  # the cache row just goes stale
        for req in expired:
            self._fail(req, "timeout",
                       f"deadline of {req.deadline_ticks} ticks exceeded "
                       f"(submitted at tick {req.submit_tick})")

    def guarded_plan(self, i: int = 0, *,
                     policy: Optional[ResilienceConfig] = None,
                     injector: Optional[FaultInjector] = None
                     ) -> GuardedExecutor:
        """The per-plan circuit breaker for warmed plan ``i`` —
        retries, then demotes down ``MsdaPlan.fallback()`` (sparse ->
        dense -> ref) and probes the primary on
        the half-open schedule.  Built on first use; clean runs build
        nothing."""
        if i not in self._plan_guards:
            self._plan_guards[i] = resil_mod.guard_plan(
                self.plans[i], policy or self.resilience, mesh=self.mesh,
                injector=injector if injector is not None else self.faults,
                engine=self.metrics._eid)
        return self._plan_guards[i]

    def resilience_state(self) -> Dict[str, Any]:
        """Machine-readable resilience block (smoke + bench artifact)."""
        guards = [self._decode_guard, self._prefill_guard,
                  *self._plan_guards.values()]
        out = resil_mod.resilience_snapshot(guards, self._admission)
        out["deadline_misses"] = self.metrics.deadline_misses
        out["exec_errors"] = self.metrics.exec_errors
        out["stragglers"] = self.metrics.stragglers
        out["boot_faults"] = list(self.boot_faults)
        if self.faults is not None:
            out["fault_log"] = [dict(d) for d in self.faults.log]
        return out

    def _splice_slot(self, new_cache, src_row: int, slot: int):
        """Copy row ``src_row`` of a (possibly batched) prefill cache
        into slot ``slot`` of the engine cache, axis-mapped per leaf."""

        def splice(big, new, ax):
            if ax < 0:
                return new  # shared leaves (pos counters) track the prefill
            src = [slice(None)] * new.ndim
            src[ax] = slice(src_row, src_row + 1)
            dst = [slice(None)] * big.ndim
            dst[ax] = slice(slot, slot + 1)
            return big.at[tuple(dst)].set(new[tuple(src)])

        self.cache = jax.tree.map(splice, self.cache, new_cache, self._batch_axes)

    def _admit(self):
        if self.is_vlm:
            return self._admit_vlm()
        free = self._free_slots()
        while free and self._queue:
            req = self._queue.popleft()
            L = len(req.prompt)
            fn = self._aot.get(("prefill", 1, L), self._prefill_jit)
            try:
                logits, cache1 = self._prefill_guard.call(
                    fn, self.params, jnp.asarray(req.prompt[None, :]))
            except ExecutorFailure as e:
                self._fail(req, "error", str(e))
                continue
            s = free.pop(0)
            self._splice_slot(cache1, 0, s)
            req.out.append(self._sample(np.asarray(logits)[0]))
            if len(req.out) >= req.max_new:
                self._finish(req)
            self._occupant[s] = req
            self.metrics.record_admit(req.rid, "lm",
                                      real_tokens=L, padded_tokens=L)

    def _admit_vlm(self):
        free = self._free_slots()
        while free and len(self.batcher):
            batch = self.batcher.next_batch(min(len(free), max(self._batch_ladder)))
            reqs = batch.items
            B = len(reqs)
            # pad the admitted batch to the next planned size so prefill
            # executes one of the boot-compiled variants, never a fresh one
            Bp = next(b for b in self._batch_ladder if b >= B)
            feats, ratios = batch.feats, batch.ratios
            tokens = np.stack([r.prompt for r in reqs]).astype(np.int32)
            if Bp > B:
                pad = Bp - B
                feats = np.concatenate(
                    [feats, np.zeros((pad,) + feats.shape[1:], feats.dtype)])
                ratios = np.concatenate(
                    [ratios, np.ones((pad,) + ratios.shape[1:], ratios.dtype)])
                tokens = np.concatenate(
                    [tokens, np.zeros((pad, tokens.shape[1]), tokens.dtype)])
            key = ("prefill", batch.bucket.levels, Bp, tokens.shape[1])
            fn = self._aot.get(key) or self._vlm_prefill(batch.bucket)
            try:
                logits, cache_b = self._prefill_guard.call(
                    fn, self.params, jnp.asarray(feats),
                    jnp.asarray(ratios), jnp.asarray(tokens))
            except ExecutorFailure as e:
                for req in reqs:
                    self._fail(req, "error", str(e))
                continue
            if self.mesh is not None:
                # a mesh-carrying prefill commits its outputs to the
                # mesh (NamedSharding); decode is a single-device AOT
                # executable, so pull the (replicated) cache rows back
                # before they are spliced into the decode cache
                dev = jax.devices()[0]
                cache_b = jax.tree.map(lambda x: jax.device_put(x, dev), cache_b)
            logits = np.asarray(logits)
            for i, req in enumerate(reqs):
                s = free.pop(0)
                self._splice_slot(cache_b, i, s)
                req.out.append(self._sample(logits[i]))
                if len(req.out) >= req.max_new:
                    self._finish(req)
                self._occupant[s] = req
            self.metrics.record_admit(
                [r.rid for r in reqs], batch.bucket.key,
                real_tokens=batch.real_tokens,
                padded_tokens=Bp * batch.bucket.tokens)

    def _sample(self, logits: np.ndarray) -> int:
        if self.temperature <= 0:
            return int(logits.argmax())
        p = np.exp((logits - logits.max()) / self.temperature)
        p /= p.sum()
        return int(self.rng.choice(len(p), p=p))

    def step(self):
        """One engine tick: faults, retire, deadline sweep, admit
        (into freed slots), batched decode (guarded)."""
        if self.faults is not None:
            ev = self.faults.begin_tick(self.metrics.ticks)
            if ev is not None and ev.kind == "straggler":
                if self.faults.straggler_s > 0:
                    time.sleep(self.faults.straggler_s)
                self.metrics.record_straggler()
        self._retire()
        self._sweep_deadlines()
        self._admit()
        self._admission.observe(self.pending)
        tok = np.zeros((self.slots,), np.int32)
        active = [s for s, r in enumerate(self._occupant)
                  if r is not None and not r.done]
        for s in active:
            tok[s] = self._occupant[s].out[-1]
        if not active:
            return False
        try:
            logits, self.cache = self._decode_guard.call(
                self.params, self.cache, jnp.asarray(tok))
        except ExecutorFailure as e:
            # the whole batched step failed past every retry and rung:
            # resolve the in-flight requests with typed errors (the tick
            # still counts — time passed)
            self.metrics.record_tick()
            for s in active:
                self._fail(self._occupant[s], "error", str(e))
                self._occupant[s] = None
            return True
        logits = np.asarray(logits)
        self.metrics.record_tick()
        self.metrics.record_decode(len(active))
        for s in active:
            req = self._occupant[s]
            req.out.append(self._sample(logits[s]))
            if len(req.out) >= req.max_new:
                self._finish(req)
        return True

    def run(self, max_ticks: int = 10_000) -> None:
        for _ in range(max_ticks):
            if not self.step() and not self.pending:
                break
        self._retire()

    def shutdown(self) -> None:
        """Release compiled kernel plans (see :func:`clear_kernel_plans`)."""
        clear_kernel_plans()
