"""Plan/execute API for multi-scale deformable attention.

Contract (the one rule of the system — ``docs/architecture.md``):
every hardware-aware decision is committed HERE, at plan time, and
execution only executes.  ``MsdaSpec`` (frozen geometry) resolves via
``msda_plan`` into an ``MsdaPlan`` carrying the backend, the query
block of its one Pallas launch per direction over the packed pyramid
(heuristic fitting model or autotuned race, winners persisted per
device kind), the per-level slab dtypes, and — when a mesh is given — the sharding
mode: the 1D query/head/batch ladder or the 2D dp x tp query tiling
with ring-reduced grad_value slabs, plus the raced ring-vs-psum
grad_value reduction (``docs/sharding.md``).  Plans live in a bounded
LRU; ``plan.describe()`` states everything that was committed.

The paper's central observation is that MSDA gets fast only when the
*static* problem geometry — level shapes, points, head dim, the VMEM
budget — is exploited ahead of time: adaptive vec-len planning (Fig. 7)
is a compile-time decision.  This module makes those decisions a
first-class artifact:

* :class:`MsdaSpec` — frozen, hashable description of one MSDA problem
  (spatial shapes, heads, head dim, points, queries, dtype, train flag,
  per-device VMEM budget, and the precision policy: ``slab_dtype`` /
  ``accum_dtype`` — bf16 slabs with fp32 accumulation are a *planned*
  variant, not a call-site cast).
* :func:`msda_plan` — resolves a backend through the registry
  (``repro.kernels.registry``), computes block sizes **and per-level
  slab dtypes** once (heuristic, or measured via ``tune="autotune"``
  which races fp32-vs-bf16 per level, with an on-disk winner cache),
  bakes in ``shard_map`` wiring when a mesh is given, and returns a
  :class:`MsdaPlan`.
* :class:`MsdaPlan` — the executable artifact: ``plan(value, loc, attn)``
  runs the op (differentiable; the custom VJP was built at plan time) and
  ``plan.describe()`` reports the launch's ``block_q``, per-level slab
  bytes, the committed slab dtype and VMEM occupancy.

Plans are cached in an explicit, bounded LRU (:func:`clear_plans`,
:func:`plan_cache_info`) — repeated calls with an identical spec return
the *same* plan object and never re-run block planning.  The legacy
9-kwarg ``ops.msda(...)`` entry point is now a thin shim over this cache.

Typical use::

    from repro.kernels import plan as msda_plan_mod

    spec = msda_plan_mod.MsdaSpec(
        spatial_shapes=((64, 64), (32, 32)), num_heads=8, head_dim=32,
        num_points=4, num_queries=5120, dtype="float32", train=True)
    plan = msda_plan_mod.msda_plan(spec, backend="pallas")
    print(plan.describe())
    out = plan(value, loc, attn)        # (B, Q, H*D), differentiable
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import time
from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.obs import registry as _obs
from repro.obs import trace as _obs_trace

Shapes = Tuple[Tuple[int, int], ...]

_SUBLANE = 8

SPARSITY_CHOICES = ("off", "topk", "auto")
QUERY_ORDER_CHOICES = ("identity", "morton", "auto")


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# --------------------------------------------------------------------------
# per-device VMEM budgets (satellite: budget is a spec field, defaulted by
# device kind, so plans for larger-VMEM parts stop under-blocking)
# --------------------------------------------------------------------------

# substring of jax.Device.device_kind (lowercased) -> usable per-core bytes,
# which plans also hand Mosaic as its scoped-VMEM limit.  TPU v5e: 128 MiB
# of VMEM per TensorCore; 100 MiB is what the paper-width DETR plans were
# compiled against (tests/test_tpu_compile.py), the rest is left to
# Mosaic's internal scratch.  The other entries are conservative planning
# budgets that no chip run here has checked.
DEVICE_VMEM_BUDGETS: Tuple[Tuple[str, int], ...] = (
    ("v6", 64 * 2**20),  # trillium-class
    ("v5p", 64 * 2**20),
    ("v5 lite", 100 * 2**20),
    ("v5e", 100 * 2**20),
    ("v4", 32 * 2**20),
    ("v3", 16 * 2**20),
    ("v2", 16 * 2**20),
)
# the Pallas interpreter (CPU hosts) has no VMEM; plans there keep the
# v5e figure, so a pyramid the chip holds plans on a CPU too, tiled alike
_INTERPRET_VMEM_BUDGET = 100 * 2**20


def default_vmem_budget(device_kind: Optional[str] = None) -> int:
    """Usable VMEM bytes for block planning, by accelerator kind.

    With no ``device_kind`` the first JAX device decides.  A TPU whose
    kind is not in :data:`DEVICE_VMEM_BUDGETS` is an error: planning it
    against a guessed budget would only fail later, in Mosaic, or waste
    the part.  Non-TPU hosts (the interpreter) get a nominal budget.
    """
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    kind = device_kind.lower()
    for sub, budget in DEVICE_VMEM_BUDGETS:
        if sub in kind:
            return budget
    if "tpu" in kind:
        raise ValueError(
            f"no VMEM budget for TPU kind {device_kind!r}; add it to "
            "repro.kernels.plan.DEVICE_VMEM_BUDGETS or set vmem_budget")
    return _INTERPRET_VMEM_BUDGET


# --------------------------------------------------------------------------
# MsdaSpec
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class MsdaSpec:
    """Static geometry of one MSDA problem (hashable; the plan-cache key).

    ``vmem_budget=0`` resolves to :func:`default_vmem_budget` for the
    current device at construction time, so the budget is always an
    explicit, inspectable number on the spec.
    """

    spatial_shapes: Shapes
    num_heads: int
    head_dim: int
    num_points: int
    num_queries: int
    dtype: str = "float32"
    train: bool = False
    vmem_budget: int = 0  # 0 -> per-device default
    # -- precision policy (the second planned axis) -----------------------
    # slab_dtype: dtype each level's values are ROUNDED to before they
    # enter the (always fp32) VMEM slab.  '' follows the operand dtype;
    # 'auto' lets tune="autotune" race fp32 vs bf16 per level; any
    # concrete dtype pins it.  The Pallas slab stays fp32 either way
    # (bf16 rows cannot be addressed singly in a packed tile), so a bf16
    # slab saves residency nowhere: it only halves a train plan's saved
    # corners (and their per-query output block).  accum_dtype: the
    # widened accumulator of the ``cpu`` executor and of the sharded
    # grad_value reduction, which level_report sizes the grad slab by;
    # the Pallas kernels always accumulate in fp32.
    slab_dtype: str = ""
    accum_dtype: str = "float32"
    # -- sparsity (the third planned axis) --------------------------------
    # 'off' executes dense MSDA exactly as before (bitwise-identical
    # plans); 'topk' pins the pruned executor — keep the sparsity_k
    # highest-weight (level, point) cells per query, renormalise, gather
    # only the surviving corners (DEFA-style point pruning; LOSSY, with
    # its own conformance tolerance tier); 'auto' lets tune="autotune"
    # race pruned-vs-dense (fwd+VJP for train specs) and stays dense
    # under the heuristic — a lossy mode is never picked untimed.
    sparsity: str = "off"
    # cells kept per query under 'topk'; 0 -> ceil(L*P / 2), always
    # clamped to L*P (see resolved_sparsity_k)
    sparsity_k: int = 0
    # -- query ordering (the fourth planned axis) -------------------------
    # 'morton' permutes queries into reference-pixel Z-curve order at the
    # executor boundary (inverted on output) so near-in-space queries
    # gather near-in-slab corners (QUILL-style locality).  Bitwise-
    # neutral to the forward and the loc/attn grads; only engages when
    # the query grid IS the pixel grid (Q == S, the encoder layout).
    # 'auto' races permuted-vs-identity under autotune.
    query_order: str = "identity"

    def __post_init__(self):
        shapes = tuple((int(h), int(w)) for h, w in self.spatial_shapes)
        object.__setattr__(self, "spatial_shapes", shapes)
        object.__setattr__(self, "dtype", str(jnp.dtype(self.dtype)))
        if self.slab_dtype not in ("", "auto"):
            object.__setattr__(self, "slab_dtype", str(jnp.dtype(self.slab_dtype)))
        object.__setattr__(self, "accum_dtype", str(jnp.dtype(self.accum_dtype)))
        if self.sparsity not in SPARSITY_CHOICES:
            raise ValueError(
                f"unknown sparsity {self.sparsity!r}; "
                f"one of {SPARSITY_CHOICES}")
        if self.sparsity_k < 0:
            raise ValueError(f"sparsity_k must be >= 0, got {self.sparsity_k}")
        if self.query_order not in QUERY_ORDER_CHOICES:
            raise ValueError(
                f"unknown query_order {self.query_order!r}; "
                f"one of {QUERY_ORDER_CHOICES}")
        if self.vmem_budget <= 0:
            object.__setattr__(self, "vmem_budget", default_vmem_budget())

    # -- derived ----------------------------------------------------------
    @property
    def num_levels(self) -> int:
        return len(self.spatial_shapes)

    @property
    def total_pixels(self) -> int:
        return sum(h * w for h, w in self.spatial_shapes)

    @property
    def value_itemsize(self) -> int:
        return jnp.dtype(self.dtype).itemsize

    def resolved_slab_dtype(self) -> str:
        """The slab storage dtype before any per-level autotune override
        ('' and 'auto' fall back to the operand dtype)."""
        if self.slab_dtype in ("", "auto"):
            return self.dtype
        return self.slab_dtype

    @property
    def slab_itemsize(self) -> int:
        return jnp.dtype(self.resolved_slab_dtype()).itemsize

    @property
    def accum_itemsize(self) -> int:
        return jnp.dtype(self.accum_dtype).itemsize

    @property
    def heads_per_launch(self) -> int:
        """Heads one kernel launch holds side by side on its lanes."""
        from repro.kernels import msda_fwd

        return msda_fwd.head_group(self.num_heads, self.head_dim)

    def resolved_sparsity_k(self) -> int:
        """Cells kept per query when the pruned executor runs (0 pins
        the half-the-cells default; always clamped to the cell count)."""
        cells = self.num_levels * self.num_points
        k = self.sparsity_k if self.sparsity_k > 0 else max(1, -(-cells // 2))
        return min(k, cells)

    def cache_token(self) -> str:
        """Stable string key (autotune disk cache)."""
        f = dataclasses.astuple(self)
        return "|".join(str(x) for x in f)


def spec_to_json(spec: MsdaSpec) -> Dict[str, Any]:
    """JSON-serialisable dict for ``spec`` (plan store / sweep tooling)."""
    d = dataclasses.asdict(spec)
    d["spatial_shapes"] = [[int(h), int(w)] for h, w in spec.spatial_shapes]
    return d


# spec fields and winner keys of plan choices no build makes any more
# (the fusion tiers, the corner-major walks, the one-hot row route and
# the fixed-block ablation): stores and winner caches that carry them
# are read with the keys dropped
RETIRED_KEYS = ("fuse_levels", "fuse_prefix", "fuse_gather", "fuse_scatter",
                "adaptive_block", "onehot_small_levels", "onehot_levels")


def spec_from_json(d: Dict[str, Any]) -> MsdaSpec:
    """Inverse of :func:`spec_to_json`.  :data:`RETIRED_KEYS` are
    dropped; other unknown keys raise — the plan store is versioned, so
    a field this build doesn't know means the entry was written by a
    newer schema and must not be half-loaded."""
    d = {k: v for k, v in d.items() if k not in RETIRED_KEYS}
    known = {f.name for f in dataclasses.fields(MsdaSpec)}
    unknown = sorted(set(d) - known)
    if unknown:
        raise ValueError(f"unknown MsdaSpec fields {unknown}")
    d["spatial_shapes"] = tuple((int(h), int(w)) for h, w in d["spatial_shapes"])
    return MsdaSpec(**d)


# dtype-policy knob (configs' ``msda.dtype_policy``) -> spec fields.
# 'follow' keeps the operand dtype; 'bfloat16' commits bf16 slabs with
# fp32 accumulation; 'auto' defers the per-level choice to autotune.
DTYPE_POLICIES: Dict[str, Tuple[str, str]] = {
    "follow": ("", "float32"),
    "float32": ("float32", "float32"),
    "bfloat16": ("bfloat16", "float32"),
    "auto": ("auto", "float32"),
}


def resolve_dtype_policy(policy: str) -> Tuple[str, str]:
    """Map a policy name to ``(slab_dtype, accum_dtype)`` spec fields."""
    try:
        return DTYPE_POLICIES[policy]
    except KeyError:
        raise ValueError(
            f"unknown msda dtype policy {policy!r}; one of {sorted(DTYPE_POLICIES)}"
        ) from None


def spec_from_arrays(
    value: jax.Array,
    spatial_shapes: Shapes,
    sampling_locations: jax.Array,
    attention_weights: jax.Array,
    *,
    train: bool = False,
    **overrides: Any,
) -> MsdaSpec:
    """Build the spec for concrete operands (the shim's entry path)."""
    del attention_weights  # shapes implied by loc
    B, S, H, D = value.shape
    Q, P = sampling_locations.shape[1], sampling_locations.shape[4]
    return MsdaSpec(
        spatial_shapes=tuple((int(h), int(w)) for h, w in spatial_shapes),
        num_heads=int(H),
        head_dim=int(D),
        num_points=int(P),
        num_queries=int(Q),
        dtype=str(value.dtype),
        train=train,
        **overrides,
    )


# --------------------------------------------------------------------------
# PlanTuning: the decisions a backend builder receives
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class PlanTuning:
    """Resolved per-plan tuning knobs handed to the backend builder."""

    # the launch's query block, one entry per level (all equal: the one
    # launch shares it across the packed pyramid)
    block_q: Tuple[int, ...]
    interpret: bool
    source: str = "heuristic"  # heuristic | autotune | autotune-cache | override
    # per-level committed slab storage dtype; () -> the spec's resolved
    # slab dtype for every level (autotune may mix fp32/bf16 per level)
    slab_dtypes: Tuple[str, ...] = ()
    # committed sparsity decision: 'dense' runs the backend executor
    # unchanged; 'topk' swaps in the pruned top-k gather executor
    sparsity: str = "dense"
    # committed query ordering: 'morton' wraps the executor in the
    # Z-curve permutation (inverted on output); 'identity' leaves it
    query_order: str = "identity"


def _default_slab_dtypes(spec: MsdaSpec) -> Tuple[str, ...]:
    return (spec.resolved_slab_dtype(),) * spec.num_levels


def _resolve_sparsity(spec: MsdaSpec) -> str:
    """Pin/heuristic side of the sparsity rung: only an explicit 'topk'
    commits the lossy executor without a timing race ('auto' stays
    dense until autotune measures a win)."""
    return "topk" if spec.sparsity == "topk" else "dense"


def _resolve_query_order(spec: MsdaSpec) -> str:
    """Pin/heuristic side of the ordering rung: a 'morton' pin engages
    only on eligible (Q == S) geometry — anything else stays identity,
    truthfully recorded in the tuning."""
    from repro.kernels import msda_sparse

    if spec.query_order == "morton" and msda_sparse.morton_eligible(spec):
        return "morton"
    return "identity"


def _apply_sparsity_wrappers(exec_fn: Callable, spec: MsdaSpec,
                             sparsity: str, query_order: str) -> Callable:
    """Commit the resolved sparsity/ordering decisions onto an executor.
    'dense' + 'identity' returns ``exec_fn`` untouched — the
    ``sparsity="off"`` path stays byte-identical to pre-sparsity plans."""
    from repro.kernels import msda_sparse

    if sparsity == "topk":
        exec_fn = msda_sparse.build_topk_exec(spec)
    if query_order == "morton":
        exec_fn = msda_sparse.wrap_query_permutation(
            exec_fn, spec.spatial_shapes)
    return exec_fn


def check_fit(spec: MsdaSpec) -> None:
    """Refuse, at plan time, a spec whose packed pyramid plus one
    sublane step of queries does not fit its VMEM budget: the Pallas
    kernels keep the whole pyramid resident, and there is no other
    schedule to fall back to."""
    from repro.kernels import ops

    need = ops.min_launch_bytes(
        spec.spatial_shapes, spec.num_points, spec.head_dim,
        value_itemsize=spec.slab_itemsize, train=spec.train,
        heads=spec.heads_per_launch)
    if need > spec.vmem_budget:
        raise ValueError(
            f"the packed pyramid of {spec.spatial_shapes} and one query "
            f"step need {need} bytes of VMEM, over the budget of "
            f"{spec.vmem_budget} bytes: no launch fits")


# --------------------------------------------------------------------------
# built-in backends
# --------------------------------------------------------------------------


@registry.backend("ref")
def _build_ref(spec: MsdaSpec, tuning: PlanTuning) -> Callable:
    """Pure-jnp oracle; tuning is irrelevant (XLA fuses it on its own)."""
    from repro.kernels import ref

    shapes = spec.spatial_shapes

    def run(value, loc, attn):
        return ref.msda_ref(value, shapes, loc, attn)

    return run


@registry.backend("pallas")
def _build_pallas(spec: MsdaSpec, tuning: PlanTuning) -> Callable:
    """xMSDA Pallas kernels with the plan's committed tiling + dtypes."""
    from repro.kernels import ops

    params = ops.MSDAParams(
        spatial_shapes=spec.spatial_shapes,
        block_q=int(tuning.block_q[0]),
        save_sampled=spec.train,
        interpret=tuning.interpret,
        slab_dtypes=tuple(tuning.slab_dtypes) or _default_slab_dtypes(spec),
        vmem_limit=spec.vmem_budget,
    )
    return ops.build_kernel_op(params)


@registry.backend("cpu")
def _build_cpu(spec: MsdaSpec, tuning: PlanTuning) -> Callable:
    """CPU-vectorised backend: one vmapped fused gather per level."""
    from repro.kernels import msda_cpu

    return msda_cpu.build_cpu_exec(spec, tuning)


# --------------------------------------------------------------------------
# tuning resolution (heuristic / autotune / override)
# --------------------------------------------------------------------------


def _heuristic_block_q(spec: MsdaSpec,
                       slab_dtypes: Tuple[str, ...]) -> Tuple[int, ...]:
    """The heuristic block of the launch with the committed per-level
    slab dtypes, one entry per level."""
    from repro.kernels import ops

    bq = ops.plan_blocks(
        spec.spatial_shapes, spec.num_points, spec.head_dim,
        spec.num_queries,
        value_itemsize=tuple(jnp.dtype(d).itemsize for d in slab_dtypes),
        train=spec.train, vmem_budget=spec.vmem_budget,
        heads=spec.heads_per_launch)
    return (bq,) * spec.num_levels


# process-wide autotune activity counters.  "raced" counts specs whose
# candidates were actually TIMED this process; a serving boot restored
# from a plan store must keep it at zero (the CI smoke job asserts it).
# "raced" counts every timing race; "raced_local" only the per-shard
# block/dtype/sparsity races, "raced_mesh" only the mesh-keyed
# sharding / grad_reduce races — the elastic restore path asserts a
# mesh-resized restart re-races EXACTLY the mesh-keyed axes
# (raced_local == 0) against this split.
_AUTOTUNE_STATS = {
    "raced": _obs.counter("msda.autotune.raced",
                          help="autotune races actually timed"),
    "raced_local": _obs.counter("msda.autotune.raced_local",
                                help="per-shard block/dtype races"),
    "raced_mesh": _obs.counter("msda.autotune.raced_mesh",
                               help="mesh-keyed sharding/grad_reduce races"),
    "cache_hits": _obs.counter("msda.winner_cache.hits",
                               help="on-disk autotune winner-cache hits"),
    "seeded": _obs.counter("msda.winner_cache.seeded",
                           help="winners installed without racing"),
}
# the winner-cache flip side: a consulted entry that was absent or
# unparseable (-> a timing race follows).  Not part of the historical
# autotune_stats() shape; read it via execution_telemetry().
_WINNER_CACHE_MISSES = _obs.counter(
    "msda.winner_cache.misses",
    help="on-disk winner lookups that found no usable entry")

# plan-execution telemetry: every MsdaPlan.__call__ whose Python body
# runs (eagerly, or once per jit trace / AOT boot compile) attributes
# its STATIC per-call launch schedule here — a zero-retrace serving
# steady state therefore adds zero, which is the invariant the smoke
# job audits.  Train plans attribute fwd+bwd together (the backward is
# wired into the same custom-VJP call).
_PLAN_CALLS = _obs.counter(
    "msda.plan_calls", help="MsdaPlan invocations (eager or traced)")
_LAUNCHES = _obs.counter(
    "msda.launches",
    help="Pallas launches attributed per direction "
         "(static schedule x plan invocations)")
_VMEM_GAUGE = _obs.gauge(
    "msda.vmem_frac",
    help="per-level VMEM occupancy of the most recently built plan "
         "(kind=committed|predicted)")


def autotune_stats() -> Dict[str, int]:
    return {k: int(c.value()) for k, c in _AUTOTUNE_STATS.items()}


def reset_autotune_stats() -> None:
    for c in _AUTOTUNE_STATS.values():
        c.reset()
    _WINNER_CACHE_MISSES.reset()


def autotune_cache_path() -> str:
    """On-disk winner cache (override via REPRO_MSDA_AUTOTUNE_CACHE)."""
    env = os.environ.get("REPRO_MSDA_AUTOTUNE_CACHE")
    if env:
        return env
    base = os.environ.get("XDG_CACHE_HOME", os.path.expanduser("~/.cache"))
    return os.path.join(base, "repro", "msda_autotune.json")


def _load_autotune_cache() -> Dict[str, Any]:
    path = autotune_cache_path()
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _store_autotune_cache(cache: Dict[str, Any]) -> None:
    path = autotune_cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(cache, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass  # read-only FS: autotune still works, winners just aren't kept


def _autotune_inputs(spec: MsdaSpec, batch: int = 1):
    """Deterministic synthetic operands at the spec's exact geometry.

    All three operands honour ``spec.dtype``: timing a bf16 spec with
    fp32 operands would trace (and cache a winner for) a *different*
    program than the one real calls execute — the casts, slab residency
    and gather widths all change with the operand dtype.

    ``batch``: the sharding race times full shard_mapped executors, and
    the 1D candidate shards batch over dp — so it asks for B = dp_size.
    """
    B = batch
    S, H, D = spec.total_pixels, spec.num_heads, spec.head_dim
    Q, L, P = spec.num_queries, spec.num_levels, spec.num_points
    dt = jnp.dtype(spec.dtype)
    value = jnp.linspace(-1.0, 1.0, B * S * H * D, dtype=jnp.float32)
    value = value.reshape(B, S, H, D).astype(dt)
    loc = jnp.linspace(0.05, 0.95, B * Q * H * L * P * 2, dtype=jnp.float32)
    loc = loc.reshape(B, Q, H, L, P, 2).astype(dt)
    attn = jnp.full((B, Q, H, L, P), 1.0 / (L * P), jnp.float32).astype(dt)
    return value, loc, attn


# a candidate must win the interleaved median by this relative margin to
# replace the incumbent — sub-noise deltas must not get PERSISTED into the
# per-device winner cache (shared runners drift 2-3x between sequential
# timing blocks; interleaving cancels most of it, the margin eats the rest)
_AUTOTUNE_MARGIN = 0.05


def _time_executors(fns: Dict[Any, Callable], args, iters: int = 3) -> Dict[Any, float]:
    """Median seconds/call per candidate, measured ALTERNATELY.

    ``fns`` values must already be jitted + warmed.  Interleaving puts
    every candidate under the same machine-load profile, so the medians
    stay comparable — sequential per-candidate blocks let load drift
    masquerade as a tuning delta.
    """
    times: Dict[Any, List[float]] = {k: [] for k in fns}
    for _ in range(iters):
        for k, f in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(f(*args))
            times[k].append(time.perf_counter() - t0)
    return {k: sorted(ts)[len(ts) // 2] for k, ts in times.items()}


# backends whose executors ignore block_q (nothing to race on that axis)
_BLOCKLESS_BACKENDS = frozenset({"ref", "cpu"})

# the two slab dtypes autotune races per level under the 'auto' policy
_SLAB_DTYPE_CANDIDATES = ("float32", "bfloat16")


# every field the winner-entry schema knows how to validate; anything
# else a cache entry carries (but :data:`RETIRED_KEYS`) was written by a
# NEWER build and must ride through this build's parse/rewrite cycle
# untouched (the "extras" dict)
_WINNER_FIELDS = ("block_q", "slab_dtypes", "sharding", "grad_reduce",
                  "sparsity", "query_order")


def _parse_cache_entry(hit, spec: MsdaSpec) -> Optional[Dict[str, Any]]:
    """Decode a winner-cache entry into the normalised winner dict.

    Returns ``{"block_q": tuple, "slab_dtypes": tuple, "sharding":
    None|'1d'|'2d'|'hybrid', "grad_reduce": None|'ring'|'psum',
    "sparsity": None|'dense'|'topk', "query_order":
    None|'identity'|'morton', "extras": dict}`` or ``None`` on a miss.
    The ``sharding``/``grad_reduce`` fields live on mesh-keyed entries
    (the 1D-vs-2D and ring-vs-psum races of distributed plans);
    ``sparsity`` / ``query_order`` record the pruned-vs-dense and
    Morton-vs-identity races.  All are OPTIONAL, so every pre-existing
    entry still parses with ``None`` there.  :data:`RETIRED_KEYS` are
    dropped.  Other keys this build does NOT know land in ``extras``
    verbatim and :func:`_winner_entry` writes them back — a field
    persisted by a newer build survives an older build re-persisting
    the entry instead of being silently erased.  A flat ``[block_q...]``
    list is accepted for hand-authored caches (offline sweep tooling /
    the pre-dtype-policy format).  Anything malformed is treated as a
    miss, never an error: a corrupt cache file must degrade to
    re-tuning.  So is a ``block_q`` whose levels differ: it was raced
    for per-level launches, and the one launch shares one block.
    """
    L = spec.num_levels

    def _block_q(bq):
        if not (isinstance(bq, list) and len(bq) == L and len(set(bq)) == 1):
            return None
        return tuple(int(b) for b in bq)

    try:
        if isinstance(hit, list):
            bq = _block_q(hit)
            return None if bq is None else _winner(
                bq, _default_slab_dtypes(spec))
        if isinstance(hit, dict):
            bq = _block_q(hit.get("block_q"))
            dts = hit.get("slab_dtypes")
            sharding = hit.get("sharding")
            if sharding is not None and sharding not in ("1d", "2d", "hybrid"):
                return None
            gr = hit.get("grad_reduce")
            if gr is not None and gr not in ("ring", "psum"):
                return None
            sparsity = hit.get("sparsity")
            if sparsity is not None and sparsity not in ("dense", "topk"):
                return None
            qorder = hit.get("query_order")
            if qorder is not None and qorder not in ("identity", "morton"):
                return None
            if bq is None:
                return None
            if not (isinstance(dts, list) and len(dts) == L):
                dts = _default_slab_dtypes(spec)
            dts = tuple(str(jnp.dtype(d)) for d in dts)
            extras = {k: v for k, v in hit.items()
                      if k not in _WINNER_FIELDS and k not in RETIRED_KEYS}
            return _winner(bq, dts, sharding, gr, sparsity, qorder, extras)
    except (TypeError, ValueError):  # hand-edited / corrupted entries
        return None
    return None


def _winner(bq, dts, sharding=None, grad_reduce=None, sparsity=None,
            query_order=None, extras=None) -> Dict[str, Any]:
    """The normalised winner dict of :func:`_parse_cache_entry`."""
    return {"block_q": tuple(bq), "slab_dtypes": tuple(dts),
            "sharding": sharding, "grad_reduce": grad_reduce,
            "sparsity": sparsity, "query_order": query_order,
            "extras": dict(extras or {})}


def mesh_token_from(axes, shape) -> str:
    """'data2xmodel2'-style token from bare (axis names, shape) tuples."""
    return "x".join(f"{a}{s}" for a, s in zip(axes, shape))


def mesh_token(mesh) -> str:
    """Stable 'data2xmodel2'-style token for a mesh's (axes, shape).

    The canonical mesh name wherever device objects can't travel: the
    winner-cache key suffix for distributed plans, the plan store's
    sharded entries, and the serving store meta gate.  Deliberately
    ignores device *ids* — a winner tuned on one 2x2 slice applies to
    any other 2x2 slice of the same part.
    """
    return mesh_token_from(mesh.axis_names, mesh.devices.shape)


def mesh_winner_suffix(mesh, query_parallel: bool) -> str:
    """Winner-cache key suffix for (mesh topology, query-parallel flag) —
    the two inputs besides the spec that change which sharding modes are
    even legal to race."""
    return f"mesh[{mesh_token(mesh)}]|qp{int(bool(query_parallel))}"


def autotune_winner_key(spec: MsdaSpec, backend: str,
                        device_kind: Optional[str] = None,
                        mesh_suffix: Optional[str] = None) -> str:
    """The on-disk winner-cache key for (device kind, backend, spec).

    ``mesh_suffix`` (see :func:`mesh_winner_suffix`) keys the
    *distributed* winner — the 1D-vs-2D sharding race — separately from
    the local block/dtype winner of the same spec.
    """
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    key = f"{device_kind}|{registry.resolve_backend(backend)}|{spec.cache_token()}"
    if mesh_suffix:
        key += f"|{mesh_suffix}"
    return key


def get_autotune_winner(spec: MsdaSpec, backend: str,
                        device_kind: Optional[str] = None,
                        mesh_suffix: Optional[str] = None) -> Optional[Dict[str, Any]]:
    """Read (and normalise) the persisted winner for a spec, or None."""
    hit = _load_autotune_cache().get(
        autotune_winner_key(spec, backend, device_kind, mesh_suffix))
    parsed = _parse_cache_entry(hit, spec)
    if parsed is None:
        return None
    return _winner_entry(parsed)


def _winner_entry(parsed: Dict[str, Any]) -> Dict[str, Any]:
    """Parsed winner dict -> the JSON entry shape (optional fields only
    when present — old schemas round-trip unchanged; unknown keys a
    newer build persisted ride through via ``extras``)."""
    out = {"block_q": [int(b) for b in parsed["block_q"]],
           "slab_dtypes": list(parsed["slab_dtypes"])}
    if parsed.get("sharding") is not None:
        out["sharding"] = parsed["sharding"]
    if parsed.get("grad_reduce") is not None:
        out["grad_reduce"] = parsed["grad_reduce"]
    if parsed.get("sparsity") is not None:
        out["sparsity"] = parsed["sparsity"]
    if parsed.get("query_order") is not None:
        out["query_order"] = parsed["query_order"]
    for k, v in (parsed.get("extras") or {}).items():
        if k not in _WINNER_FIELDS:
            out[k] = v
    return out


def seed_autotune_winners(entries, device_kind: Optional[str] = None) -> int:
    """Install winners into the on-disk cache WITHOUT racing (batch).

    ``entries``: iterable of ``(spec, backend, winner)`` or ``(spec,
    backend, winner, mesh_suffix)`` — the 4-tuple form seeds the
    mesh-keyed 1D-vs-2D sharding winner of a distributed plan (see
    :func:`mesh_winner_suffix`).  The restore path of the serving plan
    store and the offline sweep CLI use this to pre-populate the cache a
    fleet (or a restarted server) reads, so ``tune="autotune"`` resolves
    to ``autotune-cache`` with zero timing runs.  One cache read + one
    atomic write for the whole batch.  Each winner is validated with the
    same parser the cache reader uses; malformed winners are skipped
    (returns the number actually written) rather than written where they
    would poison future boots.
    """
    disk = _load_autotune_cache()
    n = 0
    for entry in entries:
        spec, backend, winner = entry[:3]
        mesh_suffix = entry[3] if len(entry) > 3 else None
        parsed = _parse_cache_entry(winner, spec)
        if parsed is None:
            continue
        if not mesh_suffix:  # sharding/grad_reduce live on mesh-keyed entries
            parsed = dict(parsed, sharding=None, grad_reduce=None)
        stored = _winner_entry(parsed)
        disk[autotune_winner_key(spec, backend, device_kind, mesh_suffix)] = stored
        n += 1
    if n:
        _store_autotune_cache(disk)
        _AUTOTUNE_STATS["seeded"].inc(n)
    return n


def seed_autotune_winner(spec: MsdaSpec, backend: str, winner: Any,
                         device_kind: Optional[str] = None) -> bool:
    """Single-entry convenience over :func:`seed_autotune_winners`."""
    return seed_autotune_winners([(spec, backend, winner)], device_kind) == 1


@_obs_trace.traced_span("autotune.race", level=3)
def _autotune_plan(
    spec: MsdaSpec, backend_name: str, builder: Callable, interpret: bool
) -> Tuple[Tuple[int, ...], Tuple[str, ...], str, str, str]:
    """Measure candidate plans; persist the winner per (device, spec).

    Four raced axes:

    * ``block_q`` — the heuristic block scaled by {1/2, 1, 2}, snapped
      to the sublane multiple.  Skipped for blockless backends ("cpu").
    * slab dtype — under the ``slab_dtype="auto"`` policy, fp32 vs bf16
      is raced PER LEVEL (greedy marginal flips on the block winner): a
      bf16 slab halves a train plan's saved corners but pays
      cast/precision overhead, and which side wins is level-size- and
      backend-dependent.
    * top-k point pruning — under ``sparsity="auto"``, the pruned
      executor (4k corner gathers per query instead of 4LP, LOSSY —
      see ``kernels/msda_sparse.py``) races the committed dense winner;
      timed fwd+VJP for train specs.  The heuristic never picks it:
      lossy plans only come from an explicit pin or a measured win.
    * Morton query ordering — under ``query_order="auto"`` on eligible
      (Q == S) geometry, the Z-curve-permuted executor races identity.
      The permutation is bitwise-neutral to outputs, so this race is
      purely about gather locality vs permute overhead.

    All timings are interleaved medians (see :func:`_time_executors`)
    and a challenger must beat the incumbent by ``_AUTOTUNE_MARGIN`` —
    load jitter must never pick a winner.

    Winners ``{"block_q", "slab_dtypes"}`` (+ optional ``sparsity`` /
    ``query_order``) are keyed by spec + device kind so a cache
    produced on one part never mis-tunes another.  Returns ``(block_q,
    slab_dtypes, sparsity, query_order, source)``.
    """
    from repro.kernels import msda_sparse

    base_dts = _default_slab_dtypes(spec)
    heur = _heuristic_block_q(spec, base_dts)
    key = autotune_winner_key(spec, backend_name)
    disk = _load_autotune_cache()
    parsed = _parse_cache_entry(disk.get(key), spec)
    if parsed is None:
        _WINNER_CACHE_MISSES.inc()
    if parsed is not None:
        _AUTOTUNE_STATS["cache_hits"].inc()
        # field-less entries (older schema) resolve the sparsity rungs
        # the way a pin/heuristic would — never surprise-lossy
        sp = (parsed["sparsity"] if parsed["sparsity"] is not None
              else _resolve_sparsity(spec))
        qo = (parsed["query_order"] if parsed["query_order"] is not None
              else _resolve_query_order(spec))
        if qo == "morton" and not msda_sparse.morton_eligible(spec):
            qo = "identity"  # entry from a differently-shaped past: ignore
        return (parsed["block_q"], parsed["slab_dtypes"], sp, qo,
                "autotune-cache")

    qcap = _round_up(spec.num_queries, _SUBLANE)
    race_sparsity = spec.sparsity == "auto"
    race_qorder = (spec.query_order == "auto"
                   and msda_sparse.morton_eligible(spec))
    candidates = []
    if backend_name not in _BLOCKLESS_BACKENDS:
        for scale_num, scale_den in ((1, 2), (1, 1), (2, 1)):
            cand = tuple(
                max(_SUBLANE, min(2048, qcap, (b * scale_num // scale_den) // _SUBLANE * _SUBLANE))
                for b in heur
            )
            if cand not in candidates:
                candidates.append(cand)
    else:
        candidates.append(heur)
    race_dtypes = spec.slab_dtype == "auto"
    if len(candidates) == 1 and not (race_dtypes or race_sparsity
                                     or race_qorder):
        return (candidates[0], base_dts, _resolve_sparsity(spec),
                _resolve_query_order(spec), "autotune")

    _AUTOTUNE_STATS["raced"].inc()
    _AUTOTUNE_STATS["raced_local"].inc()
    args = _autotune_inputs(spec)
    jit_cache: Dict[tuple, Callable] = {}

    def _warm(exec_fn, timed):
        """Jit + warm an executor; ``timed``: 'fwd' times the forward,
        'train' times forward + full VJP.  May raise."""
        if timed == "train":
            f = jax.jit(jax.grad(
                lambda v, l, a, e=exec_fn: jnp.sum(e(v, l, a)),
                argnums=(0, 1, 2)))
        else:
            f = jax.jit(exec_fn)
        jax.block_until_ready(f(*args))
        return f

    def get_fn(bq, dts, timed="fwd"):
        """Jitted + warmed executor for one candidate, cached so incumbent
        re-appearances across race rounds never recompile."""
        ck = (bq, dts, timed)
        if ck not in jit_cache:
            jit_cache[ck] = _warm(builder(spec, PlanTuning(
                block_q=bq, interpret=interpret, source="autotune",
                slab_dtypes=dts)), timed)
        return jit_cache[ck]

    def race(variants: Dict[Any, tuple]):
        """Interleave-time variants {key: (bq, dts)}; a candidate that
        fails to build drops out, and if every one fails the race raises
        with the compiler's message — never a silently untimed plan."""
        fns, errors = {}, []
        for k, v in variants.items():
            try:
                fns[k] = get_fn(*v)
            except Exception as e:  # candidate doesn't fit/compile: skip
                errors.append(f"{k}: {type(e).__name__}: {e}")
        if not fns:
            raise RuntimeError(
                f"autotune: no candidate of {spec} builds on backend "
                f"{backend_name!r}:\n" + "\n".join(errors))
        times = _time_executors(fns, args)
        return min(times, key=times.get), times

    best, _ = race({c: (c, base_dts) for c in candidates})

    best_dts = base_dts
    if race_dtypes:
        # greedy per-level flips against the committed block winner; each
        # round re-times incumbent vs challenger INTERLEAVED and the flip
        # must clear the noise margin, so a level goes bf16 only when its
        # marginal saving genuinely beats its cast cost end-to-end.  The
        # packed slab rounds each level to its own committed dtype (see
        # ops._pack_pyramid)
        wide, narrow = (str(jnp.dtype(d)) for d in _SLAB_DTYPE_CANDIDATES)
        current = (wide,) * spec.num_levels
        for l in range(spec.num_levels):
            trial = tuple(narrow if i == l else d
                          for i, d in enumerate(current))
            k, times = race({"cur": (best, current), "trial": (best, trial)})
            if (k == "trial"
                    and times["trial"] < times["cur"] * (1 - _AUTOTUNE_MARGIN)):
                current = trial
        best_dts = current
        if best_dts != base_dts and backend_name not in _BLOCKLESS_BACKENDS:
            # flipped levels narrowed the saved corners: re-plan the block
            # with the committed itemsizes and keep the clear winner
            rebq = _heuristic_block_q(spec, best_dts)
            if rebq != best:
                k, times = race({"cur": (best, best_dts), "re": (rebq, best_dts)})
                if (k == "re"
                        and times["re"] < times["cur"] * (1 - _AUTOTUNE_MARGIN)):
                    best = rebq

    best_sparsity = _resolve_sparsity(spec)
    if race_sparsity:
        # pruned challenger vs the fully committed dense winner; the
        # dense side stays the incumbent (lossy never wins on jitter).
        # Timed fwd+VJP for train specs — pruning shrinks the backward's
        # scatter set as much as the forward's gather set.
        timed = "train" if spec.train else "fwd"
        try:
            fns = {
                "dense": get_fn(best, best_dts, timed=timed),
                "topk": _warm(msda_sparse.build_topk_exec(spec), timed),
            }
            times = _time_executors(fns, args)
            if times["topk"] < times["dense"] * (1 - _AUTOTUNE_MARGIN):
                best_sparsity = "topk"
            else:
                best_sparsity = "dense"
        except Exception:
            best_sparsity = "dense"  # challenger didn't build: stay dense

    best_qorder = _resolve_query_order(spec)
    if race_qorder:
        # Morton permutation around whatever executor the sparsity rung
        # just committed — the permutation's locality payoff (and its
        # permute overhead) must be measured on the plan that will run
        timed = "train" if spec.train else "fwd"
        try:
            if best_sparsity == "topk":
                base_exec = msda_sparse.build_topk_exec(spec)
            else:
                base_exec = builder(spec, PlanTuning(
                    block_q=best, interpret=interpret, source="autotune",
                    slab_dtypes=best_dts))
            wrapped = msda_sparse.wrap_query_permutation(
                base_exec, spec.spatial_shapes)
            fns = {"identity": _warm(base_exec, timed),
                   "morton": _warm(wrapped, timed)}
            times = _time_executors(fns, args)
            if times["morton"] < times["identity"] * (1 - _AUTOTUNE_MARGIN):
                best_qorder = "morton"
            else:
                best_qorder = "identity"
        except Exception:
            best_qorder = "identity"

    disk[key] = _winner_entry(_winner(
        best, best_dts,
        sparsity=best_sparsity if race_sparsity else None,
        query_order=best_qorder if race_qorder else None))
    _store_autotune_cache(disk)
    return best, best_dts, best_sparsity, best_qorder, "autotune"


@_obs_trace.traced_span("autotune.race_sharding", level=3)
def _autotune_sharding(spec: MsdaSpec, backend_name: str, mesh,
                       query_parallel: bool, grad_reduce: str,
                       build_local: Callable):
    """Race the 1D ladder vs the 2D (dp x tp) — and, where the 1D rung
    degenerates to batch-only, the hybrid batch x query — modes.

    Returns ``(choice, built)`` where ``choice`` is ``'1d' | '2d' |
    'hybrid'`` and ``built`` is the winner's already-constructed
    ``(sharded_exec, tuning, resolution)`` — or None on a cache hit /
    degenerate race — so the caller never rebuilds what the race just
    built.

    The sharding mode joined the autotune space in the same spirit as
    block_q and the slab dtypes: which side wins is geometry- and
    topology-dependent (2D buys a dp_size-wider query fan-out but pays
    value replication over dp plus the dp-psum leg of the grad
    reduction; hybrid trades batch ways for query ways on tp-less
    meshes), so under ``tune="autotune"`` + ``sharding="auto"`` the
    full sharded executors are built — each at its OWN tuned local
    geometry, the nested block/dtype races caching per local spec as
    usual — and timed interleaved on synthetic operands at the GLOBAL
    geometry.  **Train specs time forward + backward**: the modes
    differ mostly in backward cost (the grad_value reduction), so a
    forward-only race would crown the wrong mode for training.  The
    winner persists in the standard winner-cache schema grown by a
    ``"sharding"`` field (old entries parse unchanged), keyed by
    (device kind, backend, spec, mesh topology, qp flag) so a 2x2
    winner never mis-tunes a 1x4 mesh.  The hybrid challenger only
    joins when the 1D rung resolved to batch/replicated (a trivial tp
    axis): on meshes where the ladder already tiles queries the hybrid
    tiling is redundant, and racing it would only add jitter.
    """
    from repro.sharding import rules

    r1 = _plan_sharding(spec, mesh, query_parallel, "1d")
    cands: List[tuple] = [("1d", r1)]
    r2 = _plan_sharding(spec, mesh, query_parallel, "2d")
    if r2[0] == "query2d":
        cands.append(("2d", r2))
    rh = _plan_sharding(spec, mesh, query_parallel, "hybrid")
    if rh[0] == "batchquery" and r1[0] in ("batch", "replicated"):
        cands.append(("hybrid", rh))
    if len(cands) == 1:
        return "1d", None  # no challenger on this (spec, mesh)
    key = autotune_winner_key(
        spec, backend_name, mesh_suffix=mesh_winner_suffix(mesh, query_parallel))
    disk = _load_autotune_cache()
    parsed = _parse_cache_entry(disk.get(key), spec)
    if parsed is None or parsed["sharding"] not in ("1d", "2d", "hybrid"):
        _WINNER_CACHE_MISSES.inc()
    if parsed is not None and parsed["sharding"] in ("1d", "2d", "hybrid"):
        _AUTOTUNE_STATS["cache_hits"].inc()
        return parsed["sharding"], None

    _AUTOTUNE_STATS["raced"].inc()
    _AUTOTUNE_STATS["raced_mesh"].inc()
    # batch must divide dp for the 1D candidate (dp shards batch there)
    batch = rules.axis_size(rules.resolve_axis("dp", mesh), mesh)
    if any(n == "hybrid" for n, _ in cands):
        # ... and the hybrid tile for its candidate (lcm keeps both legal)
        bt = HYBRID_BATCH_TILE
        batch = batch * bt // math.gcd(batch, bt)
    args = _autotune_inputs(spec, batch=batch)
    fns: Dict[str, Callable] = {}
    built: Dict[str, tuple] = {}
    for name, r in cands:
        mode, dp, tp, tp_size, local = r
        try:
            inner_exec, tuning = build_local(local)
            exec_fn = _build_sharded_exec(
                spec, inner_exec, local, mesh, mode, dp, tp, tp_size,
                grad_reduce)
            if spec.train:
                # time what training executes: fwd + full VJP (the
                # ring/psum grad_value legs live in the backward)
                f = jax.jit(jax.grad(
                    lambda v, l, a, e=exec_fn: jnp.sum(e(v, l, a)),
                    argnums=(0, 1, 2)))
            else:
                f = jax.jit(exec_fn)
            jax.block_until_ready(f(*args))  # compile + warm (may raise)
            fns[name] = f
            built[name] = (exec_fn, tuning, r, inner_exec)
        except Exception:
            continue  # candidate doesn't build on this mesh: skip
    if not fns:
        return "1d", None  # nothing raced: fall back, persist nothing
    if len(fns) < 2:
        # lone survivor: use it for THIS process but do NOT persist — a
        # transient compile failure on the other candidate must not
        # become a permanent (never re-raced) fleet-wide tuning decision
        winner = next(iter(fns))
        return winner, built[winner]
    times = _time_executors(fns, args)
    # the incumbent is the 1D ladder; a challenger must clear the margin
    winner = "1d"
    if "1d" in times:
        best = min((n for n in times if n != "1d"), key=times.get)
        if times[best] < times["1d"] * (1 - _AUTOTUNE_MARGIN):
            winner = best
    else:
        winner = min(times, key=times.get)
    t = built[winner][1]
    disk = _load_autotune_cache()
    disk[key] = _winner_entry(_winner(
        t.block_q, t.slab_dtypes or _default_slab_dtypes(spec),
        sharding=winner))
    _store_autotune_cache(disk)
    return winner, built[winner]


@_obs_trace.traced_span("autotune.race_grad_reduce", level=3)
def _autotune_grad_reduce(spec: MsdaSpec, backend_name: str, mesh,
                          query_parallel: bool, mode: str, dp, tp,
                          tp_size: int, inner_exec: Callable,
                          local_spec: MsdaSpec, tuning: "PlanTuning"):
    """Race the grad_value reduction (ring vs psum) per mesh topology.

    The roadmap's distribution follow-up: whether the ppermute ring or
    the monolithic psum wins the query-sharded backward's tp-axis
    grad_value reduction is topology-dependent (on DCN-crossing meshes
    the single collective can win; on ICI rings the chunked circulation
    does) — so under ``tune="autotune"`` + ``grad_reduce="auto"`` the
    two legs are raced the way the sharding mode is: both sharded
    executors share the SAME inner (unsharded) executor and differ only
    in the collective, timings are full fwd+VJP (the legs only exist in
    the backward), and the winner persists in the mesh-keyed winner
    entry's optional ``"grad_reduce"`` field alongside ``"sharding"``.

    Returns ``(choice, exec_fn_or_None)`` — the winner's built sharded
    executor when the race ran, ``None`` on a cache hit (the caller
    rebuilds; wiring a shard_map is cheap).  Only called for train
    specs: inference plans never run the backward, so 'auto' stays ring.
    """
    key = autotune_winner_key(
        spec, backend_name, mesh_suffix=mesh_winner_suffix(mesh, query_parallel))
    disk = _load_autotune_cache()
    parsed = _parse_cache_entry(disk.get(key), spec)
    if parsed is None or parsed["grad_reduce"] not in ("ring", "psum"):
        _WINNER_CACHE_MISSES.inc()
    if parsed is not None and parsed["grad_reduce"] in ("ring", "psum"):
        _AUTOTUNE_STATS["cache_hits"].inc()
        return parsed["grad_reduce"], None

    from repro.sharding import rules

    _AUTOTUNE_STATS["raced"].inc()
    _AUTOTUNE_STATS["raced_mesh"].inc()
    batch = rules.axis_size(rules.resolve_axis("dp", mesh), mesh)
    if mode == "batchquery":
        bt = HYBRID_BATCH_TILE
        batch = batch * bt // math.gcd(batch, bt)
    args = _autotune_inputs(spec, batch=batch)
    fns: Dict[str, Callable] = {}
    built: Dict[str, Callable] = {}
    for gr in ("ring", "psum"):
        try:
            exec_fn = _build_sharded_exec(
                spec, inner_exec, local_spec, mesh, mode, dp, tp, tp_size, gr)
            f = jax.jit(jax.grad(
                lambda v, l, a, e=exec_fn: jnp.sum(e(v, l, a)),
                argnums=(0, 1, 2)))
            jax.block_until_ready(f(*args))  # compile + warm (may raise)
            fns[gr] = f
            built[gr] = exec_fn
        except Exception:
            continue
    if not fns:
        return "ring", None  # nothing raced: keep the default, persist nothing
    if len(fns) < 2:
        # lone survivor: use it, don't persist (same contract as sharding)
        gr = next(iter(fns))
        return gr, built[gr]
    times = _time_executors(fns, args)
    # ring is the incumbent default; psum must clear the noise margin
    choice = ("psum" if times["psum"] < times["ring"] * (1 - _AUTOTUNE_MARGIN)
              else "ring")
    disk = _load_autotune_cache()
    prev = _parse_cache_entry(disk.get(key), spec)
    if prev is None:  # no sharding race ran (mode was pinned): start fresh
        prev = _winner(tuning.block_q, tuning.slab_dtypes
                       or _default_slab_dtypes(local_spec))
    prev["grad_reduce"] = choice
    disk[key] = _winner_entry(prev)
    _store_autotune_cache(disk)
    return choice, built[choice]


# --------------------------------------------------------------------------
# sharding (baked into the plan; collapses the old distributed_msda fork)
# --------------------------------------------------------------------------


def _mesh_cache_key(mesh) -> Optional[tuple]:
    if mesh is None:
        return None
    return (
        tuple(mesh.axis_names),
        tuple(mesh.devices.shape),
        tuple(int(d.id) for d in mesh.devices.flat),
    )


# below this per-shard query count the 2D mode stops amortising the
# second axis (ring hops + replicated-value HBM cost what the extra way
# of parallelism buys back); 'auto' then stays on the 1D ladder.  The
# 87k-query Deformable-DETR encoder clears it on any realistic mesh
# (87040 / 16 devices = 5440 per shard).  sharding="2d" overrides.
QUERY2D_MIN_LOCAL_Q = 2048

SHARDING_CHOICES = ("auto", "1d", "2d", "hybrid")
GRAD_REDUCE_CHOICES = ("auto", "ring", "psum")

# the hybrid batch x query rung re-racks the WHOLE device set as
# (batch_tile, n // batch_tile): batch shards over the first factor,
# queries tile over the second.  On a tp-less mesh (Nx1) the classic
# ladder degenerates to batch-only — mid-size B can't fill N batch ways,
# while hybrid still keeps every device busy with B=batch_tile.  The
# tile is a fixed small factor (not raced per B: B is unknown at plan
# time) — 2 is the smallest non-trivial split and keeps the query
# factor maximal.
HYBRID_BATCH_TILE = 2


def _hybrid_tiling(spec: MsdaSpec, mesh) -> Optional[Tuple[int, int]]:
    """(batch_tile, query_tile) for the hybrid rung, or None if illegal
    on this (spec, mesh): needs the device count to split as bt x qf
    with a non-trivial query factor that divides Q."""
    n = int(mesh.devices.size)
    bt = HYBRID_BATCH_TILE
    if n % bt:
        return None
    qf = n // bt
    if qf <= 1 or spec.num_queries % qf:
        return None
    return bt, qf


def _plan_sharding(spec: MsdaSpec, mesh, query_parallel: bool,
                   sharding: str = "auto"):
    """Resolve the legal sharding mode for this spec on this mesh.

    Returns (mode, dp_axis, tp_axis, tp_size, inner_spec) where ``mode``
    is one of 'replicated' | 'batch' | 'head' | 'query' | 'query2d' |
    'batchquery'.

    The 2D mode ('query2d') tiles QUERIES over dp x tp jointly — heads,
    batch and the value tensor are replicated — and is taken when both
    axes are real (dp > 1 and tp > 1), Q divides by dp*tp, and either
    ``sharding="2d"`` forces it or Q is large enough to amortise both
    axes (``QUERY2D_MIN_LOCAL_Q`` per shard; the 87k-query encoder).
    On a 1xN or Nx1 mesh one of the axes is trivial, so a 2D request
    resolves to the equivalent 1D rung instead of pretending.

    The hybrid mode ('batchquery') ignores the mesh's named factoring
    entirely and re-racks ALL devices as ``HYBRID_BATCH_TILE`` batch
    ways x ``n/HYBRID_BATCH_TILE`` query ways (see
    :func:`_hybrid_tiling`); ``tp_size`` in the returned tuple is the
    QUERY factor (the width of the grad_value reduction).  Forced by
    ``sharding="hybrid"``; under "auto" it slots between the query/head
    rungs and the batch-only floor, so a query-parallel plan on an Nx1
    mesh gets a non-degenerate step instead of idling N/B devices.

    The 1D ladder is otherwise unchanged: query-parallel needs
    Q % tp == 0, head-parallel H % tp == 0; otherwise tp idles
    (batch-only) — same degradation ladder the old distributed_msda had,
    now committed once at plan time instead of re-derived per call.
    """
    from repro.sharding import rules

    dp = rules.resolve_axis("dp", mesh)
    tp = rules.resolve_axis("tp", mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp_size = sizes.get("model", 1)
    dp_size = rules.axis_size(dp, mesh)
    H, Q = spec.num_heads, spec.num_queries
    want_query = query_parallel or sharding in ("2d", "hybrid")
    if sharding == "hybrid":
        hy = _hybrid_tiling(spec, mesh)
        if hy is not None:
            bt, qf = hy
            inner = dataclasses.replace(spec, num_queries=Q // qf)
            return "batchquery", dp, tp, qf, inner
    if (sharding not in ("1d", "hybrid") and want_query
            and dp is not None and dp_size > 1
            and tp is not None and tp_size > 1
            and Q % (dp_size * tp_size) == 0):
        local_q = Q // (dp_size * tp_size)
        if sharding == "2d" or local_q >= QUERY2D_MIN_LOCAL_Q:
            inner = dataclasses.replace(spec, num_queries=local_q)
            return "query2d", dp, tp, tp_size, inner
    if want_query and Q % tp_size == 0 and tp is not None and tp_size > 1:
        inner = dataclasses.replace(spec, num_queries=Q // tp_size)
        return "query", dp, tp, tp_size, inner
    if tp is not None and tp_size > 1 and H % tp_size == 0:
        inner = dataclasses.replace(spec, num_heads=H // tp_size)
        return "head", dp, tp, tp_size, inner
    if sharding == "auto" and want_query and (tp is None or tp_size == 1):
        # hybrid rung: the named ladder has no query axis left, but the
        # raw device count still splits as batch_tile x query_tile
        hy = _hybrid_tiling(spec, mesh)
        if hy is not None:
            bt, qf = hy
            inner = dataclasses.replace(spec, num_queries=Q // qf)
            return "batchquery", dp, tp, qf, inner
    # tp idle (or size 1): shards see the full head/query extent
    mode = "batch" if dp is not None else "replicated"
    return mode, dp, None, 1, spec


def resolve_sharding(spec: MsdaSpec, mesh, query_parallel: bool,
                     sharding: str = "auto") -> Tuple[str, MsdaSpec]:
    """Public probe: the (mode, per-shard spec) a plan would commit.

    Used by the plan store to re-derive a persisted distributed plan's
    local geometry (whose autotune winner is keyed on the LOCAL spec)
    and by tests/docs that assert on the ladder without building a plan.
    """
    mode, _, _, _, inner = _plan_sharding(spec, mesh, query_parallel, sharding)
    return mode, inner


def _resolve_grad_reduce(grad_reduce: str, mode: str, tp_size: int) -> str:
    """'auto' -> ring for the query-sharded modes (where grad_value is a
    cross-shard reduction), psum-via-AD everywhere else.  Modes whose
    value tensor is sharded ('head', 'batch') have nothing to reduce and
    always report 'none'."""
    if mode not in ("query", "query2d", "batchquery") or tp_size <= 1:
        return "none"
    if grad_reduce == "auto":
        return "ring"
    return grad_reduce


def _build_sharded_exec(spec, inner_exec, inner_spec, mesh, mode, dp, tp,
                        tp_size: int, grad_reduce: str):
    """The shard_map-wired executor of a mesh-carrying plan.  The batch
    is split over the data axes when they divide it; a batch they do not
    divide (one image on a 2x2 mesh) is replicated over them instead —
    the spec carries no batch size, so this is decided per call."""
    from repro.sharding import rules

    batch_ways = (int(mesh.devices.size) // int(tp_size)
                  if mode == "batchquery" else rules.axis_size(dp, mesh))
    built: Dict[bool, Callable] = {}

    def exec_(v, l, a):
        split = v.shape[0] % batch_ways == 0
        if split not in built:
            built[split] = _sharded_op(spec, inner_exec, inner_spec, mesh,
                                       mode, dp, tp, tp_size, grad_reduce,
                                       split_batch=split)
        return built[split](v, l, a)

    return exec_


def _sharded_op(spec, inner_exec, inner_spec, mesh, mode, dp, tp,
                tp_size: int, grad_reduce: str, *, split_batch: bool):
    from repro.sharding import rules

    from jax.sharding import Mesh, PartitionSpec as P

    if mode == "batchquery":
        # hybrid rung: re-rack the WHOLE device set as (batch_tile x
        # query_tile) — an internal mesh over the same devices — then the
        # wiring IS the query mode's on that mesh: value batch-sharded
        # over the tile, queries split over the query factor, grad_value
        # ring/psum-reduced over it.  The caller's named axes don't
        # appear inside; the plan records the ORIGINAL mesh topology.
        qf = int(tp_size)
        bt = int(mesh.devices.size) // qf
        mesh = Mesh(mesh.devices.reshape(bt, qf), ("data", "model"))
        mode, dp, tp = "query", "data", "model"

    if mode == "query2d":
        # queries tiled over dp x tp jointly; heads, batch and the value
        # tensor replicated — the whole mesh works one huge-Q problem
        # (the 87k-query encoder) instead of only the tp slice of it.
        qaxes = rules.flat_axes(dp) + rules.flat_axes(tp)
        vspec = P(None, None, None, None)
        qspec = P(None, qaxes, None, None, None, None)
        wspec = P(None, qaxes, None, None, None)
        ospec = P(None, qaxes, None)
    elif mode == "query":
        # value replicated over tp; queries split.  Backward: the
        # per-shard partial grad_value slabs are reduced over tp — by
        # the explicit ppermute ring below (default), or by shard_map's
        # transpose psum when grad_reduce="psum" — the TPU-idiomatic
        # realisation of the paper's staggered scatter (contention
        # eliminated via partial accumulators + reduction).
        bdp = dp if split_batch else None
        vspec = P(bdp, None, None, None)
        qspec = P(bdp, tp, None, None, None, None)
        wspec = P(bdp, tp, None, None, None)
        ospec = P(bdp, tp, None)
    else:
        bdp = dp if split_batch else None
        vspec = P(bdp, None, tp, None)
        qspec = P(bdp, None, tp, None, None, None)
        wspec = P(bdp, None, tp, None, None)
        ospec = P(bdp, None, tp)

    Hd = inner_spec.num_heads * inner_spec.head_dim

    def run(v, l, a):
        out = inner_exec(v, l, a)
        return out.reshape(l.shape[0], l.shape[1], Hd)

    fwd_sharded = jax.shard_map(run, mesh=mesh, in_specs=(vspec, qspec, wspec),
                                  out_specs=ospec, check_vma=False)
    reduce = _resolve_grad_reduce(grad_reduce, mode, tp_size)
    if reduce == "none":
        return fwd_sharded

    # Explicit grad_value reduction: shard_map's transpose would emit
    # one monolithic all-reduce of the full fp32 slab per backward.
    # Instead the backward runs as its own shard_map whose body computes
    # the per-shard partial slab and reduces it hierarchically — over
    # the tp axis first, then psum over the dp axes when value is
    # replicated there too (2D mode), matching the ICI-ring-then-DCN
    # topology.  The tp leg is the raced axis: a ppermute ring
    # (``msda_bwd.ring_allreduce`` — one slab shard resident per hop,
    # QUILL-style) by default, or a plain psum under
    # ``grad_reduce="psum"`` (the ablation/parity baseline — identical
    # structure, so the two paths differ ONLY in the tp reduction).
    # The per-shard forward is recomputed inside the backward (remat at
    # the shard_map boundary): at dp x tp scale the residual slabs would
    # otherwise sit resident across the whole ring schedule.
    from repro.kernels import msda_bwd

    dp_axes = rules.flat_axes(dp)
    accum = jnp.dtype(spec.accum_dtype)

    def bwd_shard(v, l, a, g):
        _, vjp = jax.vjp(run, v, l, a)
        gv, gl, ga = vjp(g)
        vdt = gv.dtype
        # reduce the slab in the widened accum dtype: cross-shard adds
        # must not round through a narrow operand dtype between hops
        gv = gv.astype(accum)
        if reduce == "ring":
            gv = msda_bwd.ring_allreduce(gv, tp, tp_size, axis=1)
        else:
            gv = jax.lax.psum(gv, tp)
        if mode == "query2d" and dp_axes:
            gv = jax.lax.psum(gv, dp_axes)
        return gv.astype(vdt), gl, ga

    bwd_sharded = jax.shard_map(
        bwd_shard, mesh=mesh, in_specs=(vspec, qspec, wspec, ospec),
        out_specs=(vspec, qspec, wspec), check_vma=False)

    @jax.custom_vjp
    def op(v, l, a):
        return fwd_sharded(v, l, a)

    def op_fwd(v, l, a):
        return fwd_sharded(v, l, a), (v, l, a)

    def op_bwd(res, g):
        return bwd_sharded(*res, g)

    op.defvjp(op_fwd, op_bwd)
    return op


# --------------------------------------------------------------------------
# MsdaPlan
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class MsdaPlan:
    """Executable MSDA plan: backend + tuning + (optional) sharding, fixed.

    Call it like the op: ``plan(value, loc, attn) -> (B, Q, H*D)``.  The
    VJP was wired at build time — ``jax.grad`` through the call just works.
    """

    spec: MsdaSpec
    backend: str
    tuning: PlanTuning
    # 'local' | 'replicated' | 'batch' | 'head' | 'query' | 'query2d'
    # | 'batchquery' (hybrid batch x query tiling over the whole mesh)
    sharding_mode: str
    # the per-shard geometry the tuning was computed for (== spec for
    # unsharded plans; Q or H divided by the sharded axes otherwise)
    local_spec: MsdaSpec
    _exec: Callable = dataclasses.field(repr=False, compare=False)
    # -- distribution record (how the mode above maps onto the mesh) ------
    # kept as plain tuples/strings (no device objects) so the plan store
    # can persist them and a restored process can validate its own mesh
    mesh_axes: Tuple[str, ...] = ()
    mesh_shape: Tuple[int, ...] = ()
    query_parallel: bool = False
    # 'none' (no cross-shard grad_value reduction) | 'ring' | 'psum'
    grad_reduce: str = "none"
    # hybrid ('batchquery') plans only: how many batch ways the whole
    # device set was re-racked into (queries take the remaining factor)
    batch_tile: int = 0
    # the tune mode the plan was REQUESTED with.  tuning.source alone
    # can't recover this: a backend with no local tuning surface (ref)
    # still races the mesh-keyed axes under "autotune", and the plan
    # store must know to re-race them after an elastic mesh resize
    tune: str = "heuristic"

    def __call__(self, value: jax.Array, sampling_locations: jax.Array,
                 attention_weights: jax.Array) -> jax.Array:
        s = self.spec
        if value.shape[1] != s.total_pixels or value.shape[3] != s.head_dim:
            raise ValueError(
                f"value {value.shape} does not match plan spec "
                f"(S={s.total_pixels}, D={s.head_dim})")
        if sampling_locations.shape[1] != s.num_queries:
            raise ValueError(
                f"loc Q={sampling_locations.shape[1]} != spec Q={s.num_queries}")
        lp = self.launches_per_call()
        _PLAN_CALLS.inc(backend=self.backend)
        if lp["fwd"]:
            _LAUNCHES.inc(lp["fwd"], direction="fwd")
        if lp["bwd"]:
            _LAUNCHES.inc(lp["bwd"], direction="bwd")
        return self._exec(value, sampling_locations, attention_weights)

    apply = __call__

    @property
    def block_q(self) -> Tuple[int, ...]:
        return self.tuning.block_q

    def launches_per_call(self) -> Dict[str, int]:
        """Static Pallas launch schedule for one plan call, by direction.

        A Pallas plan launches once per direction over the packed
        pyramid.  The ref/cpu backends and the top-k pruned executor run
        as plain XLA — zero Pallas launches.  ``bwd`` counts the
        custom-VJP backward a ``train`` plan carries (0 for inference
        plans).
        """
        if self.backend != "pallas" or self.tuning.sparsity == "topk":
            return {"fwd": 0, "bwd": 0}
        return {"fwd": 1, "bwd": 1 if self.spec.train else 0}

    # -- inspectability ---------------------------------------------------
    def level_report(self) -> List[Dict[str, Any]]:
        """Per-level planning facts (the numbers ``describe`` prints).

        Reported against ``local_spec`` — the per-shard geometry the
        tuning was actually computed for.  ``vmem_frac`` is the launch's:
        the packed pyramid resident at once plus the block's step, the
        same on every level's row.
        """
        from repro.kernels import ops

        s = self.local_spec
        dts = self.tuning.slab_dtypes or _default_slab_dtypes(s)
        resolved = tuple(
            dts[l] if l < len(dts) and dts[l] else s.resolved_slab_dtype()
            for l in range(s.num_levels))
        heads = s.heads_per_launch
        resident = ops.pyramid_resident_bytes(s.spatial_shapes, s.head_dim,
                                              heads=heads)
        # the step's saved corners are sized by the widest resident level
        per_q = ops.per_query_bytes(
            s.num_points, s.head_dim, train=s.train,
            slab_itemsize=max(jnp.dtype(d).itemsize for d in resolved),
            levels=s.num_levels, heads=heads)
        # what the occupancy model would have picked on its own, so the
        # report carries predicted-vs-committed occupancy (a raced or
        # overridden block can land far from the model)
        pred_bq = _heuristic_block_q(s, resolved)[0]
        predicted = (resident + pred_bq * per_q) / max(s.vmem_budget, 1)
        if self.tuning.sparsity == "topk":
            # the pruned executor replaces the backend's gather path
            # wholesale (XLA top-k gather) — report what runs
            gather = "xla-topk"
        else:
            gather = {"ref": "xla", "cpu": "cpu-fused"}.get(self.backend,
                                                            "vpu")
        rows = []
        for l, hw in enumerate(s.spatial_shapes):
            slab = ops.slab_rows(hw)
            sdt = resolved[l]
            if self.backend == "ref":
                # the oracle ignores the slab policy: pure fp32 compute,
                # no resident slabs — report what actually executes
                sdt = "float32"
            # the level's slab per head in its committed dtype (+ the
            # fp32 grad slab of a train plan): its HBM footprint
            slab_bytes = slab * s.head_dim * jnp.dtype(sdt).itemsize
            if s.train:
                slab_bytes += slab * s.head_dim * s.accum_itemsize
            bq = self.tuning.block_q[l] if l < len(self.tuning.block_q) else 0
            occupancy = (resident + bq * per_q) / max(s.vmem_budget, 1)
            rows.append({
                "level": l,
                "hw": hw,
                "slab_rows": slab,
                "slab_bytes": slab_bytes,
                "slab_dtype": str(sdt),
                "block_q": bq,
                "q_steps": -(-_round_up(s.num_queries, max(bq, 1)) // max(bq, 1)),
                "gather": gather,
                "vmem_frac": occupancy,
                "block_q_predicted": pred_bq,
                "vmem_frac_predicted": predicted,
            })
            _VMEM_GAUGE.set(occupancy, level=l, kind="committed")
            _VMEM_GAUGE.set(predicted, level=l, kind="predicted")
        return rows

    def weight_report(self) -> List[Dict[str, Any]]:
        """The forward launch's VMEM weight block: the levels it covers,
        its ``block_q``, the weight rows a query takes
        (``msda_fwd.weight_layout``) and the double-buffered block's
        bytes.  No launch (``[]``) where no Pallas gather runs."""
        if self.backend != "pallas" or self.tuning.sparsity == "topk":
            return []
        from repro.kernels import msda_fwd, ops

        s = self.local_spec
        bq = self.tuning.block_q[0]
        rows = msda_fwd.weight_layout(s.num_levels, s.num_points,
                                      s.head_dim)[0]
        lanes = ops.lane_width(s.heads_per_launch, s.head_dim)
        return [{"levels": tuple(range(s.num_levels)), "block_q": bq,
                 "rows_per_query": rows,
                 "vmem_bytes": 2 * bq * rows * lanes * 4}]

    def sharding_report(self) -> Dict[str, Any]:
        """Structured record of the committed distribution.

        Which mesh axes shard which operand dims, plus the grad_value
        reduction strategy — the facts ``describe()``'s mesh line prints
        and the plan store persists.  Empty-axes dict for local plans.
        """
        sizes = dict(zip(self.mesh_axes, self.mesh_shape))
        dp_axes = tuple(a for a in ("pod", "data") if a in sizes)
        tp = "model" if "model" in sizes else None
        mode = self.sharding_mode
        q_axes: Tuple[str, ...] = ()
        h_axes: Tuple[str, ...] = ()
        b_axes: Tuple[str, ...] = ()
        if mode == "query2d":
            q_axes = dp_axes + ((tp,) if tp else ())
        elif mode == "query":
            q_axes, b_axes = ((tp,) if tp else ()), dp_axes
        elif mode == "head":
            h_axes, b_axes = ((tp,) if tp else ()), dp_axes
        elif mode == "batch":
            b_axes = dp_axes
        out = {
            "mode": mode,
            "mesh": sizes,
            "query_axes": q_axes,
            "head_axes": h_axes,
            "batch_axes": b_axes,
            "query_parallel": self.query_parallel,
            "grad_reduce": self.grad_reduce,
        }
        if mode == "batchquery":
            # hybrid: the tiling ignores the named axes — report the
            # anonymous (batch_tile x query_tile) factoring instead
            n = 1
            for s in self.mesh_shape:
                n *= int(s)
            out["batch_tile"] = int(self.batch_tile)
            out["query_tile"] = n // max(int(self.batch_tile), 1)
        return out

    def describe(self) -> str:
        """Human-readable plan report.

        The header states the resolved sharding MODE and the launches
        per call; a Pallas plan adds its packed pyramid's rows and its
        launch's weight block; mesh-carrying plans add a ``mesh:`` line
        with the topology, which mesh axes
        shard which operand dims, the per-shard geometry, and the
        committed grad_value reduction (``ring`` / ``psum`` / ``local``)
        — so the report is the full distribution contract, not just the
        mode name.  Then one line per level with the launch's
        ``block_q``, slab bytes / VMEM occupancy, the gather path, and —
        the mixed-precision axis — the **chosen slab dtype variant** per
        level (``slab_dt`` column: fp32, or bf16 when the policy /
        autotune committed a narrow slab; accumulation stays in
        ``accum_dtype``, shown in the header).
        """
        s = self.spec
        shard_note = ""
        if self.mesh_axes:
            r = self.sharding_report()
            dims = []
            if r["mode"] == "batchquery":
                dims = [f"B->x{r['batch_tile']}", f"Q->x{r['query_tile']}"]
            if r["batch_axes"]:
                dims.append("B->" + "+".join(r["batch_axes"]))
            if r["query_axes"]:
                dims.append("Q->" + "+".join(r["query_axes"]))
            if r["head_axes"]:
                dims.append("H->" + "+".join(r["head_axes"]))
            gr = self.grad_reduce if self.grad_reduce != "none" else "local"
            shard_note = (
                f"  mesh: {mesh_token_from(self.mesh_axes, self.mesh_shape)}  "
                f"{'  '.join(dims) if dims else 'replicated'}  "
                f"grad_value={gr}\n")
        if self.local_spec is not self.spec:
            shard_note += (f"  per-shard: Q={self.local_spec.num_queries} "
                           f"H={self.local_spec.num_heads} (levels below are per shard)\n")
        sparse_note = ""
        if self.tuning.sparsity == "topk":
            ls = self.local_spec
            cells = ls.num_levels * ls.num_points
            k = ls.resolved_sparsity_k()
            sparse_note = (
                f"  sparsity: topk k={k}/{cells} cells/query  "
                f"corner gathers {4 * k}/query (dense {4 * cells})\n")
        if self.tuning.query_order == "morton":
            sparse_note += ("  query order: morton (plan-time Z-curve "
                            "permutation, inverted on output)\n")
        lp = self.launches_per_call()
        launch_note = (f"  launches/call: fwd={lp['fwd']} bwd={lp['bwd']}"
                       + ("" if self.backend == "pallas"
                          else f"  (no pallas kernels on '{self.backend}')")
                       + "\n")
        head = (
            f"MsdaPlan(backend={self.backend}, tune={self.tuning.source}, "
            f"sharding={self.sharding_mode}, "
            f"train={s.train}, dtype={s.dtype}, "
            f"accum={s.accum_dtype})\n"
            f"  Q={s.num_queries} H={s.num_heads} D={s.head_dim} P={s.num_points} "
            f"levels={s.num_levels} S={s.total_pixels}\n"
            + shard_note + sparse_note + launch_note +
            f"  vmem_budget={s.vmem_budget / 2**20:.1f} MiB  "
            f"interpret={self.tuning.interpret}\n"
        )
        from repro.kernels import ops

        for w in self.weight_report():
            lv = w["levels"]
            _, total = ops.pyramid_row_offsets(self.local_spec.spatial_shapes)
            span = f"{lv[0]}-{lv[-1]}" if len(lv) > 1 else f"{lv[0]}"
            head += (f"  packed pyramid: super_slab_rows={total}  "
                     f"block_q={w['block_q']}\n"
                     f"  weights lvl {span}: {w['rows_per_query']} rows/query"
                     f" x block_q {w['block_q']} = {w['vmem_bytes']} B VMEM"
                     f" (2 buffers)\n")
        lines = [head,
                 "  lvl  hw         slab_rows  slab_KiB   slab_dt   block_q  steps  gather      vmem%  pred%"]
        for r in self.level_report():
            hw = "%dx%d" % r["hw"]
            lines.append(
                f"  {r['level']:<4d} {hw:<10s} "
                f"{r['slab_rows']:<10d} {r['slab_bytes'] / 1024:<10.1f} "
                f"{r['slab_dtype']:<9s} "
                f"{r['block_q']:<8d} {r['q_steps']:<6d} {r['gather']:<11s} "
                f"{100 * r['vmem_frac']:<6.1f} {100 * r['vmem_frac_predicted']:.1f}")
        return "\n".join(lines)

    # -- degradation ladder -----------------------------------------------
    def rung_label(self) -> str:
        """Short human token for this plan's ladder rung, e.g.
        ``"pallas/topk+morton"`` / ``"pallas"`` / ``"ref"``."""
        traits = []
        if self.tuning.sparsity == "topk":
            traits.append("topk")
        if self.tuning.query_order == "morton":
            traits.append("morton")
        return "/".join([self.backend] + (["+".join(traits)] if traits else []))

    def fallback(self, *, mesh=None) -> Optional["MsdaPlan"]:
        """One rung down the degradation ladder (None at the bottom).

        The ladder walks from most- to least-optimised, one committed
        decision at a time::

            sparse / reordered (topk, morton)  ->  dense identity, same backend
            dense, non-ref backend             ->  the "ref" oracle
            ref                                ->  None (nothing below the oracle)

        A plan whose Pallas kernels are compiled (``interpret=False``:
        a TPU) has no oracle rung — falling back to XLA there would hide
        a failing device kernel; its dense rung is the bottom.

        Built RACE-FREE from the existing spec: the demoted plan pins
        the axes it drops (``sparsity="off"``, ``query_order=
        "identity"``) and is constructed with
        ``tune="heuristic"`` — no autotune timing run executes and no
        winner is ever persisted, so a circuit-breaker demotion cannot
        poison the winner cache with panic-built plans (conformance:
        every rung is numerically consistent with the primary — see
        ``tests/conformance.py``).  Mesh-carrying plans need the live
        ``mesh`` object to rebuild their shard wiring; demoting one
        without it raises rather than silently going local.
        """
        if self.mesh_axes and mesh is None:
            raise ValueError(
                f"mesh-carrying plan (mode={self.sharding_mode}) needs "
                "mesh= to build its fallback rung")
        s = self.spec
        if self.tuning.sparsity == "topk" or self.tuning.query_order == "morton":
            backend = self.backend
        elif self.backend != "ref" and not (self.backend == "pallas"
                                            and not self.tuning.interpret):
            backend = "ref"
        else:
            return None
        ns = dataclasses.replace(s, sparsity="off", query_order="identity")
        return msda_plan(ns, backend=backend, tune="heuristic", mesh=mesh,
                         query_parallel=self.query_parallel,
                         interpret=self.tuning.interpret)

    def fallback_chain(self, *, mesh=None) -> Tuple["MsdaPlan", ...]:
        """Every rung below this plan, top to bottom (ends at the ref
        oracle, or at dense Pallas for compiled kernels; empty for a
        plan already on the bottom rung)."""
        chain: List[MsdaPlan] = []
        p = self.fallback(mesh=mesh)
        while p is not None:
            chain.append(p)
            p = p.fallback(mesh=mesh)
        return tuple(chain)


# --------------------------------------------------------------------------
# the plan cache (explicit, bounded — replaces the old unbounded lru_cache
# on the compiled op; serving processes call clear_plans() to drop them)
# --------------------------------------------------------------------------

_PLAN_CACHE: "OrderedDict[tuple, MsdaPlan]" = OrderedDict()
_PLAN_CACHE_MAX = 128
_CACHE_STATS = {
    "hits": _obs.counter("msda.plan_cache.hits",
                         help="in-process plan-cache hits"),
    "misses": _obs.counter("msda.plan_cache.misses",
                           help="in-process plan-cache misses (plan builds)"),
}


def configure_plan_cache(maxsize: int) -> None:
    """Bound the in-process plan cache (evicts LRU beyond ``maxsize``)."""
    global _PLAN_CACHE_MAX
    _PLAN_CACHE_MAX = max(1, int(maxsize))
    while len(_PLAN_CACHE) > _PLAN_CACHE_MAX:
        _PLAN_CACHE.popitem(last=False)


def clear_plans() -> None:
    """Drop every cached plan (and its compiled op closures).

    Hit/miss counters survive the clear (they are monotonic process
    counters, so an engine shutdown does not erase the metrics export);
    zero them explicitly with ``obs.reset("msda.plan_cache")``.
    """
    _PLAN_CACHE.clear()


def plan_cache_info() -> Dict[str, int]:
    return {"hits": int(_CACHE_STATS["hits"].value()),
            "misses": int(_CACHE_STATS["misses"].value()),
            "size": len(_PLAN_CACHE), "maxsize": _PLAN_CACHE_MAX}


def _hit_rate(hits: int, misses: int) -> Optional[float]:
    total = hits + misses
    return (hits / total) if total else None


def execution_telemetry() -> Dict[str, Any]:
    """Process-wide plan-execution counters, registry-backed.

    The block the serve/train snapshots embed: plan-cache and
    winner-cache hit rates plus Pallas launches per direction.  Launch
    counts are *static-schedule x traced-call* attributions — each
    :meth:`MsdaPlan.__call__` whose Python body runs (eagerly, or once
    per jit trace / AOT compile) adds its plan's per-call launch
    schedule, so a zero-retrace serving steady state adds zero.
    """
    pc = plan_cache_info()
    a = autotune_stats()
    wc_misses = int(_WINNER_CACHE_MISSES.value())
    return {
        "plan_cache": {
            "hits": pc["hits"], "misses": pc["misses"],
            "size": pc["size"],
            "hit_rate": _hit_rate(pc["hits"], pc["misses"]),
        },
        "winner_cache": {
            "hits": a["cache_hits"], "misses": wc_misses,
            "seeded": a["seeded"],
            "hit_rate": _hit_rate(a["cache_hits"], wc_misses),
        },
        "launches": {
            "fwd": int(_LAUNCHES.value(direction="fwd")),
            "bwd": int(_LAUNCHES.value(direction="bwd")),
            "plan_calls": int(_PLAN_CALLS.total()),
        },
    }


def msda_plan(
    spec: MsdaSpec,
    *,
    backend: str = "auto",
    tune: str = "heuristic",
    mesh=None,
    query_parallel: bool = False,
    sharding: str = "auto",
    grad_reduce: str = "auto",
    block_q: Optional[Tuple[int, ...]] = None,
    interpret: Optional[bool] = None,
) -> MsdaPlan:
    """Resolve backend + tuning + sharding for ``spec``; cached.

    ``tune``: ``"heuristic"`` uses the paper's VMEM-occupancy model
    (Fig. 7); ``"autotune"`` times candidate block plans on synthetic
    operands and persists winners per (device kind, spec) on disk.
    ``block_q`` overrides both: one entry per level, all equal (the one
    launch over the packed pyramid takes one block).  ``mesh`` bakes the
    shard_map wiring into the returned plan; ``sharding`` picks the
    distribution family — ``"auto"`` walks the ladder (and, under
    ``tune="autotune"``, RACES 1D vs 2D vs hybrid and persists the
    winner per mesh topology), ``"1d"`` pins the classic
    query/head/batch ladder, ``"2d"`` forces dp x tp query tiling when
    legal, ``"hybrid"`` forces the batch x query whole-mesh tiling
    (mid-size B on tp-less meshes).  ``grad_reduce``
    picks the query-sharded backward's grad_value reduction:
    ``"ring"`` (default via "auto") circulates the fp32 slab over the
    tp axis with ppermute, ``"psum"`` keeps shard_map's transpose
    all-reduce (ablation / parity baseline).
    """
    if tune not in ("heuristic", "autotune"):
        raise ValueError(f"unknown tune mode {tune!r}; use 'heuristic' or 'autotune'")
    if sharding not in SHARDING_CHOICES:
        raise ValueError(
            f"unknown sharding {sharding!r}; one of {SHARDING_CHOICES}")
    if grad_reduce not in GRAD_REDUCE_CHOICES:
        raise ValueError(
            f"unknown grad_reduce {grad_reduce!r}; one of {GRAD_REDUCE_CHOICES}")
    backend_name = registry.resolve_backend(backend)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if mesh is not None and mesh.devices.size <= 1:
        mesh = None  # single-device mesh: sharding is a no-op

    key = (spec, backend_name, tune, tuple(block_q) if block_q else None,
           bool(interpret), _mesh_cache_key(mesh), bool(query_parallel),
           sharding, grad_reduce)
    cached = _PLAN_CACHE.get(key)
    if cached is not None:
        _CACHE_STATS["hits"].inc()
        _PLAN_CACHE.move_to_end(key)
        return cached
    _CACHE_STATS["misses"].inc()

    builder = registry.get_backend(backend_name)

    def _build_local_impl(s: MsdaSpec) -> Tuple[Callable, PlanTuning]:
        if backend_name == "pallas":
            check_fit(s)
        dts = _default_slab_dtypes(s)
        sparsity, qorder = _resolve_sparsity(s), _resolve_query_order(s)
        if block_q is not None:
            if len(block_q) != s.num_levels:
                raise ValueError(
                    f"block_q has {len(block_q)} entries for {s.num_levels} levels")
            if len(set(block_q)) != 1:
                raise ValueError(
                    f"block_q {tuple(block_q)} differs by level: the one "
                    "launch over the packed pyramid takes one block")
            bq, source = tuple(int(b) for b in block_q), "override"
        elif tune == "autotune" and backend_name != "ref":
            bq, dts, sparsity, qorder, source = _autotune_plan(
                s, backend_name, builder, interpret)
        else:
            bq, source = _heuristic_block_q(s, dts), "heuristic"
        tuning = PlanTuning(block_q=bq, interpret=interpret, source=source,
                            slab_dtypes=dts, sparsity=sparsity,
                            query_order=qorder)
        # a pruned plan swaps in the top-k executor (the backend's dense
        # executor is the fallback every other decision still describes);
        # dense+identity is byte-identical to the pre-sparsity build
        if sparsity == "topk":
            exec_fn = _apply_sparsity_wrappers(None, s, sparsity, qorder)
        else:
            exec_fn = _apply_sparsity_wrappers(
                builder(s, tuning), s, sparsity, qorder)
        return exec_fn, tuning

    def build_local(s: MsdaSpec) -> Tuple[Callable, PlanTuning]:
        # the span wraps ONE local build (sharded plans may build both
        # race candidates); autotune races nest inside as children
        with _obs_trace.span("plan.build", level=2, backend=backend_name,
                             q=s.num_queries, levels=s.num_levels,
                             train=s.train, tune=tune) as sp:
            exec_fn, tuning = _build_local_impl(s)
            sp["source"] = tuning.source
            return exec_fn, tuning

    if mesh is None:
        exec_fn, tuning = build_local(spec)
        plan = MsdaPlan(spec=spec, backend=backend_name, tuning=tuning,
                        sharding_mode="local", local_spec=spec, _exec=exec_fn,
                        tune=tune)
    else:
        shard_choice, prebuilt = sharding, None
        # the 1D-vs-2D race rides on query-parallel INTENT: 2D is the
        # huge-Q encoder's axis, so plans that never asked to tile
        # queries (head/batch users) are not surprise-resharded by a
        # timing run
        if tune == "autotune" and sharding == "auto" and query_parallel:
            shard_choice, prebuilt = _autotune_sharding(
                spec, backend_name, mesh, query_parallel, grad_reduce,
                build_local)
        if prebuilt is not None:
            # the race already built (and block-planned) the winner
            exec_fn, tuning, (mode, dp, tp, tp_size, local_spec), inner_exec = prebuilt
        else:
            mode, dp, tp, tp_size, local_spec = _plan_sharding(
                spec, mesh, query_parallel, shard_choice)
            inner_exec, tuning = build_local(local_spec)
            exec_fn = _build_sharded_exec(
                spec, inner_exec, local_spec, mesh, mode, dp, tp, tp_size,
                grad_reduce)
        resolved_gr = _resolve_grad_reduce(grad_reduce, mode, tp_size)
        if (tune == "autotune" and grad_reduce == "auto" and spec.train
                and resolved_gr == "ring"):
            # raced grad_value reduction (ring vs psum) per mesh topology
            choice, raced_exec = _autotune_grad_reduce(
                spec, backend_name, mesh, query_parallel, mode, dp, tp,
                tp_size, inner_exec, local_spec, tuning)
            if choice != "ring":
                exec_fn = raced_exec or _build_sharded_exec(
                    spec, inner_exec, local_spec, mesh, mode, dp, tp,
                    tp_size, choice)
                resolved_gr = choice
        plan = MsdaPlan(spec=spec, backend=backend_name, tuning=tuning,
                        sharding_mode=mode, local_spec=local_spec,
                        _exec=exec_fn,
                        mesh_axes=tuple(mesh.axis_names),
                        mesh_shape=tuple(int(s) for s in mesh.devices.shape),
                        query_parallel=bool(query_parallel),
                        grad_reduce=resolved_gr,
                        batch_tile=(int(mesh.devices.size) // tp_size
                                    if mode == "batchquery" else 0),
                        tune=tune)
    _PLAN_CACHE[key] = plan
    while len(_PLAN_CACHE) > _PLAN_CACHE_MAX:
        _PLAN_CACHE.popitem(last=False)
    return plan
