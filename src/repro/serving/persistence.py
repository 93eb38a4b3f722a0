"""Persistent plan store: rebuild a server's full plan set across restarts.

Three things make a cold serving boot slow: plan construction (block
planning), the autotune candidate races (real timing runs), and XLA
compilation.  This module removes all three from a *restarted* process:

* :class:`PlanStore` — a versioned JSON file holding every warmed
  :class:`~repro.kernels.plan.MsdaPlan`'s spec, backend, tune mode and
  autotune winner.  ``restore()`` seeds the winners into the on-disk
  autotune cache (``seed_autotune_winner`` — same ``cache_token()``
  keying the race itself uses) and rebuilds each plan; ``tune="autotune"``
  then resolves to ``autotune-cache`` with ZERO timing runs, which the
  CI serving-smoke job asserts via ``plan.autotune_stats()``.
* :func:`enable_jax_compilation_cache` — wires JAX's persistent
  compilation cache to a directory, so the restarted process's AOT
  ``lower().compile()`` calls at boot are disk hits, not fresh XLA
  compiles (:func:`compilation_cache_entries` counts the artifacts for
  the smoke job's no-recompilation assertion).

The store is written atomically (tmp + rename) and refuses nothing at
read time: a missing file, a version mismatch, or an entry written by a
newer schema all degrade to a cold start for that entry, never an error
— a stale store must not take a server down.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
from jax.experimental.compilation_cache import compilation_cache

from repro.kernels import plan as plan_mod
from repro.obs import trace as _obs_trace

# v2 grew the optional per-entry "sharding" record (distributed plans:
# mode, mesh axes/shape, query_parallel, grad_reduce) and the mesh-keyed
# winner seeding that goes with it.  v3 grew the fusion and one-hot
# routing decisions and the optional winner field ``grad_reduce``.  v4
# grew the hybrid batch x query sharding mode ('batchquery', with its
# ``batch_tile`` in the sharding record) and the
# elastic restore path (``on_mesh_mismatch="rerace"``).  v5 grew the
# sparsity axes: specs carry ``sparsity``/``sparsity_k``/``query_order``
# and autotune winners the optional ``sparsity`` / ``query_order``
# fields (pruned-vs-dense and Morton-vs-identity race decisions).
# v6 grew the partial-fusion tier.  Every plan now runs one launch per
# direction over the packed pyramid, so the fusion, walk and one-hot
# keys of v3-v6 stores (``plan.RETIRED_KEYS``) are read and dropped,
# and nothing writes them.  v1-v5 stores load unchanged otherwise;
# entries a NEWER schema writes still degrade per entry, and unknown
# winner fields ride through the parse/rewrite cycle untouched
# (``_winner_entry`` extras).
PLAN_STORE_VERSION = 6
_READABLE_VERSIONS = (1, 2, 3, 4, 5, 6)

# stored sharding mode -> the planner's sharding= pin that reproduces it
_MODE_TO_CHOICE = {"query2d": "2d", "batchquery": "hybrid"}


def _device_kind() -> str:
    try:
        return jax.devices()[0].device_kind
    except Exception:
        return "unknown"


def _norm_describe(text: str) -> str:
    """Canonical describe() for drift comparison: a plan autotuned live
    and the same plan restored from its persisted winner differ only in
    the tune-source tag ("autotune" vs "autotune-cache") — that is
    provenance, not plan content."""
    return text.replace("tune=autotune-cache", "tune=autotune")


@dataclasses.dataclass
class RestoreReport:
    """What a ``PlanStore.restore()`` actually did."""

    plans: List[Any] = dataclasses.field(default_factory=list)
    seeded_winners: int = 0
    skipped: List[str] = dataclasses.field(default_factory=list)
    describe_mismatches: List[str] = dataclasses.field(default_factory=list)
    # entries whose stored mesh topology did not match the process's and
    # were recovered by re-racing the mesh-keyed axes (elastic restore,
    # ``on_mesh_mismatch="rerace"``); one human-readable line per entry
    reraced: List[str] = dataclasses.field(default_factory=list)

    @property
    def cold(self) -> bool:
        # "cold" = nothing restored.  An unreadable store also lands a
        # named line in ``skipped``, but the boot is cold either way.
        return not self.plans


class PlanStore:
    """Versioned on-disk record of a serving process's warmed plans."""

    def __init__(self, path: str):
        self.path = str(path)

    def exists(self) -> bool:
        return os.path.exists(self.path)

    # -- save --------------------------------------------------------------
    def save_plans(self, plans: Sequence, *, meta: Optional[Dict[str, Any]] = None) -> int:
        """Serialise every plan — local AND distributed; returns the count.

        Autotuned plans store their winner; heuristic plans re-derive
        their blocks deterministically at restore (same spec, same
        device kind -> same plan), so nothing extra is persisted.

        Mesh-carrying plans store their distribution record (mode, mesh
        axes + shape, query_parallel, grad_reduce) — NOT device objects;
        a restarted process supplies its own mesh to ``restore(mesh=...)``
        and the entry only applies when the topology matches, so a store
        written on a 2x2 slice never silently mis-shards a 1x4 boot.
        The winner of a sharded plan is keyed on its LOCAL (per-shard)
        spec plus a mesh-keyed 1D-vs-2D entry; both are re-seeded at
        restore so the rebuild races nothing.
        """
        entries = []
        for plan in plans:
            src = plan.tuning.source
            entry: Dict[str, Any] = {
                "spec": plan_mod.spec_to_json(plan.spec),
                "backend": plan.backend,
                "tune": ("autotune"
                         if src.startswith("autotune")
                         or getattr(plan, "tune", "heuristic") == "autotune"
                         else "heuristic"),
                "source": src,
                "device_kind": _device_kind(),
                "describe": plan.describe(),
            }
            if plan.sharding_mode != "local":
                entry["sharding"] = {
                    "mode": plan.sharding_mode,
                    "mesh_axes": list(plan.mesh_axes),
                    "mesh_shape": [int(s) for s in plan.mesh_shape],
                    "query_parallel": bool(plan.query_parallel),
                    "grad_reduce": plan.grad_reduce,
                }
                if plan.sharding_mode == "batchquery":
                    entry["sharding"]["batch_tile"] = int(plan.batch_tile)
            if src == "override":
                entry["block_q"] = [int(b) for b in plan.tuning.block_q]
            if src.startswith("autotune"):
                winner: Dict[str, Any] = {
                    "block_q": [int(b) for b in plan.tuning.block_q],
                    "slab_dtypes": list(plan.tuning.slab_dtypes),
                }
                # the sparsity rungs' raced decisions persist only when
                # the axis actually raced ('auto') — pinned/off specs
                # keep their pre-sparsity entry byte-identical
                if plan.spec.sparsity == "auto":
                    winner["sparsity"] = plan.tuning.sparsity
                if plan.spec.query_order == "auto":
                    winner["query_order"] = plan.tuning.query_order
                entry["winner"] = winner
            entries.append(entry)
        payload = {
            "version": PLAN_STORE_VERSION,
            "jax": jax.__version__,
            "device_kind": _device_kind(),
            "created_unix": time.time(),
            "meta": meta or {},
            "entries": entries,
        }
        os.makedirs(os.path.dirname(os.path.abspath(self.path)), exist_ok=True)
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        os.replace(tmp, self.path)
        return len(entries)

    # -- load / restore ----------------------------------------------------
    def load(self) -> Optional[Dict[str, Any]]:
        """Raw payload, or None when missing/corrupt/wrong version."""
        data, _ = self._load_with_reason()
        return data

    def _load_with_reason(self):
        """(payload, None) or (None, reason) — the reason distinguishes a
        merely-missing store (no message) from a store that EXISTS but
        could not be read, which ``restore()`` surfaces in
        ``report.skipped`` instead of silently booting cold."""
        try:
            with open(self.path) as f:
                data = json.load(f)
        except FileNotFoundError:
            return None, None
        except OSError as e:
            return None, f"store {self.path}: unreadable ({e})"
        except ValueError as e:
            return None, f"store {self.path}: corrupt JSON ({e})"
        if not isinstance(data, dict):
            return None, f"store {self.path}: not a JSON object"
        if data.get("version") not in _READABLE_VERSIONS:
            return None, (f"store {self.path}: version {data.get('version')!r} "
                          f"not in readable {_READABLE_VERSIONS}")
        return data, None

    @_obs_trace.traced_span("plan.restore", level=2)
    def restore(self, *, mesh=None, verify_describe: bool = True,
                on_mesh_mismatch: str = "skip") -> RestoreReport:
        """Rebuild every stored plan; zero autotune races, by seeding.

        For each entry: the persisted winner (if any, and if recorded on
        this device kind) is seeded into the autotune disk cache first,
        so the subsequent ``msda_plan(..., tune="autotune")`` is a cache
        hit — plan construction runs, timing does not.  Entries that
        fail to parse (newer schema, unknown backend) are recorded in
        ``report.skipped`` — each line names the offending ENTRY (index,
        backend, geometry), never the whole file — and the boot proceeds
        cold for them.  A store that exists but cannot be read at all is
        itself one named ``skipped`` line.

        ``mesh``: the restarting process's mesh.  A distributed entry is
        rebuilt only when the mesh's (axis names, shape) match the
        entry's record — its winner is then ALSO seeded under the
        mesh-keyed sharding-race key and its local (per-shard) spec key,
        and the plan is rebuilt with the stored mode PINNED, so the
        restore performs zero sharding races and zero block races.

        ``on_mesh_mismatch`` decides what a topology mismatch does:

        * ``"skip"`` (default — the serving boot contract): the entry is
          recorded in ``report.skipped`` and that plan boots cold.
        * ``"rerace"`` (the elastic training path): the entry's LOCAL
          winner is re-seeded onto the per-shard geometry the NEW mesh
          implies — so the block/dtype axes stay zero-timing cache
          hits — and the plan is rebuilt under ``sharding="auto"`` /
          ``grad_reduce="auto"``, which re-races EXACTLY the mesh-keyed
          axes (sharding mode, grad_value reduction) and persists the
          new winners per the new topology.  Recovered entries are
          listed in ``report.reraced``.  When the topology matches,
          behaviour is identical to "skip" (zero re-race either way).
        """
        if on_mesh_mismatch not in ("skip", "rerace"):
            raise ValueError(
                f"on_mesh_mismatch={on_mesh_mismatch!r}; 'skip' or 'rerace'")
        report = RestoreReport()
        data, why = self._load_with_reason()
        if data is None:
            if why:
                report.skipped.append(why)
            return report
        here = _device_kind()

        def _label(i, entry) -> str:
            """Name the offending entry, not the whole file."""
            bits = [f"entry {i}"]
            try:
                s = entry.get("spec") or {}
                bits.append(f"backend={entry.get('backend')}")
                if "num_queries" in s:
                    bits.append(f"Q={s['num_queries']}")
                if "spatial_shapes" in s:
                    bits.append(f"levels={len(s['spatial_shapes'])}")
                shard = entry.get("sharding")
                if shard:
                    bits.append(f"mode={shard.get('mode')}")
            except Exception:  # noqa: BLE001 — labels must never throw
                pass
            return " ".join(bits)

        # pass 1: parse specs + batch-seed every winner (one cache write)
        parsed = []
        seeds = []
        for i, entry in enumerate(data.get("entries", ())):
            try:
                spec = plan_mod.spec_from_json(entry["spec"])
                shard = entry.get("sharding")
                choice = None
                elastic = False
                if shard is not None:
                    if mesh is None:
                        raise ValueError(
                            f"distributed entry ({shard.get('mode')}) needs a mesh")
                    if (list(mesh.axis_names) != list(shard["mesh_axes"])
                            or [int(s) for s in mesh.devices.shape]
                            != [int(s) for s in shard["mesh_shape"]]):
                        if on_mesh_mismatch != "rerace":
                            raise ValueError(
                                f"mesh mismatch: store has "
                                f"{plan_mod.mesh_token_from(shard['mesh_axes'], shard['mesh_shape'])}, "
                                f"process has {plan_mod.mesh_token(mesh)}")
                        elastic = True
                    if not elastic:
                        choice = _MODE_TO_CHOICE.get(shard["mode"], "1d")
                parsed.append((i, entry, spec, shard, choice, elastic))
            except Exception as e:  # noqa: BLE001 — degrade per entry, never die
                report.skipped.append(
                    f"{_label(i, entry)}: {type(e).__name__}: {e}")
                continue
            if (entry.get("winner") is not None and entry.get("backend")
                    and entry.get("device_kind", here) == here):
                if shard is None:
                    seeds.append((spec, entry["backend"], entry["winner"]))
                elif elastic:
                    # topology changed: the stored LOCAL winner still
                    # applies — re-key it onto the per-shard geometry
                    # the NEW mesh's auto ladder implies (blocks clamped
                    # to the new local query extent), so the rebuild's
                    # block/dtype races are cache hits and only the
                    # mesh-keyed axes re-race
                    qp = bool(shard.get("query_parallel"))
                    _, local_spec = plan_mod.resolve_sharding(
                        spec, mesh, qp, "auto")
                    winner = dict(entry["winner"])
                    bq = winner.get("block_q")
                    if isinstance(bq, list):
                        qcap = -(-local_spec.num_queries // 8) * 8
                        winner["block_q"] = [
                            max(8, min(int(b), qcap)) for b in bq]
                    seeds.append((local_spec, entry["backend"], winner))
                else:
                    qp = bool(shard.get("query_parallel"))
                    # the block/dtype winner belongs to the LOCAL spec
                    # (the geometry the race actually timed) ...
                    _, local_spec = plan_mod.resolve_sharding(
                        spec, mesh, qp, choice)
                    seeds.append((local_spec, entry["backend"], entry["winner"]))
                    # ... and the sharding choice — plus the raced
                    # grad_value reduction, so request-time
                    # grad_reduce="auto" plans resolve it from the cache
                    # instead of re-racing ring vs psum — to the
                    # mesh-keyed race entry
                    mesh_winner = dict(entry["winner"], sharding=choice)
                    if shard.get("grad_reduce") in ("ring", "psum"):
                        mesh_winner["grad_reduce"] = shard["grad_reduce"]
                    seeds.append((spec, entry["backend"], mesh_winner,
                                  plan_mod.mesh_winner_suffix(mesh, qp)))
        report.seeded_winners = plan_mod.seed_autotune_winners(seeds)
        # pass 2: rebuild the plans (autotune resolves via the seeds)
        for i, entry, spec, shard, choice, elastic in parsed:
            try:
                block_q = entry.get("block_q")
                kwargs: Dict[str, Any] = {}
                if shard is not None:
                    kwargs = dict(
                        mesh=mesh,
                        query_parallel=bool(shard.get("query_parallel")),
                        grad_reduce=shard.get("grad_reduce") or "auto")
                    if kwargs["grad_reduce"] == "none" or elastic:
                        # elastic: the stored reduction was raced on the
                        # OLD topology — let the new mesh re-race it
                        kwargs["grad_reduce"] = "auto"
                common = dict(
                    backend=entry["backend"],
                    tune=entry.get("tune", "heuristic"),
                    block_q=tuple(block_q) if block_q else None, **kwargs)
                if shard is not None and not elastic:
                    # try sharding="auto" FIRST: the request path
                    # (attention_plan with the config default) asks for
                    # "auto", and the plan cache keys on the sharding
                    # string — restoring under "auto" lets requests hit
                    # THIS plan object.  The seeded mesh-race winner
                    # pins "auto" to the stored mode with zero timing;
                    # if the ladder still resolves differently (e.g. a
                    # 2d-forced plan below the auto threshold), retry
                    # with the mode pinned so the rebuild stays exact.
                    plan = plan_mod.msda_plan(spec, sharding="auto", **common)
                    if plan.sharding_mode != shard["mode"]:
                        plan = plan_mod.msda_plan(
                            spec, sharding=choice, **common)
                else:
                    plan = plan_mod.msda_plan(spec, sharding="auto", **common) \
                        if elastic else plan_mod.msda_plan(spec, **common)
                if shard is not None and not elastic \
                        and plan.sharding_mode != shard["mode"]:
                    report.skipped.append(
                        f"{_label(i, entry)}: sharding mode drifted "
                        f"({shard['mode']} -> {plan.sharding_mode})")
                    continue
            except Exception as e:  # noqa: BLE001
                report.skipped.append(
                    f"{_label(i, entry)}: {type(e).__name__}: {e}")
                continue
            if elastic:
                report.reraced.append(
                    f"{_label(i, entry)}: "
                    f"{plan_mod.mesh_token_from(shard['mesh_axes'], shard['mesh_shape'])} "
                    f"-> {plan_mod.mesh_token(mesh)} "
                    f"({shard['mode']} -> {plan.sharding_mode})")
            elif verify_describe and entry.get("describe"):
                # (describe drift is only meaningful when the geometry
                # was supposed to be identical — elastic entries changed
                # topology by definition)
                if _norm_describe(plan.describe()) != _norm_describe(entry["describe"]):
                    report.describe_mismatches.append(
                        f"entry {i}: plan.describe() differs from stored "
                        f"(device_kind {entry.get('device_kind')} -> {here}?)")
            report.plans.append(plan)
        return report


# --------------------------------------------------------------------------
# JAX persistent compilation cache
# --------------------------------------------------------------------------


# the checkout root (src/repro/serving/persistence.py -> three levels up)
CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def compilation_cache_dir(requested: Optional[str] = None) -> str:
    """Where JAX's persistent compilation cache lives, by one rule.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins over everything (JAX
    reads it itself; ``requested`` cannot override it).  Otherwise the
    ``requested`` directory (a launcher's ``--compile-cache``), else the
    fixed ``.jax_cache/`` at the checkout root — fixed, because the
    path is part of every entry's key and a moving directory never hits.
    """
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    return requested or os.path.join(CHECKOUT_ROOT, ".jax_cache")


def enable_jax_compilation_cache(requested: Optional[str] = None) -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Thresholds are zeroed so even the CPU tier's fast compiles persist
    (the default min-compile-time gate would skip them, and the serving
    smoke's no-recompilation assertion needs every executable cached).
    Call it before the first compile; any failure raises.
    """
    cache_dir = compilation_cache_dir(requested)
    os.makedirs(cache_dir, exist_ok=True)
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # jax latches cache initialisation at the process's FIRST compile and
    # never re-reads the dir config: a boot that compiled anything (params
    # init!) before reaching here would silently cache nothing.  Drop the
    # latched state so the next compile re-reads it.
    compilation_cache.reset_cache()
    return cache_dir


def compilation_cache_entries(cache_dir: str) -> int:
    """Number of persisted executables (the smoke job's probe)."""
    try:
        return sum(1 for n in os.listdir(cache_dir) if n.endswith("-cache"))
    except OSError:
        return 0
