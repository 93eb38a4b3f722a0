"""Elastic plan recovery: mesh resize -> re-race -> persist.

The rung ROADMAP's "End-to-end training at scale" item asked for: a
training process that restarts on a DIFFERENT topology (lost a host,
grew a slice) used to hit the ``PlanStore`` mesh gate and boot cold —
worse, ``restore(mesh=...)`` refused the store outright.
:func:`recover_plans` flips that gate from reject to recover:

* topology matches  -> plain restore, zero timing runs (unchanged);
* topology differs  -> ``restore(..., on_mesh_mismatch="rerace")``
  re-keys each entry's LOCAL autotune winner onto the new per-shard
  geometry (block/dtype axes stay cache hits) and re-races ONLY
  the mesh-keyed axes — the sharding mode (1d / 2d / hybrid) and the
  grad_value reduction (ring / psum) — then **persists the new
  winners** back to the store, so the NEXT restart on this topology is
  again a zero-race boot.

The returned :class:`ElasticPlanReport` carries what happened (how many
entries re-raced, the autotune stat delta) so the harness's telemetry
can report the re-plan count and its latency.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

from repro.kernels import plan as plan_mod
from repro.serving.persistence import PlanStore


@dataclasses.dataclass
class ElasticPlanReport:
    """What :func:`recover_plans` did."""

    plans: List[Any] = dataclasses.field(default_factory=list)
    reraced: List[str] = dataclasses.field(default_factory=list)
    skipped: List[str] = dataclasses.field(default_factory=list)
    seeded_winners: int = 0
    # autotune stat deltas across the restore: a matching-topology boot
    # has raced == 0; a resized one has raced_mesh >= 1, raced_local == 0
    raced: int = 0
    raced_local: int = 0
    raced_mesh: int = 0
    recovery_s: float = 0.0
    persisted: bool = False

    @property
    def replan_count(self) -> int:
        return len(self.reraced)


def recover_plans(store_path: str, *, mesh=None, persist: bool = True,
                  verify_describe: bool = True) -> ElasticPlanReport:
    """Restore a plan store elastically onto ``mesh``.

    Missing store -> empty report (cold boot, not an error).  When any
    entry re-raced (topology changed), the rebuilt plans are written
    back with ``meta.mesh`` updated — restore-then-persist is the whole
    elastic contract — unless ``persist=False``.
    """
    report = ElasticPlanReport()
    store = PlanStore(store_path)
    if not store.exists():
        return report
    before = plan_mod.autotune_stats()
    t0 = time.perf_counter()
    rr = store.restore(mesh=mesh, verify_describe=verify_describe,
                       on_mesh_mismatch="rerace")
    report.recovery_s = time.perf_counter() - t0
    after = plan_mod.autotune_stats()
    report.plans = rr.plans
    report.reraced = rr.reraced
    report.skipped = rr.skipped
    report.seeded_winners = rr.seeded_winners
    for k in ("raced", "raced_local", "raced_mesh"):
        setattr(report, k, after[k] - before[k])
    if rr.reraced and rr.plans and persist:
        meta: Dict[str, Any] = {"elastic_reraced": len(rr.reraced)}
        if mesh is not None:
            meta["mesh"] = plan_mod.mesh_token(mesh)
        store.save_plans(rr.plans, meta=meta)
        report.persisted = True
    return report


def mesh_or_none(mesh) -> Optional[str]:
    """Telemetry helper: the store-meta mesh token (None local)."""
    return None if mesh is None else plan_mod.mesh_token(mesh)
