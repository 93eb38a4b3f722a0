"""Elastic plan recovery: mesh resize -> re-race mesh axes -> persist.

The PlanStore mesh gate used to REJECT a store written on a different
topology (restart boots cold, re-autotunes everything).  These tests
pin the recover path: ``repro.training.elastic.recover_plans`` re-keys
each entry's LOCAL winner (block/dtype axes stay cache hits — zero
local timing runs) and re-races ONLY the mesh-keyed axes (sharding
mode, grad_value reduction), then persists the new winners so the next
restart on the new topology races nothing at all.

Runs under the conftest's 4 virtual CPU devices.
"""
import json

import jax
import pytest

from repro.kernels import plan as pm
from repro.launch import mesh as mesh_lib
from repro.serving.persistence import PlanStore
from repro.training import elastic

_LEVELS = ((8, 8), (4, 4))


def _mesh(dp, tp):
    if len(jax.devices()) < dp * tp:
        pytest.skip(f"needs {dp * tp} devices")
    return mesh_lib.make_mesh_2d(dp, tp)


def _spec(q=16):
    return pm.MsdaSpec(spatial_shapes=_LEVELS, num_heads=2, head_dim=8,
                       num_points=2, num_queries=q, train=True)


@pytest.fixture()
def fresh_caches(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_MSDA_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    pm.clear_plans()
    pm.reset_autotune_stats()
    yield tmp_path
    pm.clear_plans()


def test_recover_plans_missing_store_is_cold_boot(tmp_path):
    rep = elastic.recover_plans(str(tmp_path / "nope.json"))
    assert rep.plans == [] and rep.replan_count == 0 and not rep.persisted


def test_recover_plans_matching_mesh_zero_races(fresh_caches):
    """Topology unchanged -> plain seeded restore, no timing runs."""
    tmp_path = fresh_caches
    mesh = _mesh(2, 2)
    plan = pm.msda_plan(_spec(), backend="ref", tune="autotune", mesh=mesh,
                        query_parallel=True)
    store = PlanStore(str(tmp_path / "plans.json"))
    store.save_plans([plan])
    pm.clear_plans()
    pm.reset_autotune_stats()
    rep = elastic.recover_plans(str(tmp_path / "plans.json"), mesh=mesh)
    assert len(rep.plans) == 1 and rep.replan_count == 0
    assert rep.raced == 0 and not rep.persisted
    assert rep.plans[0].sharding_mode == plan.sharding_mode


def test_recover_plans_reraces_mesh_axes_only_and_persists(fresh_caches):
    """Acceptance: a store built on 2x2 restored onto 1x4 re-races
    exactly the mesh-keyed axes (raced_local == 0) while reusing every
    local winner, persists the new winners, and the NEXT 1x4 restore
    does zero timing runs."""
    tmp_path = fresh_caches
    store_path = str(tmp_path / "plans.json")
    m22, m14 = _mesh(2, 2), _mesh(1, 4)
    plan = pm.msda_plan(_spec(), backend="ref", tune="autotune", mesh=m22,
                        query_parallel=True)
    PlanStore(store_path).save_plans([plan])

    # the resized restart
    pm.clear_plans()
    pm.reset_autotune_stats()
    rep = elastic.recover_plans(store_path, mesh=m14)
    assert rep.replan_count == 1 and len(rep.plans) == 1
    assert rep.raced_local == 0, "local axes must come from the seeded winner"
    assert rep.raced_mesh >= 1, "the mesh-keyed axes must actually re-race"
    assert rep.persisted
    assert "data2xmodel2 -> data1xmodel4" in rep.reraced[0]
    assert rep.plans[0].sharding_mode in ("query", "query2d", "batchquery")

    # the store now belongs to the new topology
    with open(store_path) as f:
        data = json.load(f)
    assert data["meta"]["mesh"] == "data1xmodel4"
    assert data["meta"]["elastic_reraced"] == 1

    # second restart on 1x4: zero races of ANY kind
    pm.clear_plans()
    pm.reset_autotune_stats()
    rep2 = elastic.recover_plans(store_path, mesh=m14)
    assert rep2.replan_count == 0 and rep2.raced == 0
    assert len(rep2.plans) == 1
    assert rep2.plans[0].sharding_mode == rep.plans[0].sharding_mode


def test_restore_default_still_rejects_mismatch(fresh_caches):
    """The elastic path is opt-in: restore()'s default mesh gate still
    degrades a mismatched entry to a skip (serving semantics, pinned by
    test_sharding_dist), and the rerace mode must be requested by name."""
    tmp_path = fresh_caches
    store_path = str(tmp_path / "plans.json")
    m22 = _mesh(2, 2)
    plan = pm.msda_plan(_spec(), backend="ref", mesh=m22, sharding="2d")
    store = PlanStore(store_path)
    store.save_plans([plan])
    pm.clear_plans()
    rep = store.restore(mesh=_mesh(1, 4))  # default on_mesh_mismatch="skip"
    assert not rep.plans and len(rep.skipped) == 1
    assert "mismatch" in rep.skipped[0]
    with pytest.raises(ValueError, match="on_mesh_mismatch"):
        store.restore(mesh=m22, on_mesh_mismatch="explode")


def test_corrupt_store_errors_name_the_offender(tmp_path):
    """Store-level corruption names the file; entry-level corruption
    names the entry — never a bare stack trace, never a silent skip."""
    p = tmp_path / "plans.json"
    p.write_text("{ not json")
    rep = PlanStore(str(p)).restore()
    assert not rep.plans and len(rep.skipped) == 1
    assert "corrupt JSON" in rep.skipped[0] and str(p) in rep.skipped[0]

    # valid store, one unreadable entry: the OTHER entries still restore
    good = pm.msda_plan(_spec(), backend="ref")
    store = PlanStore(str(tmp_path / "plans2.json"))
    store.save_plans([good])
    with open(store.path) as f:
        data = json.load(f)
    data["entries"].insert(0, {"backend": "ref", "garbage": True})
    with open(store.path, "w") as f:
        json.dump(data, f)
    pm.clear_plans()
    rep = store.restore()
    assert len(rep.plans) == 1
    assert len(rep.skipped) == 1 and "entry 0" in rep.skipped[0]
    pm.clear_plans()
