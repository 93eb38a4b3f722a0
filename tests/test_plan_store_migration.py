"""PlanStore schema migration: every historical version still restores.

``tests/data/plan_store_v{1..5}.json`` are frozen stores as the v1-v5
schemas wrote them (v1 flat-list winners, v2 per-level slab dtypes, v3
fusion + one-hot routing decisions, v4 heuristic entries, v5 sparsity
axes + a newer-build extra field).  Each must restore on the current
build with ZERO autotune timing runs and re-save as a version-6 store
without dropping any winner decision this build still makes — the
compatibility promise the version-history comment in
``repro/serving/persistence.py`` makes.  The keys of retired plan
choices (``plan.RETIRED_KEYS``: the fusion tiers, the corner-major walks,
the one-hot row route, the fixed-block ablation) are read and dropped.
"""
import json
import os

import pytest

from repro.kernels import plan as plan_mod
from repro.serving import persistence

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(autouse=True)
def _isolated(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_MSDA_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    plan_mod.clear_plans()
    plan_mod.reset_autotune_stats()
    yield
    plan_mod.clear_plans()


def _fixture(version):
    return os.path.join(DATA, f"plan_store_v{version}.json")


def _winner_of(path):
    with open(path) as f:
        data = json.load(f)
    entry = data["entries"][0]
    return entry.get("winner"), entry


@pytest.mark.parametrize("version", [1, 2, 3, 4, 5])
def test_historic_store_restores_with_zero_races(version, tmp_path):
    report = persistence.PlanStore(_fixture(version)).restore()
    assert not report.skipped, report.skipped
    assert len(report.plans) == 1
    assert report.describe_mismatches == []
    assert plan_mod.autotune_stats()["raced"] == 0, \
        f"v{version} restore ran a timing race"
    winner, entry = _winner_of(_fixture(version))
    assert report.seeded_winners == (1 if winner is not None else 0)

    # the restored plan carries the stored decisions, not re-derived ones
    plan = report.plans[0]
    assert plan.backend == entry["backend"]
    if isinstance(winner, list):  # v1 flat block_q list
        assert list(plan.tuning.block_q) == winner
    elif isinstance(winner, dict):
        assert list(plan.tuning.block_q) == winner["block_q"]
        if "slab_dtypes" in winner:
            assert list(plan.tuning.slab_dtypes) == winner["slab_dtypes"]
        if "sparsity" in winner:
            assert plan.tuning.sparsity == winner["sparsity"]

    # re-save: the store comes out at the CURRENT version with every
    # winner decision intact (the upgrade path a rolling fleet follows)
    out = persistence.PlanStore(str(tmp_path / "resaved.json"))
    assert out.save_plans(report.plans) == 1
    with open(out.path) as f:
        resaved = json.load(f)
    assert resaved["version"] == persistence.PLAN_STORE_VERSION
    if winner is not None:
        re_winner = resaved["entries"][0]["winner"]
        if isinstance(winner, list):
            assert re_winner["block_q"] == winner
        else:
            for field in ("block_q", "slab_dtypes", "sparsity"):
                if field in winner:
                    assert re_winner[field] == winner[field], field
            assert not set(plan_mod.RETIRED_KEYS) & set(re_winner)
    assert not set(plan_mod.RETIRED_KEYS) & set(resaved["entries"][0]["spec"])

    # the resaved v6 store round-trips again, still race-free
    plan_mod.clear_plans()
    os.environ["REPRO_MSDA_AUTOTUNE_CACHE"] = str(tmp_path / "autotune2.json")
    plan_mod.reset_autotune_stats()
    again = persistence.PlanStore(out.path).restore()
    assert len(again.plans) == 1 and not again.skipped
    assert plan_mod.autotune_stats()["raced"] == 0
    assert (persistence._norm_describe(again.plans[0].describe())
            == persistence._norm_describe(plan.describe()))


def test_newer_build_extras_survive_the_winner_cache(tmp_path):
    """The v5 fixture's winner carries a field only a newer build knows
    (``fleet_epoch``) — it must ride through seeding and be served back
    by the winner cache untouched, per the extras contract."""
    report = persistence.PlanStore(_fixture(5)).restore()
    assert len(report.plans) == 1
    plan = report.plans[0]
    cached = plan_mod.get_autotune_winner(plan.spec, plan.backend)
    assert cached is not None and cached.get("fleet_epoch") == 3


SPEC_KEYS = {"fuse_levels": "auto", "fuse_gather": False,
             "fuse_scatter": False, "adaptive_block": False,
             "onehot_small_levels": True}
WINNER_KEYS = {"fuse_levels": True, "fuse_prefix": 2,
               "onehot_levels": [False, True]}


@pytest.mark.parametrize("where,key", (
    [("spec", k) for k in SPEC_KEYS] + [("winner", k) for k in WINNER_KEYS]),
    ids=lambda v: str(v))
def test_store_drops_each_retired_key(where, key, tmp_path):
    """A v6 store entry carrying one retired spec field or winner key
    restores race-free without it, and is re-saved without it."""
    assert key in plan_mod.RETIRED_KEYS
    spec = {"spatial_shapes": [[8, 8], [4, 4]], "num_heads": 2,
            "head_dim": 8, "num_points": 2, "num_queries": 32,
            "dtype": "float32", "train": True}
    winner = {"block_q": [16, 16], "slab_dtypes": ["float32", "float32"]}
    (spec if where == "spec" else winner)[key] = (
        SPEC_KEYS if where == "spec" else WINNER_KEYS)[key]
    path = tmp_path / "store.json"
    path.write_text(json.dumps({
        "version": 6, "jax": "0.4.35", "device_kind": "cpu",
        "created_unix": 0.0, "meta": {},
        "entries": [{"spec": spec, "backend": "pallas", "tune": "autotune",
                     "source": "autotune", "winner": winner}]}))
    report = persistence.PlanStore(str(path)).restore()
    assert not report.skipped and len(report.plans) == 1
    assert plan_mod.autotune_stats()["raced"] == 0
    plan = report.plans[0]
    assert plan.block_q == (16, 16)
    cached = plan_mod.get_autotune_winner(plan.spec, "pallas")
    assert key not in cached
    out = persistence.PlanStore(str(tmp_path / "resaved.json"))
    assert out.save_plans(report.plans) == 1
    with open(out.path) as f:
        entry = json.load(f)["entries"][0]
    assert key not in entry["spec"] and key not in entry["winner"]
