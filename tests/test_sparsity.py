"""Sparsity-aware plans: top-k point pruning + Morton query permutation.

The tentpole contract (ISSUE 7):

* ``sparsity="off"`` / ``query_order="identity"`` plans stay bitwise
  equal to pre-sparsity plans (the axes are pure additions), and
  lossy/permuted modes are NEVER picked without a timing race;
* the Morton permutation is bitwise-neutral: forward, grad_loc and
  grad_attn are bit-identical to the identity plan (the permutation is
  a bijection — the only reassociation is in the grad_value scatter,
  which is allclose);
* the pruned executor matches the masked-renormalised oracle
  (``topk_mask_weights`` + ``msda_ref``) and reports itself truthfully
  (``xla-topk`` gather, no Pallas launch);
* both axes are planned, autotuned, persisted properties: winners
  survive the winner cache AND a ``PlanStore`` v5 save/restore with
  zero timing runs and identical ``describe()``.

Also here: the winner-cache forward-compat regression — unknown keys a
newer build persisted must ride through parse -> re-persist.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import msda_sparse
from repro.kernels import plan as pm
from repro.kernels.plan import MsdaSpec, msda_plan
from repro.kernels.ref import msda_ref

# encoder-like geometry: queries ARE the pyramid pixels (Q == S), which
# is what makes the Morton permutation statically computable
LEVELS = ((6, 6), (3, 3))
SQ = sum(h * w for h, w in LEVELS)  # 45
B, H, D, P = 2, 2, 8, 3


@pytest.fixture(autouse=True)
def _fresh_plan_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_MSDA_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    pm.clear_plans()
    pm.reset_autotune_stats()
    yield
    pm.clear_plans()


def _inputs(seed=0, levels=LEVELS, b=B, q=SQ, h=H, d=D, p=P):
    S = sum(hh * ww for hh, ww in levels)
    L = len(levels)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    value = jax.random.normal(ks[0], (b, S, h, d), jnp.float32)
    loc = jax.random.uniform(ks[1], (b, q, h, L, p, 2), minval=-0.2, maxval=1.2)
    attn = jax.nn.softmax(
        jax.random.normal(ks[2], (b, q, h, L, p)).reshape(b, q, h, -1)
    ).reshape(b, q, h, L, p)
    return value, loc, attn


def _spec(sparsity="off", query_order="identity", *, k=0, train=False,
          levels=LEVELS, q=SQ, **kw):
    return MsdaSpec(spatial_shapes=levels, num_heads=H, head_dim=D,
                    num_points=P, num_queries=q, dtype="float32", train=train,
                    sparsity=sparsity, sparsity_k=k, query_order=query_order,
                    **kw)


# --------------------------------------------------------------------------
# Morton permutation: validity + bitwise neutrality
# --------------------------------------------------------------------------


def test_morton_codes_follow_z_order():
    # 2x2 grid (raster order): Z-curve visits (y,x) = (0,0),(0,1),
    # (1,0),(1,1) in code order 0,1,2,3 (x bits even, y bits odd)
    codes = msda_sparse.morton_codes(2, 2)
    np.testing.assert_array_equal(codes, [0, 1, 2, 3])
    # 4x4: each 2x2 quad is contiguous in code space
    codes4 = msda_sparse.morton_codes(4, 4).reshape(4, 4)
    assert codes4[0, 2] == 4 and codes4[2, 0] == 8 and codes4[2, 2] == 12


def test_morton_permutation_is_per_level_bijection():
    perm = msda_sparse.morton_permutation(LEVELS)
    assert sorted(perm.tolist()) == list(range(SQ))
    # per level: rows of level 1 never migrate into level 0's block
    n0 = LEVELS[0][0] * LEVELS[0][1]
    assert set(perm[:n0].tolist()) == set(range(n0))


def test_morton_fwd_and_grads_bitwise_neutral():
    """Permuted plan == identity plan: fwd, grad_loc, grad_attn bitwise
    (per-query slots just move through a bijection); grad_value sees a
    reordered scatter -> allclose only."""
    value, loc, attn = _inputs()
    ident = msda_plan(_spec(), backend="pallas")
    mort = msda_plan(_spec(query_order="morton"), backend="pallas")
    assert mort.tuning.query_order == "morton"
    np.testing.assert_array_equal(np.asarray(mort(value, loc, attn)),
                                  np.asarray(ident(value, loc, attn)))

    def grads(plan):
        return jax.grad(lambda v, l, a: jnp.sum(plan(v, l, a) ** 2),
                        argnums=(0, 1, 2))(value, loc, attn)

    gi, gm = grads(ident), grads(mort)
    np.testing.assert_allclose(np.asarray(gm[0]), np.asarray(gi[0]),
                               atol=1e-5, rtol=1e-5)  # value: scatter order
    np.testing.assert_array_equal(np.asarray(gm[1]), np.asarray(gi[1]))
    np.testing.assert_array_equal(np.asarray(gm[2]), np.asarray(gi[2]))


def test_morton_pin_ineligible_geometry_stays_identity():
    # Q != total pixels: no static raster layout to permute — the plan
    # must report identity rather than silently half-apply the pin
    plan = msda_plan(_spec(query_order="morton", q=21), backend="pallas")
    assert plan.tuning.query_order == "identity"
    assert "morton" not in plan.describe()


# --------------------------------------------------------------------------
# top-k pruning: executor parity + truthful reporting
# --------------------------------------------------------------------------


def test_resolved_sparsity_k_defaults_and_clamps():
    assert _spec().resolved_sparsity_k() == 3      # ceil(6/2) default
    assert _spec(k=2).resolved_sparsity_k() == 2
    assert _spec(k=99).resolved_sparsity_k() == 6  # clamped to L*P
    counts = msda_sparse.gather_counts(_spec("topk", k=2))
    assert counts["dense_corner_gathers"] == 24
    assert counts["topk_corner_gathers"] == 8
    assert counts["gather_reduction"] == pytest.approx(2 / 3)


def test_topk_matches_masked_renormalised_oracle():
    value, loc, attn = _inputs(seed=1)
    k = 2
    plan = msda_plan(_spec("topk", k=k), backend="pallas")
    out = plan(value, loc, attn)
    ref = msda_ref(value, LEVELS, loc, msda_sparse.topk_mask_weights(attn, k))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_topk_plan_reports_itself_truthfully():
    plan = msda_plan(_spec("topk", k=2, train=True, vmem_budget=64 * 2**20),
                     backend="pallas")
    assert plan.tuning.sparsity == "topk"
    # the pruned executor launches no pallas kernels
    assert plan.launches_per_call() == {"fwd": 0, "bwd": 0}
    d = plan.describe()
    assert "sparsity: topk k=2/6" in d and "packed pyramid" not in d
    assert all(r["gather"] == "xla-topk" for r in plan.level_report())


def test_topk_composes_with_morton():
    value, loc, attn = _inputs(seed=2)
    k = 2
    plan = msda_plan(_spec("topk", "morton", k=k), backend="pallas")
    assert plan.tuning.query_order == "morton"
    ref = msda_ref(value, LEVELS, loc, msda_sparse.topk_mask_weights(attn, k))
    np.testing.assert_allclose(np.asarray(plan(value, loc, attn)),
                               np.asarray(ref), atol=2e-5, rtol=2e-5)


# --------------------------------------------------------------------------
# planned, autotuned, persisted: winner cache + PlanStore v5
# --------------------------------------------------------------------------


def test_auto_heuristic_resolves_dense_identity_without_race():
    plan = msda_plan(_spec("auto", "auto"), backend="pallas")
    assert plan.tuning.sparsity == "dense"
    assert plan.tuning.query_order == "identity"
    assert pm.autotune_stats()["raced"] == 0


def test_sparsity_race_persists_and_reloads(tmp_path):
    spec = _spec("auto", "auto", train=True)
    plan = msda_plan(spec, backend="cpu", tune="autotune")
    assert plan.tuning.source == "autotune"
    assert pm.autotune_stats()["raced"] == 1
    assert plan.tuning.sparsity in ("dense", "topk")
    assert plan.tuning.query_order in ("identity", "morton")
    entry = next(iter(json.load(open(tmp_path / "autotune.json")).values()))
    assert entry["sparsity"] == plan.tuning.sparsity
    assert entry["query_order"] == plan.tuning.query_order

    pm.clear_plans()
    pm.reset_autotune_stats()
    plan2 = msda_plan(spec, backend="cpu", tune="autotune")
    stats = pm.autotune_stats()
    assert stats["raced"] == 0 and stats["cache_hits"] >= 1
    assert plan2.tuning.source == "autotune-cache"
    assert plan2.tuning.sparsity == plan.tuning.sparsity
    assert plan2.tuning.query_order == plan.tuning.query_order


def test_pinned_axes_keep_entries_byte_identical(tmp_path):
    """off/pinned specs must not grow winner-cache fields: an autotuned
    off-spec entry carries NO sparsity/query_order keys, so pre-PR
    entries and new ones stay byte-compatible."""
    msda_plan(_spec(), backend="pallas", tune="autotune")
    entry = next(iter(json.load(open(tmp_path / "autotune.json")).values()))
    assert "sparsity" not in entry and "query_order" not in entry


def test_winner_cache_preserves_unknown_keys():
    """Forward-compat regression: a field persisted by a newer build
    must survive this build's parse -> re-persist round trip."""
    spec = _spec()
    entry = {"block_q": [16, 16], "slab_dtypes": ["float32", "float32"],
             "sparsity": "dense", "future_field": {"nested": [1, 2]},
             "another_unknown": "keep-me"}
    parsed = pm._parse_cache_entry(entry, spec)
    assert parsed is not None
    assert parsed["extras"] == {"future_field": {"nested": [1, 2]},
                                "another_unknown": "keep-me"}
    out = pm._winner_entry(parsed)
    assert out["future_field"] == {"nested": [1, 2]}
    assert out["another_unknown"] == "keep-me"
    # and seeding through the public API keeps them on disk
    assert pm.seed_autotune_winner(spec, "cpu", entry)
    disk = json.load(open(pm.autotune_cache_path()))
    assert next(iter(disk.values()))["future_field"] == {"nested": [1, 2]}


def test_sparsity_winners_survive_plan_store_roundtrip(tmp_path, monkeypatch):
    """Acceptance: auto-axis winners survive a PlanStore save/restore
    with zero timing runs and identical describe()."""
    from repro.serving.persistence import (PLAN_STORE_VERSION, PlanStore,
                                           _norm_describe)

    spec = _spec("auto", "auto", train=True)
    plan = msda_plan(spec, backend="cpu", tune="autotune")
    store = PlanStore(str(tmp_path / "plans.json"))
    assert store.save_plans([plan]) == 1
    raw = json.load(open(tmp_path / "plans.json"))
    assert raw["version"] == PLAN_STORE_VERSION

    pm.clear_plans()
    pm.reset_autotune_stats()
    monkeypatch.setenv("REPRO_MSDA_AUTOTUNE_CACHE", str(tmp_path / "at2.json"))
    report = store.restore()
    assert not report.skipped and not report.describe_mismatches
    assert pm.autotune_stats()["raced"] == 0
    [restored] = report.plans
    assert restored.tuning.source == "autotune-cache"
    assert restored.tuning.sparsity == plan.tuning.sparsity
    assert restored.tuning.query_order == plan.tuning.query_order
    assert _norm_describe(restored.describe()) == _norm_describe(plan.describe())
