"""Hypothesis property tests for the MSDA op's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels import msda_fwd, ops
from repro.kernels import plan as plan_mod
from repro.kernels.ref import msda_ref

SET = dict(max_examples=15, deadline=None)


def _mk(B, Q, H, D, P, levels, seed):
    S = sum(h * w for h, w in levels)
    L = len(levels)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    value = jax.random.normal(ks[0], (B, S, H, D))
    loc = jax.random.uniform(ks[1], (B, Q, H, L, P, 2), minval=-0.2, maxval=1.2)
    attn = jax.nn.softmax(
        jax.random.normal(ks[2], (B, Q, H, L, P)).reshape(B, Q, H, -1)
    ).reshape(B, Q, H, L, P)
    return value, loc, attn


dims = st.tuples(
    st.integers(1, 2),        # B
    st.integers(1, 17),       # Q
    st.integers(1, 3),        # H
    st.sampled_from([4, 8]),  # D
    st.integers(1, 4),        # P
    st.sampled_from([((5, 7),), ((8, 6), (4, 3))]),
    st.integers(0, 10_000),   # seed
)


@given(dims)
@settings(**SET)
def test_kernel_equals_oracle(args):
    B, Q, H, D, P, levels, seed = args
    value, loc, attn = _mk(B, Q, H, D, P, levels, seed)
    out = ops.msda(value, levels, loc, attn, backend="pallas")
    ref = msda_ref(value, levels, loc, attn)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@given(dims, st.floats(-2.0, 2.0), st.floats(-2.0, 2.0))
@settings(**SET)
def test_linearity_in_value(args, alpha, beta):
    """msda(a*v1 + b*v2) == a*msda(v1) + b*msda(v2)."""
    B, Q, H, D, P, levels, seed = args
    v1, loc, attn = _mk(B, Q, H, D, P, levels, seed)
    v2, _, _ = _mk(B, Q, H, D, P, levels, seed + 1)
    lhs = ops.msda(alpha * v1 + beta * v2, levels, loc, attn, backend="pallas")
    rhs = alpha * ops.msda(v1, levels, loc, attn, backend="pallas") + beta * ops.msda(
        v2, levels, loc, attn, backend="pallas"
    )
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs), atol=5e-5)


@given(dims)
@settings(**SET)
def test_constant_field_interior(args):
    """Constant value field + interior points -> exactly that constant
    (attention weights sum to 1)."""
    B, Q, H, D, P, levels, seed = args
    _, loc, attn = _mk(B, Q, H, D, P, levels, seed)
    loc = jnp.clip(loc, 0.3, 0.7)  # safely interior
    S = sum(h * w for h, w in levels)
    value = jnp.full((B, S, H, D), 2.5)
    out = ops.msda(value, levels, loc, attn, backend="pallas")
    np.testing.assert_allclose(np.asarray(out), 2.5, atol=1e-4)


@given(dims)
@settings(**SET)
def test_attention_weight_homogeneity(args):
    """Scaling attention weights scales the output (degree-1 homogeneous)."""
    B, Q, H, D, P, levels, seed = args
    value, loc, attn = _mk(B, Q, H, D, P, levels, seed)
    o1 = ops.msda(value, levels, loc, 3.0 * attn, backend="pallas")
    o2 = 3.0 * ops.msda(value, levels, loc, attn, backend="pallas")
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=5e-5)


# --------------------------------------------------------------------------
# block planning: the slab-bytes VMEM model's invariants over random specs
# --------------------------------------------------------------------------

_MIB = 2**20

spec_dims = st.tuples(
    st.sampled_from([((5, 7),), ((8, 6), (4, 3)), ((32, 32), (16, 16), (8, 8))]),
    st.integers(1, 8),                       # P
    st.sampled_from([8, 16, 32]),            # D
    st.integers(1, 90_000),                  # Q
    st.sampled_from([2 * _MIB, 16 * _MIB, 32 * _MIB, 64 * _MIB]),  # budget
    st.booleans(),                           # train
    st.sampled_from(["float32", "bfloat16"]),  # slab dtype
)


def _round_up8(x):
    return (x + 7) // 8 * 8


@given(spec_dims)
@settings(**SET)
def test_planned_block_q_respects_vmem_model(args):
    """For random specs — TRAIN ones included — heuristic block_q stays
    sublane(8)-aligned, never exceeds the query extent, the 2048 cap or
    the SMEM cap of its table chunks, is one block for every level of
    the launch, and under the VMEM model never exceeds vmem_budget once
    the packed pyramid is resident (unless already clamped at the 8-row
    floor / the model's 1 MiB minimum working set).  The per-query
    working set of a
    train plan is the backward step: gout rows, the saved corners
    (block_q x 4P rows of the head group's lanes, slab dtype), weight
    grads and phase 1's fp32 copy of the corners."""
    levels, P, D, Q, budget, train, slab = args
    spec = plan_mod.MsdaSpec(
        spatial_shapes=levels, num_heads=2, head_dim=D, num_points=P,
        num_queries=Q, train=train, vmem_budget=budget, slab_dtype=slab)
    G = spec.heads_per_launch
    L = len(levels)
    lanes = ops.lane_width(G, D)
    bqs = plan_mod._heuristic_block_q(
        spec, plan_mod._default_slab_dtypes(spec))
    per_q = ops.per_query_bytes(P, D, train=train,
                                slab_itemsize=spec.slab_itemsize, levels=L,
                                heads=G)
    rows = msda_fwd.weight_layout(L, P, D)[0]
    forward = 2 * (lanes * 4 + rows * lanes * 4)
    if train:
        k = L * 4 * P
        saved = k * lanes * spec.slab_itemsize
        assert per_q == max(
            forward + 2 * saved,
            2 * (lanes * 4 + saved + G * k * 4) + k * lanes * 4)
    else:
        assert per_q == forward
    bq = bqs[0]
    assert bqs == (bq,) * L
    assert bq % 8 == 0 and 8 <= bq <= 2048
    assert bq <= _round_up8(Q)
    assert bq <= max(8, ops.smem_block_cap(P, levels=L, heads=G,
                                           train=train))
    # the packed pyramid is resident in fp32 over the head group's lanes
    resident = sum(ops.slab_rows(hw) for hw in levels) * lanes * 4
    # the documented model: per-step bytes fit what the budget leaves
    # after the resident pyramid, floored at a 1 MiB working set
    assert bq * per_q <= max(budget - resident, 1 * _MIB) or bq == 8


@pytest.mark.parametrize("levels,P,heads", [(1, 4, 4), (5, 4, 4), (4, 8, 4),
                                            (3, 2, 8)])
def test_smem_block_cap_counts_the_tables_in_smem(levels, P, heads):
    """The forward keeps only its int32 corner-row table in SMEM (its
    weights are VMEM rows); a training plan's backward still keeps its
    four fp32 weights there too.  Each table double-buffered, less a
    tiling chunk of slack per table."""
    chunk = 2 * ops.msda_fwd.SMEM_TILE * 4  # a tile of int32 or fp32, twice
    idx = 2 * heads * levels * P * 4
    assert ops.smem_block_cap(P, levels=levels, heads=heads) == max(
        8, (ops.SMEM_BUDGET - chunk) // idx)
    assert ops.smem_block_cap(P, levels=levels, heads=heads, train=True) == (
        max(8, (ops.SMEM_BUDGET - 2 * chunk) // (5 * idx)))


@pytest.mark.parametrize("levels,P,D,heads", [
    (1, 4, 32, 4), (5, 4, 32, 4), (4, 8, 32, 4), (5, 2, 16, 8), (1, 4, 16, 8)])
def test_per_query_bytes_counts_weight_rows(levels, P, D, heads):
    """An inference step holds, per query, its output row and its weight
    rows (msda_fwd.weight_layout), each double-buffered."""
    lanes = ops.lane_width(heads, D)
    rows = msda_fwd.weight_layout(levels, P, D)[0]
    assert ops.per_query_bytes(P, D, levels=levels, heads=heads) == (
        2 * (1 + rows) * lanes * 4)


@given(spec_dims)
@settings(**SET)
def test_bf16_slab_never_narrows_blocks(args):
    """Halving slab residency (bf16 storage) can only widen the planned
    vec-len, never shrink it — the VMEM freed goes to queries."""
    levels, P, D, Q, budget, train, _ = args
    mk = lambda sdt: plan_mod.MsdaSpec(
        spatial_shapes=levels, num_heads=2, head_dim=D, num_points=P,
        num_queries=Q, train=train, vmem_budget=budget, slab_dtype=sdt)
    wide = plan_mod._heuristic_block_q(mk("float32"), ("float32",) * len(levels))
    narrow = plan_mod._heuristic_block_q(mk("bfloat16"),
                                         ("bfloat16",) * len(levels))
    assert all(n >= w for n, w in zip(narrow, wide))


# --------------------------------------------------------------------------
# autotune winner cache: round-trips through XDG_CACHE_HOME, both schemas
# --------------------------------------------------------------------------

cache_entries = st.dictionaries(
    st.text(st.characters(min_codepoint=33, max_codepoint=126), min_size=1, max_size=40),
    st.one_of(
        st.lists(st.integers(8, 2048), min_size=1, max_size=5),  # legacy
        st.fixed_dictionaries(
            {
                # one launch shares one block across the levels
                "block_q": st.integers(8, 2048).map(lambda b: [b, b]),
                "slab_dtypes": st.lists(
                    st.sampled_from(["float32", "bfloat16"]), min_size=2, max_size=2),
            },
            # entries grew OPTIONAL fields: "sharding"/"grad_reduce"
            # (mesh-keyed race winners) and "sparsity"/"query_order"
            # (pruning/Morton races) — any
            # subset must keep parsing, pre-existing entries included.
            # Keys NO build knows ("future_field"...) must ride through
            # parse -> re-persist untouched (forward compat)
            optional={
                "sharding": st.sampled_from(["1d", "2d"]),
                "grad_reduce": st.sampled_from(["ring", "psum"]),
                "sparsity": st.sampled_from(["dense", "topk"]),
                "query_order": st.sampled_from(["identity", "morton"]),
                "future_field": st.one_of(
                    st.integers(-10, 10), st.text(max_size=8),
                    st.lists(st.integers(-10, 10), max_size=3)),
                "vendor.note": st.text(max_size=8),
            },
        ),
    ),
    max_size=4,
)


@given(cache_entries)
@settings(**SET)
def test_autotune_cache_roundtrips_through_xdg_cache_home(tmp_path_factory, entries):
    """Winner caches (legacy flat lists AND the dtype-aware dict schema
    with every optional raced-axis field) survive a store/load cycle
    rooted at a tmp XDG_CACHE_HOME."""
    import os

    tmp = tmp_path_factory.mktemp("xdg")
    old_env = {k: os.environ.pop(k, None)
               for k in ("XDG_CACHE_HOME", "REPRO_MSDA_AUTOTUNE_CACHE")}
    os.environ["XDG_CACHE_HOME"] = str(tmp)
    try:
        path = plan_mod.autotune_cache_path()
        assert path.startswith(str(tmp))  # respects XDG, not ~/.cache
        plan_mod._store_autotune_cache(entries)
        assert plan_mod._load_autotune_cache() == entries
        spec = plan_mod.MsdaSpec(spatial_shapes=((8, 6), (4, 3)), num_heads=2,
                                 head_dim=8, num_points=2, num_queries=16)
        for hit in entries.values():
            parsed = plan_mod._parse_cache_entry(hit, spec)
            if isinstance(hit, dict):  # current schema always parses
                assert parsed["block_q"] == tuple(hit["block_q"])
                assert parsed["slab_dtypes"] == tuple(hit["slab_dtypes"])
                assert parsed["sharding"] == hit.get("sharding")
                assert parsed["grad_reduce"] == hit.get("grad_reduce")
                assert parsed["sparsity"] == hit.get("sparsity")
                assert parsed["query_order"] == hit.get("query_order")
                assert parsed["extras"] == {
                    k: hit[k] for k in ("future_field", "vendor.note")
                    if k in hit}
                # and the entry shape round-trips through the writer,
                # unknown keys included
                assert plan_mod._parse_cache_entry(
                    plan_mod._winner_entry(parsed), spec) == parsed
            elif len(hit) == spec.num_levels and len(set(hit)) == 1:
                # legacy: level count must match, one block for them all
                assert parsed["block_q"] == tuple(hit)
                assert parsed["slab_dtypes"] == ("float32",) * 2
                assert parsed["sharding"] is None
            else:
                assert parsed is None
    finally:
        os.environ.pop("XDG_CACHE_HOME", None)
        for k, v in old_env.items():
            if v is not None:
                os.environ[k] = v


@given(dims)
@settings(**SET)
def test_grad_value_conservation(args):
    """sum over value of grad_value == sum over queries of (attn-weighted
    corner weights) * gout — with gout = ones and all-interior points the
    scatter conserves mass: sum(grad_value) == sum(attn)... == Q*B*H*D-ish.

    Concretely: d/dv sum(msda(v)) applied to constant direction =
    sum(attn * bilinear-partition-of-unity) per (b,h,d); interior points
    have partition-of-unity corners, so total == sum(attn) * D.
    """
    B, Q, H, D, P, levels, seed = args
    value, loc, attn = _mk(B, Q, H, D, P, levels, seed)
    loc = jnp.clip(loc, 0.3, 0.7)

    g = jax.grad(
        lambda v: jnp.sum(ops.msda(v, levels, loc, attn, backend="pallas"))
    )(value)
    np.testing.assert_allclose(
        float(jnp.sum(g)), float(jnp.sum(attn)) * D, rtol=1e-3
    )
