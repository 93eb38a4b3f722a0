"""``repro.obs.scopes.layer_of``: the known scopes of an HLO op's
``op_name`` and its direction, with JAX's wrappers stripped.  The names
are the forms jax 0.9 writes into compiled HLO metadata."""
import pytest

from repro.obs import scopes
from repro.obs.scopes import layer_of

CASES = [
    # inference: nested jit, the layer scan's while body
    ("jit(forward)/encoder/while/body/closed_call/jit(op)/msda_fwd/"
     "msda_kernel/msda_gather/pallas_call",
     ("encoder", "msda_fwd", "msda_kernel", "msda_gather"), "fwd"),
    ("jit(forward)/decoder/while/body/closed_call/self_attn/dot_general",
     ("decoder", "self_attn"), "fwd"),
    ("jit(forward)/heads/logistic", ("heads",), "fwd"),
    # training forward under jvp, the remat recompute inside the backward
    ("jit(train_step)/jvp(encoder)/while/body/closed_call/jit(op)/msda_fwd/"
     "msda_tables/closed_call/while/body/closed_call/mul",
     ("encoder", "msda_fwd", "msda_tables"), "fwd"),
    ("jit(train_step)/transpose(jvp(encoder))/while/body/closed_call/"
     "checkpoint/rematted_computation/jit(op)/msda_fwd/msda_tables/while/"
     "body/closed_call/mul",
     ("encoder", "msda_fwd", "msda_tables"), "fwd"),
    # the table math's own transpose: backward, under the forward's scope
    ("jit(train_step)/transpose(jvp(encoder))/while/body/closed_call/"
     "checkpoint/jit(op)/msda_fwd/msda_tables/while/body/sub",
     ("encoder", "msda_fwd", "msda_tables"), "bwd"),
    # the custom VJP, and JAX's autodiff of the model layers
    ("jit(train_step)/transpose(jvp(decoder))/while/body/closed_call/"
     "jit(op)/msda_bwd/msda_kernel/msda_scatter/pallas_call",
     ("decoder", "msda_bwd", "msda_kernel", "msda_scatter"), "bwd"),
    ("jit(train_step)/transpose(jvp(encoder))/while/body/closed_call/"
     "checkpoint/ffn/dot_general", ("encoder", "ffn"), "bwd"),
    ("jit(train_step)/jvp(vmap(matching))/while/body/closed_call/scatter",
     ("matching",), "fwd"),
    ("jit(train_step)/transpose(jvp(loss))/vmap()/mul", ("loss",), "bwd"),
    ("jit(train_step)/optimizer/sqrt", ("optimizer",), "fwd"),
    # BEVFormer: the temporal prologue, TSA, SCA and its re-batch
    ("jit(frame)/encoder/bev_temporal/dot_general",
     ("encoder", "bev_temporal"), "fwd"),
    ("jit(frame)/encoder/while/body/closed_call/bev_tsa/msda_proj/"
     "dot_general", ("encoder", "bev_tsa", "msda_proj"), "fwd"),
    ("jit(frame)/encoder/while/body/closed_call/bev_sca/jit(op)/msda_fwd/"
     "msda_kernel/msda_gather/pallas_call",
     ("encoder", "bev_sca", "msda_fwd", "msda_kernel", "msda_gather"), "fwd"),
    ("jit(frame)/encoder/while/body/closed_call/bev_sca/sca_rebatch/gather",
     ("encoder", "bev_sca", "sca_rebatch"), "fwd"),
    # under no known scope: argument names, JAX's own top-level ops
    ("jit(train_step)/broadcast_in_dim", (), "fwd"),
    ("state.params['enc_layers']['mlp']['wi']", (), "fwd"),
    ("", (), "fwd"),
]


@pytest.mark.parametrize("op_name,want_scopes,want_direction", CASES)
def test_layer_of(op_name, want_scopes, want_direction):
    assert layer_of(op_name) == (want_scopes, want_direction)


def test_wrappers_and_look_alikes():
    # a primitive named like a wrapper is no wrapper: no direction from it
    assert layer_of("jit(f)/encoder/transpose") == (("encoder",), "fwd")
    # a jitted function's name inside jit(...) is kept like any name
    assert layer_of("jit(heads)/add") == (("heads",), "fwd")
    assert layer_of("jit(f)/vmap(transpose(jvp(ffn)))/mul") == (("ffn",), "bwd")
    # names that only contain a scope's name are not that scope
    assert layer_of("jit(f)/encoder_stub/ffn2/mul") == ((), "fwd")


def test_vocabulary():
    assert len(set(scopes.SCOPES)) == len(scopes.SCOPES)
    assert set(scopes.MSDA_OPS + scopes.MSDA_XLA) <= set(scopes.SCOPES)
    assert set(scopes.KERNELS.values()) <= set(scopes.SCOPES)
    assert all(s.isidentifier() for s in scopes.SCOPES)
