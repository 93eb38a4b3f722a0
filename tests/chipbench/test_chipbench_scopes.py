"""Device time by the program's scopes (``chipbench/device_scopes.py``):
the op names of the HLO the profile recorded, joined to the trace's
device events, the readers that sum them, the clock offset between host
and device, and the readers that were there before, pinned to what they
read on the committed trace (``data/msda_small.xplane.pb``)."""
import dataclasses
import json
import os
import re

import pytest

import chipbench_tiny
from chipbench import catalog, device_scopes, peaks, trace
from chipbench.device_scopes import UNATTRIBUTED
from chipbench.run import BenchError, TraceRun

REPO = chipbench_tiny.REPO
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DETR = catalog.family(chipbench_tiny.TINY_CONFIG, REPO)


def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        if not n:
            return out + bytes([b])
        out += bytes([b | 0x80])


def _msg(*fields):
    """A serialized protobuf message of ``(number, value)`` fields: ints
    as varints, str and bytes length-delimited, a list of ints packed."""
    out = b""
    for num, value in fields:
        if isinstance(value, int):
            out += _varint(num << 3) + value.to_bytes(1, "little") \
                if value < 0x80 else _varint(num << 3) + _varint(value)
            continue
        if isinstance(value, list):
            value = b"".join(_varint(v) for v in value)
        elif isinstance(value, str):
            value = value.encode()
        out += _varint(num << 3 | 2) + _varint(len(value)) + value
    return out


def _inst(iid, name, opcode, op_name=None, operands=None, called=None,
          packed=True):
    fields = [(1, name), (2, opcode), (35, iid)]
    if op_name is not None:
        fields.append((7, _msg((1, opcode), (2, op_name))))
    if operands:
        fields += [(36, operands)] if packed else [(36, o) for o in operands]
    if called:
        fields.append((38, called))
    return (2, _msg(*fields))


GATHER_OP = "jit(step)/encoder/msda_fwd/msda_kernel/msda_gather/pallas_call"
# an HloModuleProto: a fused computation, and the entry that calls it
MODULE = _msg(
    (1, "jit_step"),
    (3, _msg((1, "fused_computation.3"), (5, 1), (6, 11),
             _inst(10, "param_0", "parameter"),
             _inst(12, "mul.1", "multiply", "jit(step)/encoder/ffn/mul"),
             _inst(11, "add.2", "add", "jit(step)/encoder/ffn/add"))),
    (3, _msg((1, "main.9"), (5, 2), (6, 25),
             _inst(20, "x.1", "parameter", "x"),
             _inst(21, "msda_gather.4", "custom-call", GATHER_OP),
             _inst(22, "copy.5", "copy", operands=[21]),
             _inst(23, "fusion.6", "fusion", operands=[22], called=[1]),
             _inst(24, "copy.8", "copy", operands=[22], packed=False),
             _inst(26, "constant.9", "constant"),
             _inst(27, "copy.10", "copy", operands=[26]),
             _inst(25, "tanh.7", "tanh", "jit(step)/heads/tanh"))),
)


def test_hlo_op_names_by_hand():
    names, insts = device_scopes.hlo_op_names(MODULE)
    assert names == {
        "%mul.1": "jit(step)/encoder/ffn/mul",
        "%add.2": "jit(step)/encoder/ffn/add",
        "%x.1": "x",
        "%msda_gather.4": GATHER_OP,
        # a fusion without metadata of its own: its computation's root's
        "%fusion.6": "jit(step)/encoder/ffn/add",
        # XLA's copies: what they copy (a copy of a copy too)
        "%copy.5": GATHER_OP,
        "%copy.8": GATHER_OP,
        "%tanh.7": "jit(step)/heads/tanh",
    }
    # a copy of what names nothing names nothing, and is still listed
    assert "%copy.10" not in names and "%copy.10" in insts
    assert len(insts) == 11


def test_profile_op_names_by_hand(tmp_path):
    """The profiler records each traced program's ``Hlo Proto`` as a stat
    of an event's metadata on the ``/host:metadata`` plane."""
    stat = _msg((1, 7), (6, _msg((1, MODULE))))
    metadata = _msg(
        (2, "/host:metadata"),
        (4, _msg((1, 3), (2, _msg((1, 3), (2, "jit_step(42)"), (5, stat))))),
        (5, _msg((1, 7), (2, _msg((1, 7), (2, "Hlo Proto"))))),
        (5, _msg((1, 8), (2, _msg((1, 8), (2, "Program Id"))))))
    other = _msg((2, "/device:TPU:0"),
                 (4, _msg((1, 3), (2, _msg((1, 3), (5, stat))))))
    path = tmp_path / "x.xplane.pb"
    path.write_bytes(_msg((1, other), (1, metadata)))
    assert device_scopes.profile_op_names(str(path)) == (
        device_scopes.hlo_op_names(MODULE))


def _kernel(name, operands):
    args = ", ".join(f"f32[8]{{0}} %a.{i}" for i in range(operands))
    return (f"%{name} = f32[8]{{0}} custom-call({args}), "
            f'custom_call_target="tpu_custom_call", '
            f'frontend_attributes={{kernel_metadata={{"msda":"gather"}}}}')


def test_kernel_names_by_hand():
    gather, old = _kernel("msda_gather.16", 3), _kernel("op.16", 3)
    scatter = _kernel("msda_scatter.55.clone", 4)
    assert device_scopes.kernel_name(gather) == "msda_gather"
    assert device_scopes.kernel_name(scatter) == "msda_scatter"
    assert device_scopes.kernel_name(old) == "op"
    assert device_scopes.kernel_name("%msda_gather.1 = f32[2] fusion(%a)") == ""
    evs = [(gather, 0, 5), (scatter, 5, 20), (old, 20, 24), (gather, 30, 31)]
    assert device_scopes.trace_kernel_seconds(evs, "msda_gather") == (
        pytest.approx(6e-9), 2)
    assert device_scopes.trace_kernel_seconds(evs, "msda_scatter") == (
        pytest.approx(15e-9), 1)


# --------------------------------------------------------------------------
# the readers on a synthetic traced training step, by hand
# --------------------------------------------------------------------------

SPEC = dict(spatial_shapes=((32, 32), (16, 16)), num_heads=8, head_dim=32,
            num_points=4, num_queries=256, dtype="float32", train=True)
P = "jit(train_step)/"
FWD = P + "jvp(encoder)/while/body/closed_call/jit(op)/msda_fwd/"
REMAT = (P + "transpose(jvp(encoder))/while/body/closed_call/checkpoint/"
         "rematted_computation/jit(op)/msda_fwd/")
BWD = P + "transpose(jvp(encoder))/while/body/closed_call/checkpoint/jit(op)/"
# (event, op_name, start_ns, end_ns) of one training step
STEP = [
    (_kernel("msda_gather.1", 3), FWD + "msda_kernel/msda_gather/pallas_call",
     0, 100),
    ("%fusion.2 = f32[8] fusion(%a)", FWD + "msda_tables/mul", 100, 130),
    ("%fusion.3 = f32[8] fusion(%a)", FWD + "msda_slab/pad", 130, 140),
    (_kernel("msda_gather.4", 3), REMAT + "msda_kernel/msda_gather/pallas_call",
     140, 240),
    ("%fusion.5 = f32[8] fusion(%a)", FWD + "msda_reduce/add", 240, 250),
    ("%fusion.6 = f32[8] fusion(%a)",
     P + "jvp(encoder)/while/body/closed_call/ffn/dot_general", 250, 300),
    ("%copy.7 = f32[8] copy(%a)", None, 300, 310),
    (_kernel("msda_scatter.8", 4),
     BWD + "msda_bwd/msda_kernel/msda_scatter/pallas_call", 310, 510),
    ("%fusion.9 = f32[8] fusion(%a)", BWD + "msda_fwd/msda_tables/sub",
     510, 530),
    ("%fusion.10 = f32[8] fusion(%a)", BWD + "msda_bwd/msda_grad_unpack/slice",
     530, 560),
    ("%fusion.11 = f32[8] fusion(%a)",
     P + "transpose(jvp(encoder))/while/body/closed_call/checkpoint/ffn/"
     "dot_general", 560, 600),
    ("%fusion.12 = f32[8] fusion(%a)", P + "optimizer/sqrt", 600, 620),
]


class _Plan:
    def __init__(self, fwd, bwd, spec=None):
        self.launches = {"fwd": fwd, "bwd": bwd}
        self.spec = spec

    def launches_per_call(self):
        return dict(self.launches)


def _synthetic(events=STEP, op_names=None, mode="train"):
    """A traced training step whose harness hands over the op names
    (``run.op_names``), as a reader finds them in the profile."""
    from repro.kernels.plan import MsdaSpec

    spec = MsdaSpec(**SPEC)
    tr = trace.Trace(device_ops={"/device:TPU:0": [(n, s, e)
                                                   for n, _, s, e in events]},
                     host_spans=[("chipbench.window", 0, 1000)])
    if op_names is None:
        op_names = {trace.op_kind(n)[0]: o for n, o, _, _ in events if o}
    cfg = {"encoder_layers": 1, "decoder_layers": 1}
    plans = {"encoder": _Plan(1, 1, spec), "decoder": _Plan(0, 0, spec)}
    run = TraceRun(tr, cfg, {"mode": mode, "batch": 1},
                   DETR.msda_calls(cfg, mode, plans),
                   peaks.peaks("TPU v5 lite"), 2, 1, 1.0, 0, 1000)
    run.op_names = op_names
    return run


def _read(name, run):
    return catalog.metric_reader(name, REPO)(run)


def test_scope_seconds_by_hand():
    run, ds = _synthetic(), device_scopes
    assert ds.scope_seconds(run, "msda_fwd", "fwd") == pytest.approx(250e-9)
    # the table math's transpose is backward, under the forward's scope
    assert ds.scope_seconds(run, "msda_fwd") == pytest.approx(270e-9)
    assert ds.scope_seconds(run, "msda_fwd", "bwd") == pytest.approx(20e-9)
    assert ds.scope_seconds(run, "msda_bwd") == pytest.approx(230e-9)
    assert ds.scope_seconds(run, "msda_kernel", "bwd") == pytest.approx(
        200e-9)
    assert ds.scope_split(run) == {"encoder": pytest.approx(590e-9),
                                   "optimizer": pytest.approx(20e-9),
                                   UNATTRIBUTED: pytest.approx(10e-9)}
    assert ds.named_kernel_seconds(run, "fwd") == pytest.approx(200e-9)
    assert ds.named_kernel_seconds(run, "bwd") == pytest.approx(200e-9)


def test_new_readers_by_hand():
    run = _synthetic()
    # 2 images: (30 + 10 + 10 + 20 + 30) ns and (50 + 40 + 20) ns
    assert _read("msda_tables_ms_per_image", run) == pytest.approx(5e-5)
    assert _read("outside_msda_ms_per_image", run) == pytest.approx(5.5e-5)
    # the same least time over the whole forward op's 250 ns, not the
    # kernels' 200 ns
    kernel = _read("msda_fwd_roofline", run)
    assert _read("msda_fwd_op_roofline", run) == pytest.approx(
        kernel * 200 / 250)


NEW = ["msda_fwd_op_roofline", "msda_tables_ms_per_image",
       "outside_msda_ms_per_image"]


@pytest.mark.parametrize("name", NEW)
def test_new_readers_fail_without_op_names(name):
    with pytest.raises(BenchError, match="no op names"):
        _read(name, _synthetic(op_names={}))
    # none handed over, and no traced run's profile to read them from
    # (the run carries no profile directory)
    run = _synthetic()
    run.op_names = None
    with pytest.raises(BenchError, match="found no profile"):
        _read(name, run)


@pytest.mark.parametrize("name", NEW)
def test_new_readers_fail_on_another_named_kernel_count(name):
    # the recomputed gather left unnamed: the operand count would still
    # find it, the name does not, and no reader counts it
    step = [(_kernel("op.4", 3), *rest[1:]) if i == 3 else rest
            for i, rest in enumerate(STEP)]
    run = _synthetic(step)
    for reader in (name, "msda_fwd_roofline"):
        with pytest.raises(BenchError, match="named 'msda_gather'"):
            _read(reader, run)


@pytest.mark.parametrize("name", NEW)
def test_new_readers_report_nothing_without_names_or_chip(name, monkeypatch):
    with monkeypatch.context() as m:  # a program that predates the names
        m.setattr(device_scopes, "vocabulary", lambda: None)
        assert _read(name, _synthetic()) is None
    cpu = _synthetic()
    cpu.trace.device_ops.clear()  # a run without a traced chip
    assert _read(name, cpu) is None


# --------------------------------------------------------------------------
# the committed trace: the readers that were there, and the clocks
# --------------------------------------------------------------------------

# what the readers and ``breakdown`` read on ``msda_small.xplane.pb``
# before the scopes came: one encoder launch each way per call, the
# paper's configuration at one layer, the v5e's peaks
PINNED = {"msda_fwd_roofline": 3.5255252164317215,
          "step_mfu": 84.39101187864459,
          "device_idle_share": 41.02422451350115}
PINNED_OPS = [
    ["%transpose_jvp_jit_op___.1 pallas kernel, 4 operands", 0.002151258],
    ["%jvp_jit_op__.1 pallas kernel, 3 operands", 0.000612822],
    ["%dynamic_update_slice.183 dynamic-update-slice", 3.9911e-05],
    ["%copy.35 copy", 3.0188e-05],
    ["%maximum_bitcast_fusion fusion", 2.1332e-05],
    ["%copy.37 copy", 1.635e-05],
    ["%copy-done copy-done", 9.105e-06],
    ["%add_multiply_fusion.3 fusion", 8.345e-06],
    ["%dynamic_slice.224 dynamic-slice", 6.267e-06],
    ["%copy.31 copy", 5.382e-06],
]
PINNED_GAPS = [
    ["chipbench.wait", 0.001910255], ["chipbench.dispatch", 0.000595036],
    ["chipbench.dispatch", 0.000491843], ["chipbench.dispatch", 0.000390151],
    ["chipbench.wait", 2.3809e-05], ["chipbench.wait", 1.342e-06],
    ["chipbench.dispatch", 1.279e-06], ["chipbench.dispatch", 1.258e-06],
    ["chipbench.wait", 1.253e-06], ["chipbench.dispatch", 1.044e-06],
]


@pytest.fixture(scope="module")
def chip_trace():
    return trace.load(os.path.join(DATA, "msda_small.xplane.pb"))


KERNEL_BY_OPERANDS = {3: "msda_gather", 4: "msda_scatter"}


def _by_operands(events, operands):
    """Seconds and count of the Pallas kernel events with ``operands``
    operands: how the MSDA kernels were found before they had names."""
    sel = [(s, e) for n, s, e in events if trace.pallas_operands(n) == operands]
    return sum(e - s for s, e in sel) * 1e-9, len(sel)


def _named_as_now(tr):
    """A copy of a trace recorded before the program named its kernels,
    each MSDA kernel event renamed as the program names it now (the
    gather takes three operands, the scatter four)."""
    def rename(i, n):
        kernel = KERNEL_BY_OPERANDS.get(trace.pallas_operands(n))
        return re.sub(r"^%[\w.-]+", f"%{kernel}.{i}", n) if kernel else n
    return trace.Trace(
        device_ops={p: [(rename(i, n), s, e) for i, (n, s, e) in enumerate(evs)]
                    for p, evs in tr.device_ops.items()},
        host_spans=list(tr.host_spans))


def _pinned_run(chip_trace):
    from repro.kernels.plan import MsdaSpec

    spec = MsdaSpec(**SPEC)
    calls = sum(1 for n, _, _ in chip_trace.host_spans
                if n == "chipbench.dispatch")
    lo, hi = chip_trace.window()
    with open(os.path.join(REPO, "chipbench", "configs",
                           "deformable-detr.json")) as f:
        cfg = dict(json.load(f), encoder_layers=1, decoder_layers=1)
    plans = {"encoder": _Plan(1, 1, spec), "decoder": _Plan(0, 0, spec)}
    return TraceRun(_named_as_now(chip_trace), cfg,
                    {"mode": "infer", "batch": 1},
                    DETR.msda_calls(cfg, "infer", plans),
                    peaks.peaks("TPU v5 lite"), calls, calls,
                    (hi - lo) * 1e-9, lo, hi,
                    flops_per_image=DETR.flops_per_image(cfg, "infer"))


@pytest.mark.parametrize("name", sorted(PINNED))
def test_readers_read_what_they_read_before(chip_trace, name):
    run = _pinned_run(chip_trace)
    assert _read(name, run) == pytest.approx(PINNED[name], rel=1e-12)


def test_breakdown_reads_what_it_read_before(chip_trace):
    bd = trace.breakdown(chip_trace, *chip_trace.window())
    assert [n for n, _ in bd["device_ops"]] == [n for n, _ in PINNED_OPS]
    assert [s for _, s in bd["device_ops"]] == pytest.approx(
        [s for _, s in PINNED_OPS], rel=1e-9)
    assert [n for n, _ in bd["idle_gaps"]] == [n for n, _ in PINNED_GAPS]
    assert [s for _, s in bd["idle_gaps"]] == pytest.approx(
        [s for _, s in PINNED_GAPS], rel=1e-9)


@pytest.mark.parametrize("name", ["msda_small", "msda_small_named"])
def test_clock_offset_bounds(name):
    lo, hi = device_scopes.clock_offset_bounds(
        os.path.join(DATA, f"{name}.xplane.pb"))
    # the device's clock leads the host's by about 1.4-1.9 ms, far more
    # than the 1-24 us idle gaps that breakdown labels by host span
    assert 1.3e-3 < lo < hi < 2.0e-3
    assert max(s for _, s in PINNED_GAPS[4:]) < lo


def test_clock_offset_needs_paired_runs(tmp_path):
    path = tmp_path / "empty.xplane.pb"
    path.write_bytes(_msg((1, _msg((2, "/host:CPU")))))
    with pytest.raises(ValueError, match="pairs no device program run"):
        device_scopes.clock_offset_bounds(str(path))


# --------------------------------------------------------------------------
# the compiled program at the test size: every op is in a scope
# --------------------------------------------------------------------------

# opcodes XLA adds with no op_name of the program's: arguments and the
# copies XLA makes of buffers (copy insertion; an argument's copy carries
# the argument's name), constants, tuples and views
XLA_ADDED = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast",
             "copy", "copy-start", "copy-done")
# XLA:CPU rewrites some ops once fusion has set the metadata, and leaves
# none: single ops wrapped in ``%wrapped_*`` fusions, the first half of a
# reduction split in two, a reduce of a product turned into a dot
XLA_CPU_UNNAMED = re.compile(r"fusion\(.*calls=%wrapped_|dot\(")
# a constant broadcast that JAX lifts out of the loop (or vmap) that
# made it keeps no name stack
HOISTED = re.compile(r"(jit\([^/]*\)/)+broadcast_in_dim")

_COMPUTATION = re.compile(r"^(ENTRY[ \t]+)?(%[\w.-]+) .*\{[ \t]*$")
_INSTRUCTION = re.compile(r"^[ \t]+(?:ROOT[ \t]+)?(%[\w.-]+) = ")


def top_level(hlo_text):
    """``(instruction, opcode, line)`` of every instruction of the entry
    computation and of the control flow it runs: the ops a device
    trace shows, fusions' insides left out."""
    comps, entry, cur = {}, None, None
    for line in hlo_text.split("\n"):
        m = _COMPUTATION.match(line)
        if m:
            cur = m.group(2)
            comps[cur] = []
            entry = cur if m.group(1) else entry
            continue
        m = _INSTRUCTION.match(line)
        if m and cur:
            comps[cur].append((m.group(1), trace.op_kind(
                line.strip().removeprefix("ROOT "))[1], line))
    seen, todo = set(), [entry]
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for _, kind, line in comps[comp]:
            if kind in ("while", "conditional", "call"):
                todo += re.findall(
                    r"(?:body|condition|to_apply|true_computation|"
                    r"false_computation)=(%[\w.-]+)", line)
                for branches in re.findall(r"branch_computations=\{([^}]*)\}",
                                           line):
                    todo += re.findall(r"%[\w.-]+", branches)
    return [x for comp in sorted(seen) for x in comps[comp]]


@pytest.fixture(scope="module")
def compiled_programs():
    """The compiled test-size inference forward and training step, with
    the Pallas plans run by the interpreter: ``(text, op names)``."""
    import jax
    import jax.numpy as jnp

    from chipbench import generate, weights
    from repro.core import deformable_transformer as dt
    from repro.optim import adamw
    from repro.train import loop as train_loop
    from repro.train.state import TrainState

    cfg = chipbench_tiny.TINY_CONFIG
    mcfg = DETR.program_config(cfg)
    mcfg = dataclasses.replace(
        mcfg, msda=dataclasses.replace(mcfg.msda, backend="pallas"))

    def forward(p, x):
        memory = dt.encode_pyramid(p, mcfg, x, train=False, remat=False)
        return dt.decode_queries(p, mcfg, memory, train=False)

    pyr = generate.make(11, cfg, chipbench_tiny.INFER)[0]["pyramid"]
    served = weights.make(11, cfg, served=True)
    tr = chipbench_tiny.TRAIN
    params = weights.make(11, cfg)
    state = TrainState(params=params, opt=adamw.init_adamw(params),
                       step=jnp.zeros((), jnp.int32))
    step = train_loop.make_train_step(
        mcfg, num_microbatches=1, peak_lr=tr["peak_lr"],
        warmup_steps=tr["warmup_steps"], total_steps=tr["total_steps"],
        weight_decay=tr["weight_decay"], clip_norm=tr["clip_norm"])
    batch = generate.make(11, cfg, tr)[0]
    out = {}
    for mode, compiled in (
            ("infer", jax.jit(forward).lower(served, pyr).compile()),
            ("train", jax.jit(step).lower(state, batch).compile())):
        module = compiled.runtime_executable().hlo_modules()[0]
        names, _ = device_scopes.hlo_op_names(
            module.as_serialized_hlo_module_proto())
        out[mode] = (compiled.as_text(), names)
    return out


@pytest.mark.parametrize("mode", ["infer", "train"])
def test_every_compiled_op_is_in_a_scope(compiled_programs, mode):
    from repro.obs.scopes import layer_of

    text, names = compiled_programs[mode]
    insts = top_level(text)
    assert len(insts) > 300
    loose = []
    for inst, kind, line in insts:
        op_name = names.get(inst)
        if kind in XLA_ADDED:
            continue
        if op_name is None:
            if not XLA_CPU_UNNAMED.search(line):
                loose.append(line[:160])
        elif not layer_of(op_name)[0] and not HOISTED.fullmatch(op_name):
            loose.append(f"{op_name}: {line[:120]}")
    assert not loose, "\n".join(loose)


def test_compiled_train_step_has_both_directions(compiled_programs):
    from repro.obs.scopes import layer_of

    _, names = compiled_programs["train"]
    seen = {(s, d) for n in names.values() for sc, d in [layer_of(n)]
            for s in sc}
    for s in ("encoder", "decoder", "heads", "matching", "loss", "optimizer",
              "self_attn", "ffn", "msda_proj", "msda_tables", "msda_slab",
              "msda_kernel", "msda_reduce"):
        assert (s, "fwd") in seen, s
    for s in ("msda_bwd", "msda_grad_unpack", "msda_tables", "ffn", "heads"):
        assert (s, "bwd") in seen, s


# --------------------------------------------------------------------------
# a chip trace of the named program (``record_trace.py``), whose profile
# also holds the HLO of the traced step
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def named():
    path = os.path.join(DATA, "msda_small_named.xplane.pb")
    return trace.load(path), device_scopes.profile_op_names(path)[0]


def test_named_kernels_are_the_operand_counted_ones(named):
    tr, _ = named
    calls = sum(1 for n, _, _ in tr.host_spans if n == "chipbench.dispatch")
    for events in tr.device_ops.values():
        for operands, name in KERNEL_BY_OPERANDS.items():
            by_name = [(s, e) for n, s, e in events
                       if device_scopes.kernel_name(n) == name]
            by_operands = [(s, e) for n, s, e in events
                           if trace.pallas_operands(n) == operands]
            assert by_name == by_operands and len(by_name) == calls


def test_named_scopes_partition_busy_time(named):
    from repro.obs.scopes import layer_of

    tr, names = named
    for plane, events in tr.device_ops.items():
        split = {}
        for n, s, e in events:
            scopes, _ = layer_of(names.get(trace.op_kind(n)[0], ""))
            key = scopes[0] if scopes else UNATTRIBUTED
            split[key] = split.get(key, 0.0) + (e - s) * 1e-9
        busy = sum(e - s for s, e in trace.union(events)) * 1e-9
        assert sum(split.values()) == pytest.approx(busy, rel=1e-9), plane
        assert set(split) <= {"msda_fwd", "msda_bwd", UNATTRIBUTED}
        assert split.get(UNATTRIBUTED, 0.0) < 0.05 * busy, split


def test_named_trace_through_the_traced_run(named):
    tr, names = named
    calls = sum(1 for n, _, _ in tr.host_spans if n == "chipbench.dispatch")
    lo, hi = tr.window()
    cfg = {"encoder_layers": 1, "decoder_layers": 1}
    plans = {"encoder": _Plan(1, 1), "decoder": _Plan(0, 0)}
    run = TraceRun(tr, cfg, {"mode": "infer", "batch": 1},
                   DETR.msda_calls(cfg, "infer", plans), None,
                   calls, calls, (hi - lo) * 1e-9, lo, hi)
    run.op_names = names
    ds = device_scopes
    by_operands = {d: _by_operands(run.device_events(), n)[0]
                   for d, n in (("fwd", 3), ("bwd", 4))}
    for direction in ("fwd", "bwd"):
        assert ds.named_kernel_seconds(run, direction) == pytest.approx(
            by_operands[direction], rel=1e-12)
        assert ds.scope_seconds(run, "msda_kernel", direction) == (
            pytest.approx(by_operands[direction], rel=1e-12))
    kernels = by_operands["fwd"] + by_operands["bwd"]
    ops = ds.scope_seconds(run, "msda_fwd") + ds.scope_seconds(run, "msda_bwd")
    assert kernels < ops < run.busy_s


def test_a_program_without_the_vocabulary_reports_nothing(tmp_path):
    """The benchmark runs over programs that predate ``repro.obs.scopes``
    (a parent commit): its traced run must read them without failing."""
    import subprocess
    import sys

    obs = tmp_path / "repro" / "obs"
    obs.mkdir(parents=True)
    (tmp_path / "repro" / "__init__.py").write_text("")
    (obs / "__init__.py").write_text("")
    code = ("import sys; sys.path[:0] = [sys.argv[1], sys.argv[2]]; "
            "from chipbench.device_scopes import vocabulary; "
            "assert vocabulary() is None; print('none')")
    proc = subprocess.run([sys.executable, "-c", code, str(tmp_path), REPO],
                          capture_output=True, text=True, timeout=120,
                          env={k: v for k, v in os.environ.items()
                               if k != "PYTHONPATH"})
    assert proc.returncode == 0 and proc.stdout.strip() == "none", proc.stderr
    assert device_scopes.vocabulary().layer_of("jit(f)/encoder/add") == (
        ("encoder",), "fwd")


def _profile_dir(tmp_path, source):
    """A traced run's directory as the profiler lays it out."""
    d = tmp_path / "plugins" / "profile" / "2026_10_18_00_00_00"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(open(source, "rb").read())
    return tmp_path


def test_a_reader_finds_the_profile_of_its_run(tmp_path):
    named = os.path.join(DATA, "msda_small_named.xplane.pb")
    run = _synthetic()
    assert device_scopes.profile_path(run) is None  # it carries no directory
    run.trace_dir = str(tmp_path / "empty")
    assert device_scopes.profile_path(run) is None  # nor a profile in it
    d = _profile_dir(tmp_path, named)
    run.trace_dir = str(d)
    assert device_scopes.profile_path(run) == str(
        d / "plugins" / "profile" / "2026_10_18_00_00_00" / "host.xplane.pb")
    # a run of another program than the profile's: its instructions are
    # not in the profile's HLO, and the run fails
    run.op_names = None
    with pytest.raises(BenchError, match="holds none of"):
        device_scopes.attributed(run)
    # the named trace's own run reads its names from the profile
    tr = trace.load(named)
    calls = sum(1 for n, _, _ in tr.host_spans if n == "chipbench.dispatch")
    lo, hi = tr.window()
    cfg = {"encoder_layers": 1, "decoder_layers": 1}
    own = TraceRun(tr, cfg, {"mode": "infer", "batch": 1},
                   DETR.msda_calls(cfg, "infer", {"encoder": _Plan(1, 1),
                                                  "decoder": _Plan(0, 0)}),
                   None, calls, calls, (hi - lo) * 1e-9, lo, hi,
                   trace_dir=str(d))
    split = device_scopes.scope_split(own)
    assert own.op_names == device_scopes.profile_op_names(named)[0]
    assert sum(split.values()) == pytest.approx(own.busy_s, rel=1e-9)
