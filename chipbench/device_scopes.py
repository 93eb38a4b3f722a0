"""Device time by the program's own names for its work.

The program names its device work (``repro.obs.scopes``): Pallas kernels
by name (the custom call, and so its trace event, is ``%msda_gather.N``
or ``%msda_scatter.N``), everything else by ``jax.named_scope``.  The
scopes reach the compiled program only as op metadata
(``metadata={op_name="jit(forward)/encoder/..."}``); the device op events
of a trace carry none, only the HLO instruction's name, which is unique
in its module.  The profiler also records, in the same ``.xplane.pb``,
the HLO of every program it traced (an ``Hlo Proto`` stat on the
``/host:metadata`` plane).  So ``profile_op_names`` reads each
instruction's ``op_name`` from there, and the events join it on their
instruction name: the names come from the program that ran, not from
another compile of it.

A traced run hands a metric's reader the parsed trace and the directory
of its profile (``run.trace_dir``: ``--trace-dir``, or else
``<checkout>/.chipbench/trace/<workload>``), where ``profile_path`` finds
the file.

``clock_offset_bounds`` bounds the offset between the device's clock and
the host's from the host's enqueue and completion of each program run.
"""
from __future__ import annotations

import importlib.util
from typing import Dict, Iterator, List, Optional, Set, Tuple

from chipbench import trace
from chipbench.harness import BenchError

UNATTRIBUTED = "unattributed"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
MODULES_LINE = "XLA Modules"
# the host's runtime events around a program run: its enqueue, and the
# callbacks once the device has finished it
ENQUEUE, CALLBACKS = "DoEnqueueProgram", "CompleteCallbacks"


def vocabulary():
    """The program's names for its device work (``repro.obs.scopes``),
    or None for a program that has none: the metrics that read them are
    then not reported."""
    if importlib.util.find_spec("repro.obs.scopes") is None:
        return None
    return importlib.import_module("repro.obs.scopes")


# --------------------------------------------------------------------------
# the profile's HLO: protobuf wire format, read by hand
# --------------------------------------------------------------------------

# field numbers (tsl/profiler/protobuf/xplane.proto, xla/service/hlo.proto)
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_METADATA, _PLANE_STAT_METADATA = 2, 4, 5
_EVENT_METADATA_STATS = 5
_STAT_METADATA_ID, _STAT_BYTES = 1, 6
_META_ID, _META_NAME = 1, 2
_MAP_VALUE = 2
_HLO_MODULE = 1
_MODULE_COMPUTATIONS = 3
_COMP_INSTRUCTIONS, _COMP_ID, _COMP_ROOT_ID = 2, 5, 6
_INST_NAME, _INST_OPCODE, _INST_METADATA = 1, 2, 7
_INST_ID, _INST_OPERANDS, _INST_CALLED = 35, 36, 38
_OP_NAME = 2


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of a serialized message: an int for a
    varint, bytes for a length-delimited field, raw bytes for fixed."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, value


def _ints(value) -> List[int]:
    """A repeated integer field's values: one varint, or a packed run."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def _hlo_modules(xspace: bytes) -> Iterator[bytes]:
    """The ``HloModuleProto`` of every ``Hlo Proto`` the profiler recorded
    in an XSpace."""
    for f, plane in _fields(xspace):
        if f != _SPACE_PLANES:
            continue
        fields = list(_fields(plane))
        name = next((v for f2, v in fields if f2 == _PLANE_NAME), b"")
        if name.decode() != METADATA_PLANE:
            continue
        stat_ids = set()
        for f2, entry in fields:
            if f2 == _PLANE_STAT_METADATA:
                meta = dict(_fields(dict(_fields(entry))[_MAP_VALUE]))
                if meta.get(_META_NAME, b"").decode() == HLO_PROTO_STAT:
                    stat_ids.add(meta.get(_META_ID, 0))
        for f2, entry in fields:
            if f2 != _PLANE_EVENT_METADATA:
                continue
            for f3, stat in _fields(dict(_fields(entry))[_MAP_VALUE]):
                if f3 == _EVENT_METADATA_STATS:
                    st = dict(_fields(stat))
                    if st.get(_STAT_METADATA_ID) in stat_ids:
                        hlo = dict(_fields(st.get(_STAT_BYTES, b"")))
                        yield hlo.get(_HLO_MODULE, b"")


def hlo_op_names(module: bytes) -> Tuple[Dict[str, str], Set[str]]:
    """``({instruction: op_name}, every instruction's name)`` of a
    serialized ``HloModuleProto`` (the profile's, or a compiled program's
    ``hlo_modules()[0].as_serialized_hlo_module_proto()``), instruction
    names as trace events write them (``%fusion.3``).  A fusion that XLA made without metadata of its own
    takes that of its fused computation's root, else of the first
    instruction in it that has one; a copy that XLA inserted without
    metadata (to change a layout, or to break an alias around a loop)
    takes that of what it copies."""
    names: Dict[int, str] = {}
    op_names: Dict[int, str] = {}
    roots: Dict[int, int] = {}  # computation -> its root instruction
    firsts: Dict[int, int] = {}  # computation -> first named instruction
    fusions: Dict[int, int] = {}  # unnamed fusion -> its computation
    copies: Dict[int, int] = {}  # unnamed copy -> what it copies
    for f, comp in _fields(module):
        if f != _MODULE_COMPUTATIONS:
            continue
        comp_id, root, insts = 0, 0, []
        for f2, v in _fields(comp):
            if f2 == _COMP_INSTRUCTIONS:
                insts.append(v)
            elif f2 == _COMP_ID:
                comp_id = v
            elif f2 == _COMP_ROOT_ID:
                root = v
        roots[comp_id] = root
        for inst in insts:
            name, opcode, op_name, iid, operands, called = "", "", "", 0, [], []
            for f3, v in _fields(inst):
                if f3 == _INST_NAME:
                    name = v.decode()
                elif f3 == _INST_OPCODE:
                    opcode = v.decode()
                elif f3 == _INST_METADATA:
                    op_name = dict(_fields(v)).get(_OP_NAME, b"").decode()
                elif f3 == _INST_ID:
                    iid = v
                elif f3 == _INST_OPERANDS:
                    operands += _ints(v)
                elif f3 == _INST_CALLED:
                    called += _ints(v)
            names[iid] = "%" + name
            if op_name:
                op_names[iid] = op_name
                firsts.setdefault(comp_id, iid)
            elif opcode == "fusion" and called:
                fusions[iid] = called[0]
            elif opcode == "copy" and operands:
                copies[iid] = operands[0]
    for iid, comp_id in fusions.items():
        source = roots.get(comp_id)
        source = source if source in op_names else firsts.get(comp_id)
        if source in op_names:
            op_names[iid] = op_names[source]
    for iid in copies:
        source, seen = copies[iid], {iid}
        while source in copies and source not in seen:  # a copy of a copy
            seen.add(source)
            source = copies[source]
        if source in op_names:
            op_names[iid] = op_names[source]
    return ({names[i]: n for i, n in op_names.items()},
            set(names.values()))


def profile_op_names(path: str) -> Tuple[Dict[str, str], Set[str]]:
    """``hlo_op_names`` over every program the profile at ``path``
    recorded (a traced window runs one)."""
    with open(path, "rb") as f:
        xspace = f.read()
    op_names: Dict[str, str] = {}
    names: Set[str] = set()
    for module in _hlo_modules(xspace):
        o, n = hlo_op_names(module)
        op_names.update(o)
        names |= n
    return op_names, names


def profile_path(run) -> Optional[str]:
    """The profile of a traced run, in the directory it carries
    (``run.trace_dir``); None where it has none."""
    if getattr(run, "trace_dir", None) is None:
        return None
    try:
        return trace.find_xplane(run.trace_dir)
    except FileNotFoundError:
        return None


# --------------------------------------------------------------------------
# a traced run's device events by scope
# --------------------------------------------------------------------------


def _fail(msg: str):
    raise BenchError(msg)


def op_names(run) -> Dict[str, str]:
    """``{instruction: op_name}`` of the program the traced run ran: the
    run's own ``op_names`` where its harness hands them over, else read
    from its profile (and kept on the run).  The run fails if there are
    none, or if they are not the traced program's."""
    if getattr(run, "op_names", None) is None:
        path = profile_path(run)
        if path is None:
            _fail("found no profile of this traced run to read the "
                  "program's op names from")
        names, insts = profile_op_names(path)
        loose = {trace.op_kind(n)[0] for n, _, _ in run.device_events()} - insts
        if loose:
            _fail(f"the profile's HLO holds none of {len(loose)} traced "
                  f"instructions, e.g. {sorted(loose)[:3]}")
        run.op_names = names
    if not run.op_names:
        _fail("no op names of the compiled program to attribute the "
              "device events with")
    return run.op_names


def named_kernel_seconds(run, direction: str) -> float:
    """Device seconds of the MSDA kernel events of ``direction`` on the
    first traced chip, found by the kernel's name
    (``repro.obs.scopes.KERNELS``).  Their count has to be what the
    committed plans launch in the traced calls, or the run fails."""
    name = vocabulary().KERNELS[direction]
    seconds, n = trace_kernel_seconds(run.device_events(), name)
    want = run.calls * run.msda_launches(direction)
    if n != want:
        _fail(f"the trace holds {n} kernel events named {name!r}; the "
              f"committed plans launch {want} MSDA {direction} kernels in "
              f"{run.calls} calls")
    return seconds


def kernel_name(event: str) -> str:
    """The Pallas kernel's name of a kernel event (``%msda_gather.3`` ->
    ``msda_gather``; a kernel the program did not name reads ``op``), or
    '' for an event that is not a Pallas kernel."""
    if trace.PALLAS not in event:
        return ""
    return trace.op_kind(event)[0].lstrip("%").split(".")[0]


def trace_kernel_seconds(events, name: str) -> Tuple[float, int]:
    """Total seconds and count of the Pallas kernel events named ``name``."""
    sel = [(s, e) for n, s, e in events if kernel_name(n) == name]
    return sum(e - s for s, e in sel) * 1e-9, len(sel)


def attributed(run) -> List[Tuple[Tuple[str, ...], str, float]]:
    """``(scopes, direction, seconds)`` of each device op event of the
    first traced chip: where ``repro.obs.scopes.layer_of`` places its
    op_name (no scopes for an event with no op_name, or none the
    vocabulary knows).  The run fails if the op names are missing or
    not the traced program's, or if the kernels are not found by name
    as often as the plans launch them: a reading would then attribute
    nothing, or not what it says."""
    names = op_names(run)
    for direction in ("fwd", "bwd"):
        if run.msda_launches(direction):
            named_kernel_seconds(run, direction)
    layer_of = vocabulary().layer_of
    out = []
    for n, s, e in run.device_events():
        scopes, direction = layer_of(names.get(trace.op_kind(n)[0], ""))
        out.append((scopes, direction, (e - s) * 1e-9))
    return out


def scope_seconds(run, scope: str, direction: Optional[str] = None) -> float:
    """Device seconds of the events in ``scope`` (and in ``direction``,
    'fwd' or 'bwd', if given)."""
    return sum(sec for scopes, d, sec in attributed(run)
               if scope in scopes and direction in (None, d))


def scope_split(run) -> Dict[str, float]:
    """Device seconds by outermost scope, and ``UNATTRIBUTED``: a
    partition of the first traced chip's op events."""
    out: Dict[str, float] = {}
    for scopes, _, sec in attributed(run):
        key = scopes[0] if scopes else UNATTRIBUTED
        out[key] = out.get(key, 0.0) + sec
    return out


# --------------------------------------------------------------------------
# the clocks
# --------------------------------------------------------------------------


def clock_offset_bounds(path: str) -> Tuple[float, float]:
    """Bounds ``(lo, hi)``, in seconds, on the offset to add to a time of
    the first device's clock to put it on the host's, from the profile at
    ``path``.  Each program run (``run_id``) is enqueued by the host
    before the device starts it (``lo`` = the latest enqueue start less
    device start), and the host's callbacks start after the device has
    ended it (``hi`` = the earliest callbacks start less device end).  A
    positive offset means the device clock reads early: it leads the
    host's."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    devices: Dict[str, Dict[int, Tuple[float, float]]] = {}
    enq: Dict[int, float] = {}
    cbs: Dict[int, float] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            runs = {_run_id(e): (e.start_ns, e.start_ns + e.duration_ns)
                    for line in plane.lines if line.name == MODULES_LINE
                    for e in line.events}
            if runs:
                devices[plane.name] = runs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == ENQUEUE:
                        enq[_run_id(e)] = e.start_ns
                    elif e.name == CALLBACKS:
                        cbs[_run_id(e)] = e.start_ns
    mods = devices[min(devices)] if devices else {}
    lo = [enq[r] - s for r, (s, _) in mods.items() if r in enq]
    hi = [cbs[r] - e for r, (_, e) in mods.items() if r in cbs]
    if not lo or not hi:
        raise ValueError("the profile pairs no device program run with the "
                         "host's enqueue and callbacks")
    return max(lo) * 1e-9, min(hi) * 1e-9


def _run_id(event) -> int:
    return int(dict(event.stats).get("run_id", -1))
