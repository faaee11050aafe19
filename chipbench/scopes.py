"""Each device op's JAX name stack, read from the profiler trace itself.

A TPU trace carries the optimized HLO module of every program that ran:
on its ``/host:metadata`` plane, one ``Hlo Proto`` stat per program,
keyed by the program's id. Each instruction there holds its name stack
in ``metadata.op_name`` (``jit(step_fn)/transpose(jvp(layers))/while/
body/closed_call/checkpoint/attention/...``), which carries the scopes
the program sets with ``jax.named_scope``, wrapped in the names of the
transformations applied to them. The device plane's ops are named by
the same instruction names, and each op's event metadata names its
program (stat ``program_id``). So the trace alone joins each op to its
scope, for any program, with nothing but Python's standard library: the
few fields needed are decoded from protobuf's wire format here.

Instructions that XLA's passes make carry no name stack (the sort that
a scatter becomes, the pieces of a cumsum's reduce-window, a copy): each
takes the stack of the nearest instruction that consumes its result and
has one, or else of the instruction whose computation it runs in (a
while loop's body). An op that matches no instruction is ``(unscoped)``.
"""
from __future__ import annotations

import functools
import os
import re
from collections import deque

from chipbench import trace as trace_lib

UNSCOPED = "(unscoped)"
HLO_STAT = "Hlo Proto"
PROGRAM_STAT = "program_id"
METADATA_PLANE = "/host:metadata"

# field numbers: tsl/profiler/protobuf/xplane.proto, xla/service/hlo.proto, xla/xla_data.proto
SPACE_PLANES = 1
PLANE_NAME, PLANE_EVENT_METADATA, PLANE_STAT_METADATA = 2, 4, 5
MAP_KEY, MAP_VALUE = 1, 2
META_ID, META_NAME, EVENT_META_STATS = 1, 2, 5
STAT_METADATA_ID, STAT_BYTES = 1, 6
STAT_INTS = (3, 4)  # uint64_value, int64_value
HLO_MODULE = 1
MODULE_COMPUTATIONS = 3
COMPUTATION_INSTRUCTIONS, COMPUTATION_ID = 2, 5
INSTR_NAME, INSTR_METADATA, INSTR_ID, INSTR_OPERANDS, INSTR_CALLED = 1, 7, 35, 36, 38
OP_NAME = 2


def _varint(buf, i: int) -> tuple[int, int]:
    """The varint at ``buf[i]``, unsigned (a negative int64 reads as its
    64-bit two's complement, so an id stored as int64 in one place and as
    uint64 in another compares equal), and the index after it."""
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one message; a varint as an
    int, a length-delimited field as a memoryview, fixed-width ones skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            i += 8
            continue
        elif wire == 5:
            i += 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _ints(value) -> list[int]:
    """A repeated integer field's occurrence: packed (bytes) or one varint."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _map_entries(plane, field: int):
    """(key, value message) of a map field of a plane."""
    for f, entry in plane:
        if f == field:
            key, value = 0, b""
            for g, v in _fields(entry):
                if g == MAP_KEY:
                    key = v
                elif g == MAP_VALUE:
                    value = v
            yield key, value


def _stat_ids(plane) -> dict[str, int]:
    out = {}
    for _, meta in _map_entries(plane, PLANE_STAT_METADATA):
        fields = dict(_fields(meta))
        out[_text(fields.get(META_NAME, b""))] = fields.get(META_ID, 0)
    return out


def _event_metadata(plane):
    """(id, name, [(stat id, value)]) of each event metadata of a plane."""
    for key, meta in _map_entries(plane, PLANE_EVENT_METADATA):
        name, stats = "", []
        for f, v in _fields(meta):
            if f == META_NAME:
                name = _text(v)
            elif f == EVENT_META_STATS:
                stat, value = 0, None
                for g, w in _fields(v):
                    if g == STAT_METADATA_ID:
                        stat = w
                    elif g == STAT_BYTES or g in STAT_INTS:
                        value = w
                stats.append((stat, value))
        yield key, name, stats


def module_stacks(hlo_proto) -> dict[str, str]:
    """Instruction name -> name stack, for one serialized ``HloProto``."""
    module = next((v for f, v in _fields(hlo_proto) if f == HLO_MODULE), b"")
    instrs: dict[int, dict] = {}
    comps: dict[int, list[int]] = {}
    for f, comp in _fields(module):
        if f != MODULE_COMPUTATIONS:
            continue
        ids, cid = [], 0
        for g, v in _fields(comp):
            if g == COMPUTATION_ID:
                cid = v
            elif g == COMPUTATION_INSTRUCTIONS:
                ins = {"name": "", "stack": "", "operands": [], "called": []}
                for h, w in _fields(v):
                    if h == INSTR_NAME:
                        ins["name"] = _text(w)
                    elif h == INSTR_ID:
                        ins["id"] = w
                    elif h == INSTR_OPERANDS:
                        ins["operands"] += _ints(w)
                    elif h == INSTR_CALLED:
                        ins["called"] += _ints(w)
                    elif h == INSTR_METADATA:
                        op = next((x for k, x in _fields(w) if k == OP_NAME), b"")
                        # a bare op name ("reduce_window_sum") has no stack
                        ins["stack"] = _text(op) if b"/" in bytes(op) else ""
                instrs[ins["id"]] = ins
                ids.append(ins["id"])
        comps[cid] = ids
    users: dict[int, list[int]] = {i: [] for i in instrs}
    caller: dict[int, int] = {}
    for i, ins in instrs.items():
        for o in ins["operands"]:
            if o in users:
                users[o].append(i)
        for c in ins["called"]:
            for j in comps.get(c, ()):
                caller.setdefault(j, i)

    @functools.cache
    def stack(i: int) -> str:
        if instrs[i]["stack"]:
            return instrs[i]["stack"]
        seen, todo = {i}, deque(users[i])
        while todo:  # nearest consumer with a stack, breadth first
            j = todo.popleft()
            if instrs[j]["stack"]:
                return instrs[j]["stack"]
            if j not in seen:
                seen.add(j)
                todo.extend(users[j])
        return stack(caller[i]) if i in caller else UNSCOPED

    return {ins["name"]: stack(i) for i, ins in instrs.items()}


def read(path: str) -> dict[str, str]:
    """Device op name -> its name stack, for every TPU op of the trace at
    ``path`` (a file, or a profiler log directory)."""
    if os.path.isdir(path):
        path = trace_lib.find_xplane(path)
    with open(path, "rb") as f:
        space = memoryview(f.read())
    modules: dict[int, dict[str, str]] = {}
    devices = []
    for f, plane in _fields(space):
        if f != SPACE_PLANES:
            continue
        fields = list(_fields(plane))
        name = next((_text(v) for g, v in fields if g == PLANE_NAME), "")
        if name == METADATA_PLANE:
            hlo = _stat_ids(fields).get(HLO_STAT)
            for program, _, stats in _event_metadata(fields):
                for stat, value in stats:
                    if stat == hlo and value is not None:
                        modules[program] = module_stacks(value)
        elif trace_lib.DEVICE_PLANE.match(name):
            devices.append(fields)
    stacks: dict[str, str] = {}
    for fields in devices:
        program = _stat_ids(fields).get(PROGRAM_STAT)
        for _, text, stats in _event_metadata(fields):
            op = trace_lib.op_name(text)
            pid = next((v for s, v in stats if s == program), None)
            stacks.setdefault(op, modules.get(pid, {}).get(op, UNSCOPED))
    return stacks


def holds(stack: str, scope: str) -> bool:
    """Whether a component of ``stack`` is ``scope``, bare or wrapped in
    transformations (``jvp(layers)``, ``transpose(jvp(layers))``)."""
    pattern = re.compile(r"(?:[^/()]*\()*" + re.escape(scope) + r"\)*")
    return any(pattern.fullmatch(c) for c in stack.split("/"))


def backward(stack: str) -> bool:
    """Whether the op is in the transposed (backward) pass."""
    return any(c.startswith("transpose(") for c in stack.split("/"))


def scope_seconds(trace, stacks: dict[str, str], match) -> float:
    """Self seconds of the ops whose name stack ``match`` accepts, summed
    over the chips and divided by their number, as ``Trace.op_seconds``."""
    return trace.op_seconds(lambda name: match(stacks.get(name, UNSCOPED)))


def ms_per_unit(trace, match) -> float | None:
    """Device milliseconds per unit of work of the ops whose name stack
    ``match`` accepts, in the run's trace; None where there are none."""
    if trace is None or not trace.units():
        return None
    from chipbench import run

    seconds = scope_seconds(trace, read(str(run.TRACE_DIR)), match)
    if seconds <= 0:
        return None
    return seconds / len(trace.units()) * 1e3
