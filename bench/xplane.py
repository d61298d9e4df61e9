"""Read what `jax.profiler.ProfileData` does not expose from a profiler
trace (``.xplane.pb``): each device op's metadata stats (``tf_op``, the
HLO op's ``op_name`` with its ``jax.named_scope`` path; ``bytes_accessed``;
``program_id``) and the HLO modules the trace carries.

A small reader of the protobuf wire format, for the few fields it needs, so
the benchmark imports no TensorFlow:

    XSpace          1 planes
    XPlane          2 name, 3 lines, 4 event_metadata, 5 stat_metadata
    XLine           2 name, 3 timestamp_ns, 4 events
    XEvent          1 metadata_id, 2 offset_ps, 3 duration_ps
    XEventMetadata  1 id, 2 name, 5 stats
    XStatMetadata   1 id, 2 name
    XStat           1 metadata_id, 2 double, 3 uint64, 4 int64, 5 str,
                    6 bytes, 7 ref (the name of another stat metadata)

    HloProto             1 hlo_module
    HloModuleProto       3 computations
    HloComputationProto  2 instructions, 5 id, 6 root_id
    HloInstructionProto  1 name, 2 opcode, 7 metadata, 35 id,
                         36 operand_ids, 38 called_computation_ids
    OpMetadata           2 op_name

Event times are whole nanoseconds, as `ProfileData` gives them.  An
event's stats are its metadata's; the per-event stats (the device's own
offsets and durations) are not read.

`scope_seconds` splits a window's leaf device time by the program's stage:
the innermost ``gw.`` component of each op's ``tf_op`` (its ``op_name``,
which ``jax.named_scope`` writes).  An op that XLA made after lowering
carries no ``op_name`` of its own; it takes the name of its nearest named
neighbour in the HLO module the trace carries (`op_names`).  Time in no
``gw.`` scope is ``"unscoped"``.  The harness hands the split of a traced
window to the per-layer readers (`bench.trace.Reduced.scope_seconds`);
the result line does not carry it.
"""
from __future__ import annotations

import dataclasses
import re
import struct
from collections import defaultdict, deque

DEVICE_PREFIX = "/device:"
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
OPS_LINE = "XLA Ops"
UNSCOPED = "unscoped"
_SCOPE = re.compile(r"\bgw\.[a-z_]+")


@dataclasses.dataclass
class Event:
    name: str          # its metadata's name: for an XLA op, the instruction
    start_ns: int
    duration_ns: int
    stats: dict        # its metadata's stats, by name


@dataclasses.dataclass
class Space:
    #: plane name → line name → [Event], for the planes asked for
    planes: dict
    #: program id → serialized ``HloProto``
    hlo: dict


def _varint(buf, i):
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _fields(buf, i=0, end=None):
    """(field number, value) of each field in ``buf[i:end]``: an int for
    varint and fixed fields, a ``(start, end)`` slice for length-delimited
    ones."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value = (i, i + size)
            i += size
        elif kind == 1:
            value = struct.unpack_from("<Q", buf, i)[0]
            i += 8
        elif kind == 5:
            value = struct.unpack_from("<I", buf, i)[0]
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, value


def _str(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf, span, stat_names: dict):
    """(name, value) of one XStat."""
    name, value = None, None
    for f, v in _fields(buf, *span):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f == 2:
            value = struct.unpack("<d", struct.pack("<Q", v))[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = _signed(v)
        elif f == 5:
            value = _str(buf, v)
        elif f == 6:
            value = bytes(buf[v[0]:v[1]])
        elif f == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _map_entries(buf, span):
    """(key, value slice) of one protobuf map entry."""
    key = value = None
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _plane(buf, span, prefix: str) -> tuple:
    """(name, {line name: [Event]} or None, {program id: HloProto bytes})
    of one XPlane; its events only when its name starts with ``prefix``."""
    name, lines, meta, stat_meta = "", [], [], []
    for f, v in _fields(buf, *span):
        if f == 2:
            name = _str(buf, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            meta.append(v)
        elif f == 5:
            stat_meta.append(v)
    hlo, want = {}, name.startswith(prefix)
    if not want and name != METADATA_PLANE:
        return name, None, hlo
    stat_names = {}
    for entry in stat_meta:
        key, v = _map_entries(buf, entry)
        stat_names[key] = next((_str(buf, s) for f, s in _fields(buf, *v)
                                if f == 2), "")
    events_meta = {}
    for entry in meta:
        key, v = _map_entries(buf, entry)
        ename, stats = "", {}
        for f, s in _fields(buf, *v):
            if f == 2:
                ename = _str(buf, s)
            elif f == 5:
                k, val = _stat(buf, s, stat_names)
                stats[k] = val
        events_meta[key] = (ename, stats)
        if name == METADATA_PLANE and HLO_STAT in stats:
            hlo[key] = stats[HLO_STAT]
    if not want:
        return name, None, hlo
    out = {}
    for line in lines:
        lname, t0, events = "", 0, []
        for f, v in _fields(buf, *line):
            if f == 2:
                lname = _str(buf, v)
            elif f == 3:
                t0 = _signed(v)
            elif f == 4:
                events.append(v)
        decoded = out.setdefault(lname, [])
        for ev in events:
            mid = offset = duration = 0
            for f, v in _fields(buf, *ev):
                if f == 1:
                    mid = v
                elif f == 2:
                    offset = _signed(v)
                elif f == 3:
                    duration = _signed(v)
            ename, stats = events_meta.get(mid, ("", {}))
            decoded.append(Event(ename, t0 + offset // 1000, duration // 1000,
                                 stats))
    return name, out, hlo


def read(path: str, prefix: str = DEVICE_PREFIX) -> Space:
    """The events of every plane whose name starts with ``prefix``, and the
    HLO modules of the metadata plane."""
    with open(path, "rb") as fh:
        buf = memoryview(fh.read())
    planes, hlo = {}, {}
    for f, v in _fields(buf):
        if f == 1:
            name, lines, protos = _plane(buf, v, prefix)
            hlo.update(protos)
            if lines is not None:
                planes[name] = lines
    return Space(planes, hlo)


@dataclasses.dataclass
class _Inst:
    name: str = ""
    opcode: str = ""
    op_name: str = ""
    operands: list = dataclasses.field(default_factory=list)
    called: list = dataclasses.field(default_factory=list)


def _append_ids(buf, value, out: list) -> None:
    """The values of a repeated integer field, packed or not."""
    if not isinstance(value, tuple):
        out.append(value)
        return
    j = value[0]
    while j < value[1]:
        x, j = _varint(buf, j)
        out.append(x)


def _instructions(proto: bytes):
    """({instruction id: _Inst}, {computation id: root instruction id}) of
    an ``HloProto``."""
    buf = memoryview(proto)
    insts, roots = {}, {}
    for f, module in _fields(buf):
        if f != 1:
            continue
        for g, comp in _fields(buf, *module):
            if g != 3:
                continue
            cid = root = None
            for h, v in _fields(buf, *comp):
                if h == 5:
                    cid = v
                elif h == 6:
                    root = v
                elif h == 2:
                    inst, iid = _Inst(), None
                    for k, w in _fields(buf, *v):
                        if k == 1:
                            inst.name = _str(buf, w)
                        elif k == 2:
                            inst.opcode = _str(buf, w)
                        elif k == 7:
                            inst.op_name = next(
                                (_str(buf, x) for m, x in _fields(buf, *w)
                                 if m == 2), "")
                        elif k == 35:
                            iid = w
                        elif k == 36:
                            _append_ids(buf, w, inst.operands)
                        elif k == 38:
                            _append_ids(buf, w, inst.called)
                    insts[iid] = inst
            roots[cid] = root
    return insts, roots


def op_names(proto: bytes) -> dict:
    """Instruction name → ``op_name`` for every instruction of an
    ``HloProto``, filled in where XLA made the instruction after JAX's
    lowering and gave it no metadata (a layout copy, a fusion rooted in
    one): a fusion takes the name of its root, else of the nearest named
    instruction feeding the root; any other instruction that of the
    nearest named instruction that consumes its result, else of the
    nearest that produces its operands.  An instruction with no named
    neighbour keeps ""."""
    insts, roots = _instructions(proto)
    users = {}
    for iid, inst in insts.items():
        for op in inst.operands:
            users.setdefault(op, []).append(iid)

    def operands(i):
        return insts[i].operands

    def consumers(i):
        return users.get(i, ())

    named = {}

    def own(i):
        if i not in named:
            inst = insts[i]
            named[i] = inst.op_name
            if not inst.op_name and inst.opcode == "fusion" and inst.called:
                root = roots.get(inst.called[0])
                if root in insts:
                    named[i] = own(root) or nearest(root, operands)
        return named[i]

    def nearest(start, step):
        seen, todo = {start}, deque(step(start))
        while todo:
            i = todo.popleft()
            if i in seen or i not in insts:
                continue
            seen.add(i)
            if own(i):
                return own(i)
            todo.extend(step(i))
        return ""

    return {inst.name: own(i) or nearest(i, consumers) or nearest(i, operands)
            for i, inst in insts.items()}


def scope_of(op_name: str | None) -> str:
    """The innermost ``gw.`` scope of an ``op_name``, or "unscoped"."""
    found = _SCOPE.findall(op_name or "")
    return found[-1] if found else UNSCOPED


def scope_seconds(path: str, lo: float, hi: float) -> dict:
    """{scope: seconds} of the leaf device events inside [lo, hi] ns,
    summed over the traced devices."""
    from bench.trace import DEVICE_PREFIX as TPU_PREFIX, leaves

    space = read(path, TPU_PREFIX)
    names = {}
    out = defaultdict(float)
    for lines in space.planes.values():
        ops = []
        for e in lines.get(OPS_LINE, ()):
            name = e.stats.get("tf_op")
            if name is None:
                pid = e.stats.get("program_id")
                if pid not in names:
                    proto = space.hlo.get(pid)
                    names[pid] = op_names(proto) if proto else {}
                name = names[pid].get(e.name.split(" = ", 1)[0][1:])
            ops.append((e.start_ns, e.start_ns + e.duration_ns,
                        scope_of(name)))
        for s, e, scope in leaves(ops):
            d = min(e, hi) - max(s, lo)
            if d > 0:
                out[scope] += d
    return {k: v * 1e-9 for k, v in out.items()}
