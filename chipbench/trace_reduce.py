"""Profiler trace (``.xplane.pb``) -> device time per named scope, busy
union, idle share, top device operations and named idle gaps.

A device operation belongs to a layer by the ``jax.named_scope`` path in
its metadata (the ``tf_op`` stat of a TPU ``XLA Ops`` event, e.g.
``jit(_simulate)/while/body/policy_step/...``). A fusion carries the
metadata of one instruction, so a fusion that spans two scopes is counted
once, whole, under the scopes of its own path. An operation that XLA
inserts carries no metadata (a relayout ``copy``, an async copy), though
the profiler may give it the path of an enclosing ``while``: it takes the
path of the nearest instruction that consumes its result, or failing that
of the nearest that produces its operand, over the HLO graph of its own
program (the ``Hlo Proto`` the trace's ``/host:metadata`` plane carries);
where neither has a path it keeps none. A ``while`` operation is left out: its time is
that of its body's operations, which the trace lists one by one.
Collectives are recognised by operation name and counted apart from every
scope.

The reduction covers a *stretch*: from the first device operation at or
after ``start_ns`` to the first at or after ``end_ns`` (the harness passes
the host times at which two consecutive calls began, so the stretch holds
one whole call and the host gap that follows it).
"""

from __future__ import annotations

import bisect
import re
from typing import NamedTuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|allreduce|allgather|send|recv",
    re.IGNORECASE,
)
HOST_PLANE = "/host:CPU"
# Device gaps shorter than this are the launch spacing between operations
# of one program, not host work; they are counted under one name.
SHORT_GAP_NS = 50_000


class Op(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float
    path: str  # the named-scope path, "" where the event carries none


class HostSpan(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float


# The named scopes the program's scan body opens (kvsim/simulate.py).
SCOPES = (
    "chunk_replay", "policy_step", "fault_prepass", "routing_prepass",
    "contention_prepass", "fault_counters", "repair_accounting",
    "attribution_components", "attribution_fold", "flight_recorder",
)


class Reduced(NamedTuple):
    chips: int
    window_ns: float  # length of the stretch, mean over chips
    busy_ns: float  # union of operation intervals, mean over chips
    ops: list  # [Op] inside the stretch, every chip, ends clipped to it
    device_ops: list  # [[name, seconds], ...] longest first, at most 10
    idle_gaps: list  # [[host activity, seconds], ...] longest first

    def time_ns(self, keep) -> float:
        """Device time of the operations ``keep(op)`` selects, mean over
        chips."""
        return sum(op.dur_ns for op in self.ops if keep(op)) / self.chips


def is_collective(op: Op) -> bool:
    return bool(COLLECTIVE.search(op.name))


def under(scope: str):
    """Selects the non-collective operations whose path names ``scope``."""
    return lambda op: not is_collective(op) and scope in op.path.split("/")


def unscoped(op: Op) -> bool:
    parts = op.path.split("/")
    return not is_collective(op) and not any(s in parts for s in SCOPES)


def _proto_classes():
    """The ``XSpace`` message of the profiler's ``xplane.proto`` and the
    ``HloProto`` of XLA's ``hlo.proto``, declared here with the fields this
    reduction reads (event metadata carries the ``tf_op`` scope path that
    ``jax.profiler.ProfileData`` does not expose)."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    fd = descriptor_pb2.FieldDescriptorProto
    proto = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package="chipbench", syntax="proto3"
    )
    i64, u64, dbl = fd.TYPE_INT64, fd.TYPE_UINT64, fd.TYPE_DOUBLE
    text, blob, msg = fd.TYPE_STRING, fd.TYPE_BYTES, fd.TYPE_MESSAGE
    schema = {
        "XSpace": [("planes", 1, msg, "XPlane")],
        "XPlane": [("name", 2, text, None), ("lines", 3, msg, "XLine"),
                   ("event_metadata", 4, msg, "EventMetadataEntry"),
                   ("stat_metadata", 5, msg, "StatMetadataEntry")],
        "EventMetadataEntry": [("key", 1, i64, None),
                               ("value", 2, msg, "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, i64, None),
                              ("value", 2, msg, "XStatMetadata")],
        "XLine": [("name", 2, text, None), ("timestamp_ns", 3, i64, None),
                  ("events", 4, msg, "XEvent")],
        "XEvent": [("metadata_id", 1, i64, None), ("offset_ps", 2, i64, None),
                   ("duration_ps", 3, i64, None)],
        "XStat": [("metadata_id", 1, i64, None), ("double_value", 2, dbl, None),
                  ("uint64_value", 3, u64, None), ("int64_value", 4, i64, None),
                  ("str_value", 5, text, None), ("bytes_value", 6, blob, None),
                  ("ref_value", 7, u64, None)],
        "XEventMetadata": [("name", 2, text, None),
                           ("display_name", 4, text, None),
                           ("stats", 5, msg, "XStat")],
        "XStatMetadata": [("name", 2, text, None)],
        "HloProto": [("hlo_module", 1, msg, "HloModuleProto")],
        "HloModuleProto": [("name", 1, text, None),
                           ("computations", 3, msg, "HloComputationProto")],
        "HloComputationProto": [("instructions", 2, msg,
                                 "HloInstructionProto")],
        "HloInstructionProto": [("name", 1, text, None),
                                ("metadata", 7, msg, "OpMetadata"),
                                ("id", 35, i64, None),
                                ("operand_ids", 36, i64, None)],
        "OpMetadata": [("op_name", 2, text, None)],
    }
    repeated = {"planes", "lines", "event_metadata", "stat_metadata",
                "events", "stats", "computations", "instructions",
                "operand_ids"}
    for name, fields in schema.items():
        m = proto.message_type.add(name=name)
        for field, number, kind, of in fields:
            f = m.field.add(
                name=field, number=number, type=kind,
                label=fd.LABEL_REPEATED if field in repeated
                else fd.LABEL_OPTIONAL,
            )
            if of:
                f.type_name = ".chipbench." + of
    pool = descriptor_pool.DescriptorPool()
    pool.Add(proto)
    get = lambda name: message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench." + name)
    )
    return get("XSpace"), get("HloProto")


def _metadata_stats(meta, stat_names) -> dict:
    out = {}
    for st in meta.stats:
        ref = stat_names.get(st.ref_value) if st.ref_value else None
        out[stat_names.get(st.metadata_id)] = st.str_value or ref
    return out


# Control-flow operations: their time is their body's, listed on its own.
CONTAINERS = ("while", "conditional", "call")


def inherited_paths(instructions) -> dict:
    """``{name: path}`` for the instructions of one HLO module that carry
    no ``op_name``: the nearest user's path, else the nearest operand's,
    searched breadth first through other instructions without one (``""``
    where none is found). ``instructions`` holds ``(id, name, op_name,
    operand_ids)`` for every instruction of the module."""
    by_id = {i: (name, op) for i, name, op, _ in instructions}
    operands = {i: [o for o in ops if o in by_id]
                for i, _, _, ops in instructions}
    users = {i: [] for i in by_id}
    for i, ops in operands.items():
        for o in ops:
            users[o].append(i)

    def nearest(start, step):
        seen, frontier = {start}, [start]
        while frontier:
            nxt = []
            for i in frontier:
                for j in step[i]:
                    if j in seen:
                        continue
                    seen.add(j)
                    if by_id[j][1]:
                        return by_id[j][1]
                    nxt.append(j)
            frontier = nxt
        return ""

    return {name: nearest(i, users) or nearest(i, operands)
            for i, (name, op) in by_id.items() if not op}


def _module_paths(space, hlo_class) -> dict:
    """``{program name: {instruction: inherited path}}`` from the HLO
    protos of the ``/host:metadata`` plane."""
    out = {}
    for plane in space.planes:
        if plane.name != METADATA_PLANE:
            continue
        for entry in plane.event_metadata:
            for st in entry.value.stats:
                if not st.bytes_value:
                    continue
                hlo = hlo_class()
                hlo.ParseFromString(st.bytes_value)
                out[entry.value.name] = inherited_paths([
                    (ins.id, ins.name, ins.metadata.op_name,
                     list(ins.operand_ids))
                    for comp in hlo.hlo_module.computations
                    for ins in comp.instructions
                ])
    return out


def _with_inherited(ops, modules, paths):
    """Each operation of a program that ``paths`` covers, with the path
    its instruction inherits where it carries no ``op_name``. ``modules``
    holds the sorted ``(start_ns, end_ns, program)`` spans of the device's
    ``XLA Modules`` line."""
    starts = [m[0] for m in modules]
    out = []
    for op in ops:
        i = bisect.bisect_right(starts, op.start_ns) - 1
        program = modules[i][2] if i >= 0 and op.start_ns < modules[i][1] else None
        inherited = paths.get(program, {})
        out.append(op._replace(path=inherited[op.name])
                   if op.name in inherited else op)
    return out


def read_xplane(path: str):
    """``(ops per device [[Op]], host spans [HostSpan])`` from an
    ``.xplane.pb`` file: the leaf operations of each device's ``XLA Ops``
    line, and the spans of the host thread that opened the harness's
    ``chipbench.call`` spans (Python frames and annotations), all on the
    profiler's common clock."""
    xspace, hlo = _proto_classes()
    space = xspace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    paths = _module_paths(space, hlo)
    devices, host_lines = {}, []
    for plane in space.planes:
        meta = {e.key: e.value for e in plane.event_metadata}
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, modules = [], []
            for line in plane.lines:
                at = lambda ev: line.timestamp_ns + ev.offset_ps / 1e3
                if line.name == MODULES_LINE:
                    modules.extend(
                        (at(ev), at(ev) + ev.duration_ps / 1e3,
                         meta[ev.metadata_id].name)
                        for ev in line.events
                    )
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    md = meta[ev.metadata_id]
                    st = _metadata_stats(md, stat_names)
                    if st.get("hlo_category") in CONTAINERS:
                        continue
                    ops.append(Op(md.display_name or md.name, at(ev),
                                  ev.duration_ps / 1e3,
                                  (st.get("tf_op") or "").rstrip(":")))
            devices[int(m.group(2))] = _with_inherited(ops, sorted(modules),
                                                       paths)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host_lines.append([
                    HostSpan(meta[ev.metadata_id].name,
                             line.timestamp_ns + ev.offset_ps / 1e3,
                             ev.duration_ps / 1e3)
                    for ev in line.events
                ])
    host = next((spans for spans in host_lines
                 if any(s.name == "chipbench.call" for s in spans)), [])
    return [devices[k] for k in sorted(devices)], host


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _host_name(spans, t: float) -> str:
    """The innermost host span open at time ``t``."""
    best = None
    for sp in spans:
        if sp.start_ns <= t < sp.start_ns + sp.dur_ns:
            if best is None or sp.dur_ns < best.dur_ns:
                best = sp
    return "host idle" if best is None else best.name


def _label(op: Op) -> str:
    if is_collective(op):
        return "collective:" + op.name
    parts = op.path.split("/")
    scope = next((p for p in reversed(parts) if p in SCOPES), "unscoped")
    return scope + ":" + op.name


def reduce(devices, host, start_ns: float, end_ns: float,
           top: int = 10) -> Reduced:
    """Reduce one stretch of a trace (see the module docstring)."""
    busy = window = 0.0
    kept, gaps = [], {}
    used = [ops for ops in devices if ops]
    for chip, ops in enumerate(used):
        starts = sorted(op.start_ns for op in ops)
        lo = next((t for t in starts if t >= start_ns), None)
        hi = next((t for t in starts if t >= end_ns), None)
        if lo is None or hi is None or hi <= lo:
            raise ValueError("the trace holds no device operation after "
                             "both ends of the stretch")
        window += hi - lo
        inside = [
            op._replace(dur_ns=min(op.start_ns + op.dur_ns, hi) - op.start_ns)
            for op in ops if lo <= op.start_ns < hi
        ]
        kept.extend(inside)
        merged = _union((op.start_ns, op.start_ns + op.dur_ns)
                        for op in inside)
        busy += sum(e - s for s, e in merged)
        if chip == 0:
            edges = [(lo, lo)] + [tuple(m) for m in merged] + [(hi, hi)]
            for (_, e0), (s1, _) in zip(edges, edges[1:]):
                if s1 > e0:
                    name = ("between operations" if s1 - e0 < SHORT_GAP_NS
                            else _host_name(host, (e0 + s1) / 2))
                    gaps[name] = gaps.get(name, 0.0) + (s1 - e0)
    chips = len(used)
    by_label: dict = {}
    for op in kept:
        by_label[_label(op)] = by_label.get(_label(op), 0.0) + op.dur_ns
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return Reduced(
        chips=chips,
        window_ns=window / chips,
        busy_ns=busy / chips,
        ops=kept,
        device_ops=[[k, v / chips / 1e9] for k, v in rank(by_label)],
        idle_gaps=[[k, v / 1e9] for k, v in rank(gaps)],
    )
