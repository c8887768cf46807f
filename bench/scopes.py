"""Read a ``jax.profiler`` capture (``.xplane.pb``) by the program's own
names: device seconds per ``jax.named_scope`` and host seconds per
``repro.obs`` span.

Every event of a device's ``XLA Ops`` line points at metadata whose
stats carry ``tf_op``, the JAX name stack of the operation (for example
``jit(advance)/esd.advance/esd.cache_update/capacity_cut/sort:``), and
``program_id``.  ``jax.profiler.ProfileData`` does not show metadata
stats, so this module reads the protobuf itself, with the message layout
of the public ``xplane.proto`` (``tsl/profiler/protobuf/xplane.proto``)
written out below; it needs ``google.protobuf`` and not TensorFlow.

Device time is exclusive: the XLA Ops line nests events (a ``while``
encloses the operations of its body), and an operation keeps only the
time not covered by the operations inside it, so each device nanosecond
counts once.  A scope holds every operation whose name stack has it as
a component, or inside the ``jvp(...)`` or ``transpose(...)`` of one
(the forward and backward of a differentiated scope).  Host time is the
``TraceAnnotation`` events of the main thread: the host line that holds
the ``bench.window`` mark.  Everything is clipped to the window; device
seconds are averaged over the chips.
"""
from __future__ import annotations

import dataclasses
from collections import defaultdict
from functools import lru_cache

from .xplane import MARK, OPS_LINE

_WRAPPERS = ("jvp(", "transpose(")


@lru_cache(maxsize=None)
def _xspace_class():
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    F = descriptor_pb2.FieldDescriptorProto
    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    i64, u64, dbl = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_DOUBLE
    text, raw, sub = F.TYPE_STRING, F.TYPE_BYTES, F.TYPE_MESSAGE
    pkg = "bench.xplane"
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package=pkg, syntax="proto3")

    def message(into, name, fields, oneof=None):
        m = into.add(name=name)
        if oneof:
            m.oneof_decl.add(name=oneof)
        for fname, number, ftype, label, *rest in fields:
            f = m.field.add(name=fname, number=number, type=ftype,
                            label=label)
            if ftype == sub:
                f.type_name = f".{pkg}.{rest[0]}"
            elif rest:                     # a member of the oneof
                f.oneof_index = 0
        return m

    message(fd.message_type, "XSpace", [
        ("planes", 1, sub, many, "XPlane"),
        ("errors", 2, text, many), ("warnings", 3, text, many),
        ("hostnames", 4, text, many)])
    plane = message(fd.message_type, "XPlane", [
        ("id", 1, i64, one), ("name", 2, text, one),
        ("lines", 3, sub, many, "XLine"),
        ("event_metadata", 4, sub, many, "XPlane.EventMetadataEntry"),
        ("stat_metadata", 5, sub, many, "XPlane.StatMetadataEntry"),
        ("stats", 6, sub, many, "XStat")])
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        m = message(plane.nested_type, entry, [
            ("key", 1, i64, one), ("value", 2, sub, one, value)])
        m.options.map_entry = True
    message(fd.message_type, "XLine", [
        ("id", 1, i64, one), ("display_id", 10, i64, one),
        ("name", 2, text, one), ("display_name", 11, text, one),
        ("timestamp_ns", 3, i64, one), ("duration_ps", 9, i64, one),
        ("events", 4, sub, many, "XEvent")])
    message(fd.message_type, "XEvent", [
        ("metadata_id", 1, i64, one), ("offset_ps", 2, i64, one, 0),
        ("num_occurrences", 5, i64, one, 0),
        ("duration_ps", 3, i64, one), ("stats", 4, sub, many, "XStat")],
        oneof="data")
    message(fd.message_type, "XStat", [
        ("metadata_id", 1, i64, one), ("double_value", 2, dbl, one, 0),
        ("uint64_value", 3, u64, one, 0), ("int64_value", 4, i64, one, 0),
        ("str_value", 5, text, one, 0), ("bytes_value", 6, raw, one, 0),
        ("ref_value", 7, u64, one, 0)], oneof="value")
    message(fd.message_type, "XEventMetadata", [
        ("id", 1, i64, one), ("name", 2, text, one),
        ("display_name", 4, text, one), ("metadata", 3, raw, one),
        ("stats", 5, sub, many, "XStat"), ("child_id", 6, i64, many)])
    message(fd.message_type, "XStatMetadata", [
        ("id", 1, i64, one), ("name", 2, text, one),
        ("description", 3, text, one)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{pkg}.XSpace"))


def read_space(path):
    """The ``XSpace`` message of an ``.xplane.pb`` file."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def scopes_of(tf_op: str) -> tuple:
    """The scopes an operation runs under: each component of its name
    stack but the last (the operation itself), with ``jvp(...)`` and
    ``transpose(...)`` taken off, each once, outermost first."""
    path = tf_op.partition(":")[0].split("/")[:-1]
    out = []
    for part in path:
        while part.endswith(")") and part.startswith(_WRAPPERS):
            part = part[part.index("(") + 1:-1]
        if part and part not in out:
            out.append(part)
    return tuple(out)


def _stat(plane, stats, name):
    for st in stats:
        if plane.stat_metadata[st.metadata_id].name == name:
            kind = st.WhichOneof("value")
            if kind == "ref_value":
                return plane.stat_metadata[st.ref_value].name
            return getattr(st, kind) if kind else None
    return None


@dataclasses.dataclass
class Op:
    tf_op: str
    seconds: float                  # exclusive, mean over devices


@dataclasses.dataclass
class Scopes:
    scope_s: dict                   # scope -> exclusive device seconds
    ops: dict                       # (program_id, hlo name) -> Op
    exclusive_s: float              # every operation's exclusive time
    host_s: dict                    # span name -> main-thread seconds


def _clipped(line, w0, w1):
    """(metadata id, start ps, end ps) of the line's events, clipped to
    the window (w0, w1) in ns; events outside it are left out."""
    base = line.timestamp_ns * 1000
    for ev in line.events:
        s = max(base + ev.offset_ps, w0 * 1000)
        e = min(base + ev.offset_ps + ev.duration_ps, w1 * 1000)
        if e > s:
            yield ev.metadata_id, s, e


def _exclusive(intervals):
    """Exclusive length of each (start, end) in a properly nested set."""
    order = sorted(range(len(intervals)),
                   key=lambda k: (intervals[k][0], -intervals[k][1]))
    excl = [e - s for s, e in intervals]
    stack = []                      # (end, index) of the enclosing events
    for k in order:
        s, e = intervals[k]
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            excl[stack[-1][1]] -= min(e, stack[-1][0]) - s
        stack.append((e, k))
    return excl


def reduce(space, window_ns, names=None) -> Scopes:
    """Device seconds per scope and host seconds per span name over
    ``window_ns`` (start, end) on the trace's clock; ``names`` limits
    the host side to the program's own spans."""
    w0, w1 = window_ns
    devices = [p for p in space.planes if p.name.startswith("/device:TPU:")
               and p.name[len("/device:TPU:"):].isdigit()]
    scope_s, ops = defaultdict(float), {}
    total = 0.0
    for plane in devices:
        owner = {}                  # metadata id -> (key, tf_op, scopes)
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            events = list(_clipped(line, w0, w1))
            spans = [(s, e) for _, s, e in events]
            for (mid, _, _), ps in zip(events, _exclusive(spans)):
                if mid not in owner:
                    md = plane.event_metadata[mid]
                    tf_op = _stat(plane, md.stats, "tf_op") or ""
                    hlo = md.name.split(" = ", 1)[0].lstrip("%")
                    key = (str(_stat(plane, md.stats, "program_id")), hlo)
                    owner[mid] = (key, tf_op, scopes_of(tf_op))
                key, tf_op, scopes = owner[mid]
                sec = ps * 1e-12 / len(devices)
                total += sec
                for scope in scopes:
                    scope_s[scope] += sec
                op = ops.setdefault(key, Op(tf_op, 0.0))
                op.seconds += sec
    return Scopes(dict(scope_s), ops, total, _host(space, w0, w1, names))


def _host(space, w0, w1, names):
    for plane in space.planes:
        if not plane.name.startswith("/host:"):
            continue
        names_of = {mid: md.name for mid, md in plane.event_metadata.items()}
        for line in plane.lines:
            if not any(names_of.get(ev.metadata_id) == MARK
                       for ev in line.events):
                continue
            out = defaultdict(float)
            for mid, s, e in _clipped(line, w0, w1):
                name = names_of.get(mid)
                if names is None or name in names:
                    out[name] += (e - s) * 1e-12
            return dict(out)
    return {}
