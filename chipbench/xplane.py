"""Read a profiler ``.xplane.pb`` file with the protobuf runtime alone.

The schema below is the subset of XPlane (``tsl/profiler/protobuf/
xplane.proto``, the profiler's own format) that the benchmark reads:
planes, their lines, events and event names, with the field numbers of
the published proto.  Fields not declared here are skipped when parsing.
An event's time is its line's ``timestamp_ns`` plus its ``offset_ps``,
the profiler's one clock for host and device planes.
"""
from __future__ import annotations

import numpy as np
from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_F = descriptor_pb2.FieldDescriptorProto
# message -> [(field, number, type, label, message type)]
_SCHEMA = {
    "XSpace": [("planes", 1, _F.TYPE_MESSAGE, _F.LABEL_REPEATED, "XPlane")],
    "XPlane": [
        ("name", 2, _F.TYPE_STRING, _F.LABEL_OPTIONAL, None),
        ("lines", 3, _F.TYPE_MESSAGE, _F.LABEL_REPEATED, "XLine"),
        ("event_metadata", 4, _F.TYPE_MESSAGE, _F.LABEL_REPEATED,
         "XPlane.EventMetadataEntry"),
    ],
    "XLine": [
        ("name", 2, _F.TYPE_STRING, _F.LABEL_OPTIONAL, None),
        ("timestamp_ns", 3, _F.TYPE_INT64, _F.LABEL_OPTIONAL, None),
        ("events", 4, _F.TYPE_MESSAGE, _F.LABEL_REPEATED, "XEvent"),
    ],
    "XEvent": [
        ("metadata_id", 1, _F.TYPE_INT64, _F.LABEL_OPTIONAL, None),
        ("offset_ps", 2, _F.TYPE_INT64, _F.LABEL_OPTIONAL, None),
        ("duration_ps", 3, _F.TYPE_INT64, _F.LABEL_OPTIONAL, None),
    ],
    "XEventMetadata": [
        ("name", 2, _F.TYPE_STRING, _F.LABEL_OPTIONAL, None),
    ],
}
_PKG = "chipbench.xplane"


def _message(name, fields):
    msg = descriptor_pb2.DescriptorProto(name=name)
    for fname, num, ftype, label, tname in fields:
        f = msg.field.add(name=fname, number=num, type=ftype, label=label)
        if tname:
            f.type_name = f".{_PKG}.{tname}"
    return msg


def _space_class():
    fd = descriptor_pb2.FileDescriptorProto(
        name="chipbench/xplane.proto", package=_PKG, syntax="proto3")
    for name, fields in _SCHEMA.items():
        msg = fd.message_type.add()
        msg.CopyFrom(_message(name, fields))
        if name == "XPlane":
            entry = msg.nested_type.add()
            entry.CopyFrom(_message("EventMetadataEntry", [
                ("key", 1, _F.TYPE_INT64, _F.LABEL_OPTIONAL, None),
                ("value", 2, _F.TYPE_MESSAGE, _F.LABEL_OPTIONAL,
                 "XEventMetadata")]))
            entry.options.map_entry = True
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PKG}.XSpace"))


def read(path: str):
    """``[(plane, line, names, ids, start_ns, end_ns)]`` for every line;
    times are float64 ns from the earliest line's timestamp."""
    space = _space_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    lines = [(p, ln) for p in space.planes for ln in p.lines]
    base = min((ln.timestamp_ns for _, ln in lines), default=0)
    out = []
    for plane, ln in lines:
        meta = {k: v.name for k, v in plane.event_metadata.items()}
        n = len(ln.events)
        mid = np.empty(n, np.int64)
        off = np.empty(n, np.int64)
        dur = np.empty(n, np.int64)
        for i, ev in enumerate(ln.events):
            mid[i] = ev.metadata_id
            off[i] = ev.offset_ps
            dur[i] = ev.duration_ps
        keys, ids = np.unique(mid, return_inverse=True)
        start = (ln.timestamp_ns - base) + off / 1000.0
        out.append((plane.name, ln.name,
                    [meta.get(int(k), str(int(k))) for k in keys],
                    ids.astype(np.int32), start, start + dur / 1000.0))
    return out
