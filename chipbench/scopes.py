"""The device's op time split by the program's ``sb.*`` scopes.

A TPU profile names each device op by its HLO instruction.  The op's
scope path (``jax.named_scope`` names joined by ``/``: the ``op_name`` of
the op's metadata; a fusion carries its root op's) is the ``SCOPE_STAT``
stat of its event metadata, a string such as
``"jit(run)/while/body/closed_call/sb.write/gather:"`` or a reference to a
stat metadata entry whose name is that string.  ``chipbench/xplane.py``
reads no stats, so the harness's lines carry no scope paths: ``read``
reads them beside ``tracing.load_xplane``'s lines, and ``split`` turns a
traced window into the shares of op self time that ``SPLIT`` names.

Run ``python3 chipbench/scopes.py <file.xplane.pb>`` to print the split of
a profile whose window is the harness's ``WINDOW`` span.
"""
from __future__ import annotations

import json
import os
import re
import sys

import numpy as np
from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from chipbench import tracing, xplane  # noqa: E402
from chipbench.metrics import reduce  # noqa: E402

_F = xplane._F
# XPlane's fields beyond ``xplane._SCHEMA``, with the published numbers
_STATS = {
    "XPlane": [
        ("stat_metadata", 5, _F.TYPE_MESSAGE, _F.LABEL_REPEATED,
         "XPlane.StatMetadataEntry"),
        ("stats", 6, _F.TYPE_MESSAGE, _F.LABEL_REPEATED, "XStat"),
    ],
    "XEventMetadata": [
        ("stats", 5, _F.TYPE_MESSAGE, _F.LABEL_REPEATED, "XStat"),
    ],
    "XStat": [
        ("metadata_id", 1, _F.TYPE_INT64, _F.LABEL_OPTIONAL, None),
        ("uint64_value", 3, _F.TYPE_UINT64, _F.LABEL_OPTIONAL, None),
        ("str_value", 5, _F.TYPE_STRING, _F.LABEL_OPTIONAL, None),
        ("ref_value", 7, _F.TYPE_UINT64, _F.LABEL_OPTIONAL, None),
    ],
    "XStatMetadata": [
        ("name", 2, _F.TYPE_STRING, _F.LABEL_OPTIONAL, None),
    ],
}
# XPlane's two maps: id -> event metadata, id -> stat metadata
_MAPS = {"EventMetadataEntry": "XEventMetadata",
         "StatMetadataEntry": "XStatMetadata"}
# the event-metadata stat that holds an op's scope path
SCOPE_STAT = "tf_op"
# the plane stat holding the absolute time the file's line timestamps
# count from
START_STAT = "profile_start_time"
UNSCOPED = "unscoped"
# the shares ``split`` reports, each the sum of its scopes
SPLIT = {
    "cycle_body_pct": ("sb.read", "sb.step", "sb.write"),
    "block_step_pct": ("sb.step",),
    "exchange_pct": ("sb.drain", "sb.permute", "sb.fill"),
    "epoch_overhead_pct": ("sb.rows_split", "sb.rows_join", "sb.done"),
}
# the last ``sb.*`` component of a path (a TPU trace ends each path with
# ":", TensorFlow's "name:type" form)
_SCOPE = re.compile(r"(?:^|/)(sb\.[^/:]+)(?=[/:]|$)")


def _space_class():
    fd = descriptor_pb2.FileDescriptorProto(
        name="chipbench/scopes.proto", package=xplane._PKG, syntax="proto3")
    schema = {k: v + _STATS.get(k, []) for k, v in xplane._SCHEMA.items()}
    schema.update({k: v for k, v in _STATS.items() if k not in schema})
    for name, fields in schema.items():
        msg = fd.message_type.add()
        msg.CopyFrom(xplane._message(name, fields))
        if name == "XPlane":
            for entry_name, value in _MAPS.items():
                entry = msg.nested_type.add()
                entry.CopyFrom(xplane._message(entry_name, [
                    ("key", 1, _F.TYPE_INT64, _F.LABEL_OPTIONAL, None),
                    ("value", 2, _F.TYPE_MESSAGE, _F.LABEL_OPTIONAL,
                     value)]))
                entry.options.map_entry = True
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{xplane._PKG}.XSpace"))


def _parse(path: str):
    space = _space_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def _plane_scopes(plane) -> dict:
    """{event metadata id: scope path} for the plane's events whose
    metadata carries ``SCOPE_STAT``."""
    stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
    ids = {k for k, name in stat_names.items() if name == SCOPE_STAT}
    out = {}
    for k, md in plane.event_metadata.items() if ids else ():
        for st in md.stats:
            if st.metadata_id in ids:
                out[k] = st.str_value or stat_names.get(st.ref_value, "")
                break
    return out


def read(path: str):
    """``(lines, scopes)``: ``tracing.load_xplane``'s lines, and
    ``{(plane, line): [scope path of each of the line's names]}`` for the
    lines in which an event carries one."""
    space = _parse(path)
    scopes = {}
    for plane in space.planes:
        paths = _plane_scopes(plane)
        for ln in plane.lines:
            keys = np.unique([ev.metadata_id for ev in ln.events])
            if any(int(k) in paths for k in keys):
                scopes[plane.name, ln.name] = [paths.get(int(k), "")
                                               for k in keys]
    return tracing.load_xplane(path), scopes


def base_ns(path: str) -> int:
    """The absolute profiler-clock time (ns) that ``read``'s times count
    from: the file stores line timestamps from the profile's start, kept
    as the ``START_STAT`` stat of one plane (0 where none has it)."""
    space = _parse(path)
    start = 0
    for plane in space.planes:
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        for st in plane.stats:
            if names.get(st.metadata_id) == START_STAT:
                start = st.uint64_value
    return start + min((ln.timestamp_ns for p in space.planes
                        for ln in p.lines), default=0)


def load_json(path: str):
    """``(lines, scopes)`` from a trace in ``tracing.load_json``'s form
    whose lines may hold a ``scopes`` table ``{op name: scope path}``."""
    with open(path) as f:
        doc = json.load(f)
    lines = tracing.from_json(doc)
    scopes = {}
    for rec, ln in zip(doc["lines"], lines):
        if "scopes" in rec:
            scopes[ln.plane, ln.name] = [rec["scopes"].get(n, "")
                                         for n in ln.names]
    return lines, scopes


def scope_of(path: str) -> str:
    """The last ``sb.*`` component of a scope path, or ``UNSCOPED``."""
    found = _SCOPE.findall(path or "")
    return found[-1] if found else UNSCOPED


def scope_pct(lines, scopes) -> dict | None:
    """{scope: % of op self time}: device operations in the window by
    self time, each under ``scope_of`` its scope path, summed over
    devices, as shares of the total.  None where no op in the window
    carries an ``sb.*`` scope."""
    t0, t1 = reduce.window(lines)
    tot: dict[str, float] = {}
    for plane, dev in reduce.devices(lines).items():
        paths = scopes.get((plane, reduce.OPS))
        if paths is None or reduce.OPS not in dev:
            continue
        ops = reduce._clip(dev[reduce.OPS], t0, t1)
        per = np.bincount(ops.ids, weights=reduce.self_times(ops),
                          minlength=len(ops.names))
        for path, v in zip(paths, per):
            k = scope_of(path)
            tot[k] = tot.get(k, 0.0) + float(v)
    total = sum(tot.values())
    if total <= 0 or set(tot) <= {UNSCOPED}:
        return None
    return {k: 100.0 * v / total for k, v in tot.items()}


def split(lines, scopes) -> dict | None:
    """``SPLIT``'s shares and ``UNSCOPED``'s (None as ``scope_pct``)."""
    pct = scope_pct(lines, scopes)
    if pct is None:
        return None
    out = {k: sum(pct.get(s, 0.0) for s in v) for k, v in SPLIT.items()}
    out[UNSCOPED] = pct.get(UNSCOPED, 0.0)
    return out


if __name__ == "__main__":
    got = read(sys.argv[1])
    print(json.dumps({"split": split(*got), "scopes": scope_pct(*got)}))
