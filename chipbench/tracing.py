"""Profiler capture, and the trace read back as compact per-line arrays.

``capture(fn)`` runs ``fn`` under ``jax.profiler.trace`` (Python tracer
off) inside a host span named ``WINDOW``, reads the ``.xplane.pb`` file
back with ``chipbench/xplane.py`` and deletes it.  Every event of every
plane and line is kept as (name id, start, end) in nanoseconds on the
profiler's one clock, so the harness's host spans and the device's
events can be laid side by side.  Not ``jax.profiler.ProfileData``: it
gives each event's start as absolute float nanoseconds, which at today's
clock (~1.8e18 ns) step by 256 ns, longer than many of the device's ops.  The reductions are in
``chipbench/metrics/reduce.py``.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import shutil
import tempfile
import time

import numpy as np

WINDOW = "chipbench.window"


@dataclasses.dataclass
class Line:
    plane: str
    name: str
    names: list          # name table of this line
    ids: np.ndarray      # (n,) int32 index into ``names``
    start: np.ndarray    # (n,) float64 ns
    end: np.ndarray      # (n,) float64 ns

    def select(self, keep: np.ndarray) -> "Line":
        return Line(self.plane, self.name, self.names, self.ids[keep],
                    self.start[keep], self.end[keep])


def load_xplane(path: str) -> list[Line]:
    from chipbench import xplane

    return [Line(*rec) for rec in xplane.read(path)]


def from_json(doc: dict) -> list[Line]:
    out = []
    for rec in doc["lines"]:
        table: dict[str, int] = {}
        ids = [table.setdefault(n, len(table)) for n, _, _ in rec["events"]]
        st = np.array([s for _, s, _ in rec["events"]], np.float64)
        du = np.array([d for _, _, d in rec["events"]], np.float64)
        out.append(Line(rec["plane"], rec["line"], list(table),
                        np.array(ids, np.int32), st, st + du))
    return out


def load_json(path: str) -> list[Line]:
    with open(path) as f:
        return from_json(json.load(f))


def capture(fn):
    """``(fn(), lines, notes)``: ``fn`` run under the profiler in a
    ``WINDOW`` host span; the notes give the seconds spent stopping the
    profiler and reading its file back, and the file's size.  The trace is written to a temporary directory
    (under ``TMPDIR``) and deleted once read."""
    import jax.profiler

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        with jax.profiler.trace(tmp, profiler_options=opts):
            with jax.profiler.TraceAnnotation(WINDOW):
                out = fn()
            t0 = time.perf_counter()
        t1 = time.perf_counter()
        paths = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace file, found {paths}")
        size = os.path.getsize(paths[0])
        lines = load_xplane(paths[0])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    t2 = time.perf_counter()
    return out, lines, {"trace_stop_s": t1 - t0, "trace_read_s": t2 - t1,
                        "trace_bytes": size}
