"""Structured trace layer: spans on the profiler's clock, a bounded
recorder with Perfetto export, and the names of the epoch program's device
scopes.

``span(name)`` is the one span call.  It always enters a
``jax.profiler.TraceAnnotation`` of the same name, so the span lands in the
profiler's host plane, beside the device's ops, whenever a profile is
running; when the recorder is enabled it also appends a Chrome trace-event
span to the bounded buffer.  With the recorder off and no profile running
a span costs one flag check and the C++ ``TraceMe`` check.

``now_ns()`` is the profiler's clock: absolute wall-clock nanoseconds
(``CLOCK_REALTIME``, ~1.8e18 today), which the profiler stamps on its own
host and device events (its ``.xplane.pb`` file keeps them from the
profile's start, and that start's absolute time as the Task Environment
plane's ``profile_start_time``).  Every timestamp the recorder stores is
on it —
session spans, instants, recovery snapshots and the procs workers' phase
records (system-wide, so worker records land on the launcher's
timeline).  Waits and timeouts stay on ``time.monotonic()``.

Events follow the Chrome trace-event JSON format (loadable in Perfetto /
``chrome://tracing``): ``ph="X"`` complete spans with microsecond
``ts``/``dur``, ``ph="i"`` instants, ``ph="M"`` track-naming metadata.
One track per worker / bridge / launcher: ``pid`` groups a host process,
``tid`` is the member (worker index, ``NW+i`` for bridge ``i``, and
``TID_SESSION`` for the launcher/session track).

The recorder is process-global and bounded: past ``max_events`` new
events are dropped and counted (``trace.dropped`` in the export), never
grown — a free-running fleet can trace indefinitely.

Device scopes: the epoch program wraps each layer's ops in
``jax.named_scope`` with one flat name from ``SCOPES``; the name rides in
every op's ``op_name`` metadata, and a fused op carries its root op's.
"""
from __future__ import annotations

import atexit
import json
import os
import time

from jax.profiler import TraceAnnotation

ENV_TRACE = "REPRO_TRACE"

#: tid of the launcher/session track within a host pid.
TID_SESSION = 1000

_PH_ALLOWED = {"X", "i", "M", "C"}

# Device scopes of the epoch program (``jax.named_scope`` names).
# cycle body (``FusedEngine._cycle_body``)
READ = "sb.read"        # queue fronts, combined views, rx/tx table gathers
STEP = "sb.step"        # the vmapped block step + clock-divider masking
WRITE = "sb.write"      # inverse-map gathers, register commit, queue cycle
# tier exchange
DRAIN = "sb.drain"      # credit-bounded egress drain into the slab
PERMUTE = "sb.permute"  # batch-row moves and ppermutes, both directions
FILL = "sb.fill"        # ingress fill + the returned-credit read
# epoch glue
ROWS_SPLIT = "sb.rows_split"  # flat state -> per-row carries
ROWS_JOIN = "sb.rows_join"    # per-row carries -> flat state
DONE = "sb.done"              # run_until's predicate, after every epoch
SCOPES = (READ, STEP, WRITE, DRAIN, PERMUTE, FILL, ROWS_SPLIT, ROWS_JOIN,
          DONE)


def now_ns() -> int:
    """The profiler's clock: absolute wall-clock nanoseconds."""
    return time.time_ns()


class TraceRecorder:
    """Bounded in-memory event buffer, Chrome-trace-format export."""

    def __init__(self, max_events: int = 400_000):
        self.enabled = False
        self.max_events = int(max_events)
        self.events: list[dict] = []
        self.dropped = 0
        self._tracks: dict[tuple[int, int], str] = {}
        self._procs: dict[int, str] = {}

    # ------------------------------------------------------------ recording
    def _append(self, ev: dict) -> None:
        if len(self.events) >= self.max_events:
            self.dropped += 1
            return
        self.events.append(ev)

    def set_process(self, pid: int, name: str) -> None:
        self._procs[int(pid)] = str(name)

    def set_track(self, pid: int, tid: int, name: str) -> None:
        self._tracks[(int(pid), int(tid))] = str(name)

    def span(self, name: str, t0: int, dur: int, *, pid: int = 0,
             tid: int = TID_SESSION, cat: str = "sim",
             args: dict | None = None) -> None:
        """One complete span measured by the caller: ``t0`` and ``dur`` are
        nanoseconds on the profiler's clock (``now_ns``)."""
        if not self.enabled:
            return
        ev = {"name": name, "cat": cat, "ph": "X",
              "ts": t0 / 1e3, "dur": max(dur, 0) / 1e3,
              "pid": int(pid), "tid": int(tid)}
        if args:
            ev["args"] = args
        self._append(ev)

    def instant(self, name: str, *, pid: int = 0, tid: int = TID_SESSION,
                cat: str = "sim", args: dict | None = None) -> None:
        if not self.enabled:
            return
        ev = {"name": name, "cat": cat, "ph": "i", "s": "p",
              "ts": now_ns() / 1e3, "pid": int(pid), "tid": int(tid)}
        if args:
            ev["args"] = args
        self._append(ev)

    # -------------------------------------------------------------- export
    def to_dict(self) -> dict:
        meta: list[dict] = []
        for pid, name in sorted(self._procs.items()):
            meta.append({"name": "process_name", "ph": "M", "pid": pid,
                         "tid": 0, "args": {"name": name}})
        for (pid, tid), name in sorted(self._tracks.items()):
            meta.append({"name": "thread_name", "ph": "M", "pid": pid,
                         "tid": tid, "args": {"name": name}})
        return {
            "traceEvents": meta + self.events,
            "displayTimeUnit": "ms",
            "otherData": {"recorder": "repro.obs", "dropped": self.dropped},
        }

    def export(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_dict(), f)
            f.write("\n")
        return path

    def clear(self) -> None:
        self.events.clear()
        self.dropped = 0
        self._tracks.clear()
        self._procs.clear()


_RECORDER = TraceRecorder()
_env_armed = False


def recorder() -> TraceRecorder:
    return _RECORDER


def enabled() -> bool:
    return _RECORDER.enabled


class span:
    """The one span call: ``with span("session.dispatch"): ...``.

    Enters a ``TraceAnnotation`` of the same name while a profile is
    running, and records a complete span into the recorder while it is
    enabled (``cat``/``args`` go to the recorder only; ``args`` may be set
    on the span object inside the body).  Otherwise it costs one flag check
    and the C++ ``TraceMe`` check."""

    __slots__ = ("name", "cat", "args", "_tm", "_t0")

    def __init__(self, name: str, *, cat: str = "session",
                 args: dict | None = None):
        self.name = name
        self.cat = cat
        self.args = args

    def __enter__(self) -> "span":
        self._tm = None
        if TraceAnnotation.is_enabled():
            self._tm = TraceAnnotation(self.name)
            self._tm.__enter__()
        self._t0 = now_ns() if _RECORDER.enabled else None
        return self

    def __exit__(self, *exc) -> None:
        if self._t0 is not None:
            _RECORDER.span(self.name, self._t0, now_ns() - self._t0,
                           cat=self.cat, args=self.args)
        if self._tm is not None:
            self._tm.__exit__(*exc)


def instant(name: str, **kw) -> None:
    _RECORDER.instant(name, **kw)


def _flush_engines() -> None:
    """Pull any undrained worker telemetry into the recorder before an
    export (live procs engines hold it in their shm rings)."""
    try:
        from ..runtime.launcher import _live_engines
    except Exception:  # pragma: no cover - runtime not imported
        return
    for eng in list(_live_engines):
        try:
            flush = getattr(eng, "flush_telemetry", None)
            if flush is not None:
                flush()
        except Exception:  # pragma: no cover - stats stay best-effort
            pass


def _atexit_export() -> None:  # pragma: no cover - interpreter exit
    path = os.environ.get(ENV_TRACE)
    if path and _RECORDER.enabled and (_RECORDER.events or _RECORDER._tracks):
        _flush_engines()
        _RECORDER.export(path)


def maybe_enable_from_env() -> bool:
    """Arm the recorder from ``REPRO_TRACE=<path>`` (idempotent): enable
    now, export to the named path at interpreter exit.  Returns whether
    tracing is enabled after the call."""
    global _env_armed
    path = os.environ.get(ENV_TRACE)
    if path and not _env_armed:
        _env_armed = True
        _RECORDER.enabled = True
        atexit.register(_atexit_export)
    return _RECORDER.enabled


__all__ = [
    "DONE", "DRAIN", "ENV_TRACE", "FILL", "PERMUTE", "READ", "ROWS_JOIN",
    "ROWS_SPLIT", "SCOPES", "STEP", "TID_SESSION", "TraceRecorder", "WRITE",
    "enabled", "instant", "maybe_enable_from_env", "now_ns", "recorder",
    "span",
]
