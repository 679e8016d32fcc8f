"""Flight recorder (ISSUE 10; DESIGN.md §Observability).

One observability spine shared by all five engines:

  * ``registry`` — process-global metrics registry (counters / gauges /
    histograms under stable dotted names; near-zero-cost when disabled);
  * ``trace`` — bounded structured trace buffers (span / instant events)
    exported as Chrome/Perfetto ``trace.json``, driven by
    ``Simulation.trace(path)`` or the ``REPRO_TRACE`` env knob; the one
    ``span`` call on the profiler's clock, and the ``sb.*`` device scope
    names of the epoch program;
  * ``telemetry`` — the per-worker shm telemetry ring: fixed-size phase
    records the procs workers publish and the launcher drains (same SPSC
    machinery as ``runtime/shmem.py``; the credit rings stay untouched);
  * ``schema`` — the ONE validated ``Simulation.stats()`` schema every
    engine shares, plus the Perfetto trace-format validator (CLI:
    ``python -m repro.obs.schema trace.json``);
  * ``report`` — ``python -m repro.obs.report trace.json``: top stalls,
    straggler ranking, per-phase breakdown from a trace file.
"""
from . import registry, schema, telemetry, trace  # noqa: F401
from .registry import REGISTRY, MetricsRegistry  # noqa: F401
from .trace import TraceRecorder  # noqa: F401

__all__ = [
    "REGISTRY", "MetricsRegistry", "TraceRecorder",
    "registry", "schema", "telemetry", "trace",
]
