"""The benchmark's general part: finds a cell's files by the names in
``BENCHMARK.json``, runs set-up, the window (or the traced window), the
rest of the open work and the check, and builds the result line.

Everything that belongs to one configuration, traffic mix or metric sits
in a file of its own under ``chipbench/`` and is found by name:

  configs/<config>.json     the design's sizes, layout, source and cuts;
                            ``design`` and ``reference`` name the files below
  designs/<design>.py       ``build(cfg, devices, dtype=None)`` -> session;
                            optional ``bytes_per_cycle(cfg)``
  reference/<reference>.py  the plain reference (NumPy, nothing of the
                            program)
  traffic/<traffic>.json    the mix's parameters; ``driver`` names
  drivers/<driver>.py       ``Driver(design, traffic, reference, seed)``:
                            warm_up, window, prepare_trace, trace_window,
                            finish, collect, check; ``spans`` names the
                            host spans it puts around its calls; optional
                            ``notes`` for standard error
  metrics/<metric>.py       ``read(run)`` -> number, or None when there is
                            nothing to read
  peaks.json                peaks keyed by ``device_kind``
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import re
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# Where JAX's persistent compilation cache lives: a fixed directory inside
# the checkout, so every run after a cell's first one loads its programs.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` (names may hold dots)."""
    path = os.path.join(BENCH, kind, name + ".py")
    mod_name = f"chipbench.{kind}.{re.sub(r'[^0-9A-Za-z_]', '_', name)}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def _applies(entry: dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


class Cell:
    """One ``workloads`` entry of ``BENCHMARK.json`` with its files."""

    def __init__(self, bench: dict, workload: str):
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
        self.name = workload
        self.spec = cells[workload]
        cfg_entry = {c["name"]: c for c in bench["configs"]}[
            self.spec["config"]]
        self.cfg = read_json(os.path.join(ROOT, cfg_entry["file"]))
        self.traffic = read_json(os.path.join(
            BENCH, "traffic", self.spec["traffic"] + ".json"))
        self.chips = int(self.spec["chips"])
        self.end_to_end = [m for m in bench["end_to_end"]
                           if _applies(m, workload)]
        self.per_layer = [m for m in bench["per_layer"]
                          if _applies(m, workload)]

    @classmethod
    def load(cls, workload: str) -> "Cell":
        return cls(read_json(os.path.join(ROOT, "BENCHMARK.json")), workload)


def require_chips(n: int):
    """The first ``n`` TPU devices, or ``NoChip``."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX sees platform {devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devs)}")
    return devs[:n]


def peak_for(kind: str) -> dict:
    table = read_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


class CompileClock:
    """Seconds JAX reports for tracing, lowering and compiling (a load
    from the persistent cache counts as the compile it replaces)."""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.compiles = 0
        self._mon = jax.monitoring
        self._mon.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name in _COMPILE_EVENTS:
            self.seconds += secs
            self.compiles += name.endswith("backend_compile_duration")

    def close(self):
        self._mon.unregister_event_duration_listener(self._on)


class TraceRun:
    """What a per-layer reader gets: the traced window's events and
    counts, and the cell's constants."""

    def __init__(self, lines, unit, count, chips, peak, bytes_per_cycle,
                 compile_s):
        self.lines = lines
        self.unit = unit
        self.count = count
        self.chips = chips
        self.peak = peak
        self.bytes_per_cycle = bytes_per_cycle
        self.compile_s = compile_s


def _peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest device (None where the backend
    keeps no count, as the CPU)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float, dtype=None) -> dict:
    """Set-up, window, the rest of the open work, the check.  Returns the
    result line as a dict (``checks`` last) and notes for standard error.
    ``dtype`` overrides the design's payload type (the control)."""
    from chipbench import tracing
    from chipbench.metrics import reduce

    clock = CompileClock()
    try:
        t_enter = time.perf_counter()
        design_mod = load_module("designs", cell.cfg["design"])
        ref = load_module("reference", cell.cfg["reference"])
        drv_mod = load_module("drivers", cell.traffic["driver"])
        design = design_mod.build(cell.cfg, devices, dtype)
        drv = drv_mod.Driver(design, cell.traffic, ref, seed)
        t_built = time.perf_counter()
        drv.warm_up()
        setup_s = time.perf_counter() - t_start
        compile_s, compiles_setup = clock.seconds, clock.compiles
        split = {"start_s": t_enter - t_start, "build_s": t_built - t_enter,
                 "warm_up_s": t_start + setup_s - t_built}

        if trace:
            drv.prepare_trace()
            out, lines, trace_notes = tracing.capture(drv.trace_window)
        else:
            out, trace_notes = drv.window(seconds), {}
        compiles_window = clock.compiles - compiles_setup
        drv.finish()
    finally:
        clock.close()
    memory = _peak_bytes(devices)
    drv.collect()
    del design, drv.d
    gc.collect()
    checks = drv.check()

    kind = devices[0].device_kind
    import jax

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory}
    metrics, extra = {}, {}
    if trace:
        t0 = time.perf_counter()
        bpc = getattr(design_mod, "bytes_per_cycle", None)
        run = TraceRun(lines, drv.unit, out["count"], len(devices),
                       peak_for(kind), None if bpc is None else bpc(cell.cfg),
                       compile_s)
        for m in cell.per_layer:
            v = load_module("metrics", m["name"]).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device["busy_s"] = reduce.busy_s(lines)
        device["window_s"] = reduce.window_s(lines)
        extra["breakdown"] = {
            "device_ops": reduce.top_ops(lines),
            "idle_gaps": reduce.idle_gaps(lines, drv.spans),
        }
        trace_notes["reduce_s"] = time.perf_counter() - t0
    else:
        have = dict(out, setup_s=setup_s)
        for m in cell.end_to_end:
            if m["name"] not in have:
                raise KeyError(f"the driver gave no {m['name']!r}")
            metrics[m["name"]] = {"value": float(have[m["name"]]),
                                  "unit": m["unit"]}
    notes = {"window_count": out["count"], "window_wall_s": out["wall_s"],
             "compile_s": compile_s, "compiles_in_window": compiles_window,
             **split, **trace_notes, **getattr(drv, "notes", {})}
    result = {
        "correct": all(v <= lim for v, lim in checks.values()),
        "attempted": drv.attempted,
        "failed": drv.failed,
        "metrics": metrics,
        "device": device,
        **extra,
        "checks": {k: {"value": v, "limit": lim}
                   for k, (v, lim) in checks.items()},
    }
    return result, notes
