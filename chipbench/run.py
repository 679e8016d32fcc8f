"""Run one cell of the on-chip benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is a ``workloads`` entry of ``BENCHMARK.json``.  Set-up (imports,
device start, build, compile or cache load, warm-up) is timed from the
first line of this file; then the window runs for ``--seconds`` with the
profiler off (``--trace 0``, the end-to-end metrics), or a short window
runs under the profiler (``--trace 1``, the per-layer metrics).  Work
still open at the window's close is run to its end, and everything the
window produced is compared with the plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``
``breakdown``), and last ``checks``, each number compared beside its
limit; the same numbers are the last lines of standard error.  Without a
TPU, or with fewer chips than the cell asks for, the run exits with
code 2 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.Cell.load(args.workload)
    from repro.core.compile_cache import enable_compile_cache

    try:
        devices = harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(e, file=sys.stderr)
        return 2
    harness.peak_for(devices[0].device_kind)  # an unknown chip is an error
    enable_compile_cache(harness.CACHE_DIR)
    result, notes = harness.run_cell(cell, args.seed, args.seconds,
                                     bool(args.trace), devices, T_START)
    line = json.dumps(result)
    print("notes " + json.dumps(notes), file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
