"""The control for ``correct``: a cell run with the program's own lower-
precision path switched on.

    python3 chipbench/control.py --workload <cell> --seeds 1 2 3 \
        [--seconds 5]

Each seed is one run as ``run.py`` makes it (set-up, a window of
``--seconds``, the open work run to its end, the exact comparison with
the plain reference), except that the design's payload type is the
configuration's ``control_dtype`` (bfloat16 where it states float32).
Every seed's run has to come out not correct: its readings are the upper
ones that the limits of the checks sit below.  One line of JSON per seed,
``{"seed", "correct", "checks"}``.  The benchmark's own runs never run
this.
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


def control_runs(cell, seeds, seconds, devices):
    """One control run per seed; yields ``(seed, result)``."""
    for seed in seeds:
        result, _ = harness.run_cell(cell, seed, seconds, False, devices,
                                     time.perf_counter(),
                                     dtype=cell.cfg["control_dtype"])
        yield seed, result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)

    cell = harness.Cell.load(args.workload)
    from repro.core.compile_cache import enable_compile_cache

    try:
        devices = harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(e, file=sys.stderr)
        return 2
    enable_compile_cache(harness.CACHE_DIR)
    for seed, r in control_runs(cell, args.seeds, args.seconds, devices):
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "attempted": r["attempted"],
                          "checks": r["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
