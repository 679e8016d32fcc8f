"""Shared benchmark utilities."""
import os
import subprocess
import sys
import time

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
sys.path.insert(0, SRC)

# Rows recorded by ``emit`` since the last ``begin_suite``, keyed by suite —
# ``benchmarks.run`` serializes this to BENCH_PR2.json so the perf
# trajectory is machine-readable PR over PR.
_RECORDS: dict[str, list[dict]] = {}
_CURRENT_SUITE: str | None = None


def begin_suite(name: str) -> None:
    global _CURRENT_SUITE
    _CURRENT_SUITE = name
    _RECORDS.setdefault(name, [])


def records() -> dict[str, list[dict]]:
    return _RECORDS


def timeit(fn, *args, n: int = 5, warmup: int = 2):
    """Median wall time of fn(*args) over n runs (after warmup)."""
    for _ in range(warmup):
        fn(*args)
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn(*args)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def run_subprocess(code: str, devices: int = 4, timeout: int = 600) -> str:
    """Run ``code`` in a child on ``devices`` simulated CPU devices.  The
    child is pinned to the CPU: the parent has imported JAX and, on a TPU
    host, holds the chip, which a second process cannot share."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=timeout,
    )
    if out.returncode != 0:
        raise RuntimeError(f"subprocess failed:\n{out.stdout}\n{out.stderr}")
    return out.stdout


def emit(name: str, us_per_call: float, derived: str) -> None:
    print(f"{name},{us_per_call:.2f},{derived}", flush=True)
    if _CURRENT_SUITE is not None:
        _RECORDS[_CURRENT_SUITE].append(
            {"name": name, "us_per_call": round(float(us_per_call), 2),
             "derived": derived}
        )
