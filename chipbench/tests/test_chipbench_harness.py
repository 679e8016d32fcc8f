"""Each traffic mix through the harness's own functions at a tiny size on
the CPU (an 8x8 torus): the result line has exactly the
contract's keys, the metrics BENCHMARK.json names for the cell, and a
correct comparison.  The command line refuses to run without a TPU, and
without the program beside it."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import pytest

from chipbench import harness

TOP = ["correct", "attempted", "failed", "metrics", "device", "checks"]
TINY = {"wafer64k.allreduce": {"grid_rows": 8, "grid_cols": 8}}


def tiny_cell(workload: str) -> harness.Cell:
    cell = harness.Cell.load(workload)
    cell.cfg.update(TINY[workload])
    return cell


def run(workload, trace, seed=2**31 + 7, seconds=1.0):
    cell = tiny_cell(workload)
    return cell, harness.run_cell(cell, seed, seconds, trace,
                                  jax.devices()[:1], time.perf_counter())


@pytest.fixture
def cpu_peaks(monkeypatch):
    monkeypatch.setattr(harness, "peak_for",
                        lambda kind: {"hbm_bytes_per_s": 1e11})


@pytest.mark.parametrize("workload", sorted(TINY))
def test_window_run_line(workload):
    cell, (line, notes) = run(workload, trace=False)
    assert list(line) == TOP
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert sorted(line["metrics"]) == sorted(m["name"]
                                             for m in cell.end_to_end)
    assert "setup_s" in line["metrics"]
    for m in line["metrics"].values():
        assert m["value"] > 0 and set(m) == {"value", "unit"}
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in line["checks"].values())
    assert notes["compiles_in_window"] == 0
    json.dumps(line)


@pytest.mark.parametrize("workload", sorted(TINY))
def test_traced_run_line(workload, cpu_peaks):
    cell, (line, notes) = run(workload, trace=True)
    assert list(line) == TOP[:5] + ["breakdown", "checks"]
    assert line["correct"] is True
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    # the CPU has no device planes: only the host-clock metric is read
    assert set(line["metrics"]) == {"compile_s"}
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert line["device"]["window_s"] > 0


def test_seeds_fix_the_traffic():
    cell = tiny_cell("wafer64k.allreduce")
    drv_mod = harness.load_module("drivers", "allreduce_jobs")
    ref = harness.load_module("reference", "torus_allreduce")

    class Stub:
        rows = cols = 8
        epoch_cycles = 64

        def ring_length(self):
            return 16

    a = drv_mod.Driver(Stub(), cell.traffic, ref, 2**31 + 99)
    b = drv_mod.Driver(Stub(), cell.traffic, ref, 2**31 + 99)
    c = drv_mod.Driver(Stub(), cell.traffic, ref, 2**31 + 100)
    va, vb, vc = (d._values([d.seed, 1, 0]) for d in (a, b, c))
    assert (va == vb).all() and not (va == vc).all()
    assert va.min() >= 1 and va.max() <= 97


def _cli(cwd, *extra, env=None):
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "chipbench", "run.py"),
         "--workload", "wafer64k.allreduce", "--seed", "1", "--seconds", "1",
         "--trace", "0", *extra],
        capture_output=True, text=True, timeout=300, cwd=cwd,
        env=dict(os.environ if env is None else env, JAX_PLATFORMS="cpu"))


def test_cli_refuses_without_tpu():
    out = _cli(harness.ROOT)
    assert out.returncode == 2, out.stderr[-2000:]
    assert "no TPU" in out.stderr
    assert out.stdout.strip() == ""


def test_cli_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = _cli(str(tmp_path), env=env)
    assert out.returncode not in (0, 2), out.stderr[-2000:]
    assert out.stdout.strip() == ""
