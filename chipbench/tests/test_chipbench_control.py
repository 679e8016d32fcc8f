"""The control for ``correct`` comes out not correct: the wafer cell run
on the program's own bfloat16 payload path, at a tiny size on the CPU."""
import jax
import pytest

from chipbench import control, harness

TINY = {"wafer64k.allreduce": {"grid_rows": 8, "grid_cols": 8}}


@pytest.mark.parametrize("workload", sorted(TINY))
def test_control_is_not_correct(workload):
    cell = harness.Cell.load(workload)
    cell.cfg.update(TINY[workload])
    for seed, line in control.control_runs(cell, [3, 4], 0.5,
                                           jax.devices()[:1]):
        assert line["correct"] is False, (seed, line["checks"])
