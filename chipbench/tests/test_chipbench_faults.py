"""The comparison that decides ``correct`` catches a broken timed path on
the wafer.

Each test skips the look for a chip and drives the rest of a run of the
wafer cell at 8x8 on the CPU, with one fault planted in the program
underneath, and sees ``correct`` come out false: a step that returns its
state unchanged, half of the batch (the folded granule rows) left out,
the exchange between granules left out, and an answer altered where it
is produced."""
import time

import jax
import jax.numpy as jnp

from chipbench import harness
from repro.core.distributed import GraphEngine
from repro.core.fused import FusedEngine
from repro.hw.manycore import ManycoreCell

TINY = {"grid_rows": 8, "grid_cols": 8}


def run(seconds=0.5):
    cell = harness.Cell.load("wafer64k.allreduce")
    cell.cfg.update(TINY)
    line, _ = harness.run_cell(cell, 11, seconds, False, jax.devices()[:1],
                               time.perf_counter())
    return line


def _unchanged(self, state, *args, **kw):
    return state


def _altered_core_step(orig):
    def step(self, state, rx, tx_ready):
        new, rx_ready, tx = orig(self, state, rx, tx_ready)
        pay, valid = tx["e_out"]
        pay = pay.at[0].add(jnp.where(state.sent == 3, 1.0, 0.0))
        return new, rx_ready, dict(tx, e_out=(pay, valid))
    return step


def _half_rows(orig):
    def rows_program(self, rows, credits, tb, t0):
        new, credits = orig(self, rows, credits, tb, t0)
        h = len(rows) // 2
        return tuple(new[:h]) + tuple(rows[h:]), credits
    return rows_program


def test_sound_wafer_is_correct():
    assert run()["correct"] is True


def test_wafer_step_returns_state_unchanged(monkeypatch):
    monkeypatch.setattr(FusedEngine, "run_until", _unchanged)
    line = run()
    assert line["correct"] is False
    assert line["checks"]["cores_mismatched"]["value"] > 0


def test_wafer_half_the_batch_left_out(monkeypatch):
    monkeypatch.setattr(FusedEngine, "_rows_program",
                        _half_rows(FusedEngine._rows_program))
    line = run()
    assert line["correct"] is False and line["failed"] >= 1


def test_wafer_exchange_between_granules_left_out(monkeypatch):
    monkeypatch.setattr(GraphEngine, "_bat_move",
                        lambda self, x, *a, **k: jnp.zeros_like(x))
    line = run()
    assert line["correct"] is False
    assert line["checks"]["jobs_unfinished"]["value"] >= 1


def test_wafer_answer_altered_where_produced(monkeypatch):
    monkeypatch.setattr(ManycoreCell, "step",
                        _altered_core_step(ManycoreCell.step))
    line = run()
    assert line["correct"] is False
    assert line["checks"]["cores_mismatched"]["value"] > 0
