"""Plain reference for the torus allreduce: every core's final state.

Written from the protocol alone, with NumPy and nothing of the program.
An R x C torus of message-passing cores runs two ring phases:

  phase 0: each row is a ring (west -> east); phase 1: each column is a
  ring (north -> south).  In a ring of length L a core sends L-1 packets:
  its own contribution first, then the first L-2 values it receives, in
  arrival order; it adds each of the L-1 values it receives to its
  accumulator.  The row sum becomes the core's contribution to phase 1.

Links are lossless and in order, so the k-th value a core receives is
the one its upstream neighbour sent k-th, whatever the timing: the final
state is fixed by the values alone.  The reference steps the rings in
lock-step rounds (round k: every core receives its neighbour's k-th
packet), adding in the same order as the cores do, so the float sums are
the same bit for bit.

``dtype`` is the arithmetic the design states (float32); the control
runs the same code in the precision below it (bfloat16).
"""
from __future__ import annotations

import numpy as np

FIELDS = ("value", "own", "acc", "total", "phase", "sent", "rcvd", "fwd",
          "fwd_v", "fires")


def _ring(send, acc, fwd, length, axis):
    """One ring phase: ``length - 1`` lock-step rounds along ``axis``."""
    for k in range(1, length):
        recv = np.roll(send, 1, axis=axis)  # upstream neighbour's packet
        acc = acc + recv
        if k <= length - 2:  # every arrival but the last is forwarded
            fwd = recv
        send = recv
    return acc, fwd


def final_state(values: np.ndarray, dtype=np.float32) -> dict:
    """Every core's state once the allreduce is done, row-major (R*C,).

    ``values`` is the (R, C) grid of contributions."""
    v = np.asarray(values).astype(dtype)
    R, C = v.shape
    fwd = np.zeros_like(v)
    acc, fwd = _ring(v, v, fwd, C, axis=1)       # row sums
    acc, fwd = _ring(acc, acc, fwd, R, axis=0)   # plus every other row sum
    n = R * C
    return {
        "value": v.reshape(n),
        "own": acc.reshape(n),
        "acc": acc.reshape(n),
        "total": acc.reshape(n),
        "phase": np.full(n, 2, np.int32),
        "sent": np.zeros(n, np.int32),
        "rcvd": np.zeros(n, np.int32),
        "fwd": fwd.reshape(n),
        "fwd_v": np.zeros(n, bool),
        "fires": np.full(n, 2 * (R - 1) + 2 * (C - 1), np.int32),
    }


def mismatched_cores(got: dict, want: dict) -> int:
    """How many cores differ from the reference in any field (exact)."""
    bad = None
    for f in FIELDS:
        a = np.asarray(got[f])
        b = np.asarray(want[f])
        if a.shape != b.shape:
            return int(b.shape[0])
        diff = a.astype(np.float64) != b.astype(np.float64)
        bad = diff if bad is None else bad | diff
    return int(bad.sum())
