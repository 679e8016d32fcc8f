"""Allreduce jobs run back to back on a torus design (driver of traffic
mixes whose ``driver`` is ``allreduce_jobs``).

Job ``j`` of a run with seed ``s`` starts from ``reset`` with every core's
value drawn uniformly from ``[value_low, value_high]`` by
``numpy.random.default_rng([s, 1, j])``; set-up warms up on the values of
``default_rng([s, 0])``.  The job advances by ``Simulation.run(until=
done, max_epochs=chunk_epochs)`` calls, so a window can end between
chunks.  A job still running when the window closes is run to its end
afterwards; a job that passes ``budget_cycles_per_ring_length * (R + C)``
cycles is abandoned, and checked as it stands.  Every job's final state
is compared with the plain reference once the window has closed.
"""
from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation


class _OverBudget(Exception):
    """The current job passed its cycle budget."""


class Driver:
    unit = "cycles"  # what ``window`` counts, for the per-layer readers
    spans = ("reset", "run_chunk", "read_cycle")  # host spans, for idle gaps

    def __init__(self, design, traffic: dict, reference, seed: int):
        self.d = design
        self.ref = reference
        self.seed = int(seed)
        self.lo = int(traffic["value_low"])
        self.hi = int(traffic["value_high"])
        self.chunk = int(traffic["chunk_epochs"])
        self.trace_chunks = int(traffic["trace_chunks"])
        self.budget = (int(traffic["budget_cycles_per_ring_length"])
                       * design.ring_length())
        self.chunk_cycles = self.chunk * design.epoch_cycles
        self.jobs: list[dict] = []
        self.failed = 0
        self._job_cycles = 0
        self._cycle = 0
        self._chunks_in_job = 0
        self._timed: list[tuple] = []  # (what, job, chunk of job, seconds)

    def _values(self, key) -> np.ndarray:
        rng = np.random.default_rng(key)
        return rng.integers(self.lo, self.hi + 1,
                            size=(self.d.rows, self.d.cols)).astype(np.float32)

    def _start_job(self) -> None:
        values = self._values([self.seed, 1, len(self.jobs)])
        with TraceAnnotation("reset"):
            self.d.reset(values)
        self.jobs.append({"values": values, "done": False, "state": None,
                          "closed": False})
        self._job_cycles = self._cycle = 0
        self._chunks_in_job = 0

    def _timed_call(self, what: str, fn):
        """``fn()``, its wall time kept for the notes (window only)."""
        t0 = time.perf_counter()
        out = fn()
        self._timed.append((what, len(self.jobs) - 1, self._chunks_in_job,
                            time.perf_counter() - t0))
        return out

    def _chunk(self) -> int:
        """One chunk of the open job; returns the cycles it advanced.  A
        job that has ended keeps its final state for the check."""
        with TraceAnnotation("run_chunk"):
            self.d.advance(self.chunk)
        with TraceAnnotation("read_cycle"):
            cyc = self.d.cycle
        step, self._cycle = cyc - self._cycle, cyc
        self._job_cycles += step
        self._chunks_in_job += 1
        job = self.jobs[-1]
        if step < self.chunk_cycles:  # run_until stopped early: done
            job.update(done=True, closed=True, state=self.d.hold(),
                       cycles=self._job_cycles)
        elif self._job_cycles > self.budget:
            job.update(closed=True, state=self.d.hold())
            raise _OverBudget
        return step

    def _open(self) -> bool:
        return bool(self.jobs) and not self.jobs[-1]["closed"]

    def warm_up(self) -> None:
        """Compile or load every program the window runs: the reset, one
        chunk and the cycle read."""
        self.d.reset(self._values([self.seed, 0]))
        self.d.advance(self.chunk)
        _ = self.d.cycle

    def window(self, seconds: float | None = None,
               chunks: int | None = None) -> dict:
        """Jobs back to back until ``seconds`` have passed (or ``chunks``
        chunks have run), ending between chunks."""
        cycles = n = 0
        t0 = time.perf_counter()
        try:
            if not self._open():
                self._timed_call("reset", self._start_job)
            while True:
                cycles += self._timed_call("chunk", self._chunk)
                n += 1
                if chunks is not None and n >= chunks:
                    break
                if seconds is not None and time.perf_counter() - t0 >= seconds:
                    break
                if not self._open():
                    self._timed_call("reset", self._start_job)
        except _OverBudget:
            pass
        self.d.sim.block_until_ready()
        wall = time.perf_counter() - t0
        return {"count": cycles, "wall_s": wall,
                "core_cycles_per_s": cycles * self.d.cores / wall}

    def prepare_trace(self) -> None:
        """Start the job the traced window runs in, outside the trace: the
        traced chunks are steady stepping, without the per-job reset."""
        self._start_job()

    def trace_window(self) -> dict:
        """The short window the profiler traces."""
        return self.window(chunks=self.trace_chunks)

    def finish(self) -> None:
        """Run the job still open at the window's close to its end."""
        try:
            while self._open():
                self._chunk()
        except _OverBudget:
            pass

    def collect(self) -> None:
        """Read every job's final state to the host; drop the device
        copies."""
        for job in self.jobs:
            st, job["state"] = job["state"], None
            job["cores"] = None if st is None else self.d.read_cores(st)

    def check(self) -> dict:
        """Exact comparison with the plain reference: cores whose final
        state differs in any field, over every job, and jobs that did not
        finish."""
        bad = unfinished = 0
        self.failed = 0
        for job in self.jobs:
            want = self.ref.final_state(job["values"])
            got = job["cores"]
            n = (len(want["value"]) if got is None
                 else self.ref.mismatched_cores(got, want))
            unfinished += not job["done"]
            bad += n
            self.failed += bool(n) or not job["done"]
        return {"cores_mismatched": (bad, 0),
                "jobs_unfinished": (unfinished, 0)}

    @property
    def attempted(self) -> int:
        return len(self.jobs)

    @property
    def notes(self) -> dict:
        """For standard error: the cycles each finished job took."""
        chunks = sorted(t[3] for t in self._timed if t[0] == "chunk")
        slow = sorted(self._timed, key=lambda t: -t[3])[:6]
        return {"job_cycles": [j.get("cycles") for j in self.jobs],
                "window_chunks": len(chunks),
                "chunk_s_median": chunks[len(chunks) // 2] if chunks else None,
                "slowest_calls": [list(t) for t in slow]}
