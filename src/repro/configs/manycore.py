"""manycore — the paper's own application (§IV-B).

A 1024x1024 grid of systolic MAC cores (the PicoRV32 array's dataflow)
computing Y = A @ B for M=1024 streamed rows, distributed over the
production mesh with the epoch-batched queue engine.  This config is
exercised by launch/dryrun.py --arch manycore; it is not part of the 40
LM cells.

The sync rates are tiered (DESIGN.md §3): intra-pod (ICI) boundaries
exchange every ``k_inner`` cycles, inter-pod (DCI) boundaries every
``k_inner * k_outer`` — the paper's fast-shm/slow-TCP split.  The flat
single-K schedule is ``k_outer = 1``.  ``WAFER`` is the CPU-runnable
flagship shape consumed by ``examples/wafer_scale.py``.
"""
import dataclasses


@dataclasses.dataclass(frozen=True)
class ManycoreConfig:
    grid_rows: int = 1024
    grid_cols: int = 1024
    m_stream: int = 1024
    k_inner: int = 16          # intra-pod cycles per exchange (Fig. 15 knob)
    k_outer: int = 4           # inner rounds per inter-pod exchange
    pods: int = 2              # outer-tier (DCI) split of the grid rows
    queue_capacity: int = 62   # paper §III-B
    payload_words: int = 2

    @property
    def k_epoch(self) -> int:
        """Back-compat alias: the innermost sync rate."""
        return self.k_inner

    @property
    def pod_period(self) -> int:
        """Cycles between inter-pod synchronizations."""
        return self.k_inner * self.k_outer


CONFIG = ManycoreConfig()
SMOKE = ManycoreConfig(grid_rows=8, grid_cols=8, m_stream=8, k_inner=4,
                       k_outer=2, queue_capacity=8)
# >= 64k cores, sized to finish in minutes on host-CPU fake devices.
WAFER = ManycoreConfig(grid_rows=256, grid_cols=256, m_stream=0,
                       k_inner=8, k_outer=4, queue_capacity=8)
