"""The wafer: an R x C torus of ``ManycoreCell`` cores on ``engine="fused"``.

``build`` lays the configuration's granules onto the devices it is given
(``fold_mesh``: leading axes on the mesh, the rest folded as batch rows)
and returns a ``Torus`` session that the ``allreduce_jobs`` driver runs.
``bytes_per_cycle`` is the design's least memory traffic per simulated
cycle, counted from the design's shapes alone (the roofline numerator).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    ChannelGraph, FusedEngine, Simulation, fold_mesh, tiered_grid_partition,
)
from repro.hw.manycore import ManycoreCell, allreduce_done, make_core_params

# Bytes the design holds per core: ManycoreCell's state is five float32
# words (value, own, acc, total, fwd), four int32 counters (phase, sent,
# rcvd, fires) and one bool (fwd_v); its parameter is one float32.
STATE_BYTES = 5 * 4 + 4 * 4 + 1
PARAM_BYTES = 4
OUT_CHANNELS = 2  # east and south
WORD_BYTES = 4    # float32 payload words


def _done(s):
    return allreduce_done(s.block_states[0], s.tables.active[0])


class Torus:
    """One wafer session: reset to a grid of values, advance in chunks,
    read every core's state."""

    def __init__(self, cfg: dict, devices, dtype=None):
        R, C = int(cfg["grid_rows"]), int(cfg["grid_cols"])
        lay = cfg["layout"]
        axes = {str(a): int(n) for a, n in lay["axes"].items()}
        tiles = [tuple(t) for t in lay["tiles"]]
        graph = ChannelGraph.torus(
            ManycoreCell(R, C), R, C,
            params=make_core_params(np.zeros((R, C), np.float32)),
            capacity=int(cfg["queue_capacity"]),
            dtype=jnp.dtype(dtype or cfg["dtype"]),
        )
        mesh, batch = fold_mesh(axes, devices)
        k = (int(cfg["k_outer"]), int(cfg["k_inner"]))
        tiers = [(tuple(ax), kt) for ax, kt in zip(lay["tier_axes"], k)]
        self.engine = FusedEngine(
            graph, tiered_grid_partition(R, C, tiles), mesh, tiers=tiers,
            batch_axes=batch,
        )
        self.sim = Simulation(self.engine)
        self.rows, self.cols = R, C
        self.cores = R * C
        self.epoch_cycles = int(self.engine.cycles_per_epoch)

    def reset(self, values: np.ndarray) -> None:
        self.sim.reset(jax.random.key(0),
                       group_params={0: make_core_params(values)})

    def advance(self, max_epochs: int) -> None:
        """Run until every core is done, or ``max_epochs`` more epochs."""
        self.sim.run(until=_done, max_epochs=max_epochs,
                     cache_key="chipbench.torus_allreduce.done")

    @property
    def cycle(self) -> int:
        return self.sim.cycle

    def hold(self):
        """The live state, kept for reading after the window (the next
        ``reset`` replaces it without donating it)."""
        return self.sim.state

    def read_cores(self, state) -> dict:
        """Every core's state from a held state, in row-major core order."""
        st = self.engine.gather_group(state, 0)
        return {f: np.asarray(getattr(st, f)) for f in st.__dataclass_fields__}

    def ring_length(self) -> int:
        return self.rows + self.cols


def build(cfg: dict, devices, dtype=None) -> Torus:
    return Torus(cfg, devices, dtype)


def _block_ids(R: int, C: int, tiles) -> list[np.ndarray]:
    """Per tier level, the (R*C,) index of the block holding each core
    under the configuration's nested tiling (outermost first)."""
    rr, cc = np.divmod(np.arange(R * C, dtype=np.int64), C)
    ids, gid, br, bc = [], np.zeros(R * C, np.int64), R, C
    for tr, tc in tiles:
        br, bc = br // tr, bc // tc
        gid = gid * (tr * tc) + ((rr // br) % tr) * tc + (cc // bc) % tc
        ids.append(gid.copy())
    return ids


def boundary_channels(cfg: dict) -> list[int]:
    """Per tier (outermost first), the channels whose two ends lie in
    different blocks of that tier but in the same block of every outer
    one: the channels that tier's exchange carries."""
    R, C = int(cfg["grid_rows"]), int(cfg["grid_cols"])
    tiles = [tuple(t) for t in cfg["layout"]["tiles"]]
    ids = _block_ids(R, C, tiles)
    rr, cc = np.divmod(np.arange(R * C, dtype=np.int64), C)
    east = rr * C + (cc + 1) % C
    south = ((rr + 1) % R) * C + cc
    counts = [0] * len(tiles)
    for dst in (east, south):
        decided = np.zeros(R * C, bool)
        for t, gid in enumerate(ids):
            cross = (gid != gid[dst]) & ~decided
            counts[t] += int(cross.sum())
            decided |= cross
    return counts


def bytes_per_cycle(cfg: dict) -> float:
    """Least bytes one simulated cycle must move through memory.

    Resident lower bound: every core's state and parameter, and one
    packet register (payload words + valid flag) per channel, read and
    written once per innermost epoch of ``k_inner`` cycles; plus, at
    each tier's exchange, one slab per boundary channel, read once and
    written once: ``min(period, capacity - 1)`` packets, a count and a
    returned credit (int32 each)."""
    R, C = int(cfg["grid_rows"]), int(cfg["grid_cols"])
    W, cap = int(cfg["payload_words"]), int(cfg["queue_capacity"])
    k_inner, k_outer = int(cfg["k_inner"]), int(cfg["k_outer"])
    register = W * WORD_BYTES + 1
    per_core = 2 * STATE_BYTES + PARAM_BYTES + 2 * OUT_CHANNELS * register
    total = R * C * per_core / k_inner
    periods = (k_inner * k_outer, k_inner)
    for n, period in zip(boundary_channels(cfg), periods):
        slab = min(period, cap - 1) * W * WORD_BYTES + 4 + 4
        total += n * 2 * slab / period
    return float(total)
