"""Wafer-scale many-core simulation across a tiered mesh (paper §IV-B).

The paper's flagship demo spreads a million RISC-V cores over thousands of
cloud cores with a *tiered* transport: fast shm queues inside a host, slow
TCP bridges between hosts, both tolerated by latency-insensitive channels.
This example is that scenario on the tiered GraphEngine:

  * a >= 64k-core torus of message-passing mini-cores
    (``repro.hw.manycore``) built by the vectorized ``ChannelGraph.torus``
    builder — O(cores) numpy, one vmapped step for every core;
  * hierarchically partitioned over a ``pod`` (DCI analogue) tier and an
    intra-pod granule tier via ``tiered_grid_partition``;
  * per-tier sync rates: intra-pod boundaries exchange every K_inner
    cycles, pod boundaries every K_inner * K_outer — the slow tier simply
    presents deeper elastic buffering (DESIGN.md §3);
  * end-to-end check: the fabric runs a two-phase ring-allreduce in the
    data plane, so the run is correct iff **every core's accumulator equals
    the global sum** — one equality that witnesses every packet crossing
    every tier.

The 2 x (2x2) granule layout maps onto the devices that exist
(``fold_mesh``): as many leading granule axes as fit become mesh axes and
the rest fold onto each device as batch axes — one TPU chip runs all 8
granules, 8 devices run one each.  On the CPU the script re-executes
itself with 8 simulated devices first.

    PYTHONPATH=src python examples/wafer_scale.py               # 256x256
    PYTHONPATH=src python examples/wafer_scale.py --rows 64 --cols 64
"""
from __future__ import annotations

import argparse
import os
import sys
import time

N_DEVICES = 8

import jax  # noqa: E402

# Re-exec with fake devices only on the CPU, and ONLY as the real main
# module: the procs engine's spawned workers re-import this file as
# __mp_main__ (with the device flag deliberately stripped), and re-execing
# there would fork-bomb.
if __name__ == "__main__" and "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", "") and jax.default_backend() == "cpu":
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={N_DEVICES} "
        + os.environ.get("XLA_FLAGS", "")
    ).strip()
    os.execv(sys.executable, [sys.executable] + sys.argv)

import numpy as np  # noqa: E402

from repro.configs.manycore import WAFER  # noqa: E402
from repro.core import Simulation, fold_mesh, tiered_grid_partition  # noqa: E402
from repro.core.compile_cache import enable_compile_cache  # noqa: E402
from repro.core.distributed import GraphEngine  # noqa: E402
from repro.core.graph import ChannelGraph  # noqa: E402
from repro.hw.manycore import (  # noqa: E402
    ManycoreCell, allreduce_done, expected_total, make_core_params,
)


def build_engine(R: int, C: int, k_inner: int, k_outer: int,
                 capacity: int = WAFER.queue_capacity,
                 engine: str = "graph", batch_signatures: bool = False,
                 overlap="auto", hosts=None) -> tuple[GraphEngine, np.ndarray]:
    """Torus fabric on a (2 pods) x (2x2 granules/pod) tiered layout,
    folded onto the devices that exist (``fold_mesh``) — or,
    with ``engine="procs"``, on a (2 pods) x (2 workers/pod) fleet of
    free-running OS processes over shared-memory queues (no mesh at all:
    the paper's actual deployment model, DESIGN.md §Runtime).
    ``batch_signatures`` stacks same-signature procs workers into one
    vmapped dispatch per epoch; ``overlap=True`` splits every exchange
    into issue/commit halves (send-early/receive-late, DESIGN.md §Perf) —
    bit-identical results either way.  ``hosts`` (procs only) shards the
    fleet over N cooperating launcher processes joined by loopback TCP
    ring bridges — the paper's fast-shm-inside / slow-TCP-between tiered
    transport, end to end (DESIGN.md §Multi-host fleet)."""
    values = (np.arange(R * C, dtype=np.int64) % 97 + 1).astype(np.float32)
    cell = ManycoreCell(R, C)
    graph = ChannelGraph.torus(
        cell, R, C, params=make_core_params(values.reshape(R, C)),
        capacity=capacity,
    )
    if engine == "procs":
        from repro.core.graph import PartitionTree, Tier
        from repro.runtime.launcher import ProcsEngine

        part = tiered_grid_partition(R, C, [(2, 1), (2, 1)])
        ptree = PartitionTree(
            part,
            (Tier(axes=("pod",), K=k_outer), Tier(axes=("g",), K=k_inner)),
            {"pod": 2, "g": 2},
        )
        return ProcsEngine(graph, ptree, timeout=120.0,
                           batch_signatures=batch_signatures,
                           overlap=overlap, hosts=hosts), values
    mesh, batch_axes = fold_mesh({"pod": 2, "gr": 2, "gc": 2})
    part = tiered_grid_partition(R, C, [(2, 1), (2, 2)])
    if engine == "fused":
        from repro.core.fused import FusedEngine as Engine
    else:
        Engine = GraphEngine
    eng = Engine(
        graph, part, mesh,
        tiers=[(("pod",), k_outer), ((("gr", "gc")), k_inner)],
        batch_axes=batch_axes, overlap=overlap,
    )
    return eng, values


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=WAFER.grid_rows)
    ap.add_argument("--cols", type=int, default=WAFER.grid_cols)
    ap.add_argument("--k-inner", type=int, default=WAFER.k_inner)
    ap.add_argument("--k-outer", type=int, default=WAFER.k_outer)
    ap.add_argument("--engine", choices=("graph", "fused", "procs"),
                    default="graph",
                    help="queue interpreter, the fused-epoch fast path, or "
                         "the free-running multiprocess runtime (identical "
                         "results; see DESIGN.md §Perf / §Runtime)")
    ap.add_argument("--batch-signatures", action="store_true",
                    help="procs only: stack same-signature workers into one "
                         "vmapped dispatch per epoch (ISSUE 6)")
    ap.add_argument("--overlap", action="store_true",
                    help="split every tier exchange into issue/commit halves "
                         "(send-early/receive-late; bit-identical results, "
                         "transfers hidden under the next window's compute)")
    ap.add_argument("--hosts", type=int, default=None,
                    help="procs only: shard the fleet over N cooperating "
                         "launcher processes joined by loopback TCP ring "
                         "bridges (ISSUE 9; bit-identical results)")
    args = ap.parse_args()
    if args.hosts and args.engine != "procs":
        ap.error("--hosts requires --engine procs")
    R, C = args.rows, args.cols
    enable_compile_cache()

    print(f"wafer-scale fabric: {R}x{C} torus = {R * C} cores, "
          f"{len(jax.devices())} devices, engine={args.engine}")
    eng, values = build_engine(R, C, args.k_inner, args.k_outer,
                               engine=args.engine,
                               batch_signatures=args.batch_signatures,
                               overlap=True if args.overlap else "auto",
                               hosts=args.hosts)
    periods = eng.periods
    plan = getattr(eng, "host_plan", None)
    if plan is not None:
        print(f"  host mesh: {plan.n_hosts} launcher processes "
              f"{plan.hosts}, {len(eng._links)} TCP ring bridge link(s), "
              f"granules {dict((h, plan.granules_of(h)) for h in plan.hosts)}")
    print(f"  partition: {eng.ptree.summary()}")
    if hasattr(eng, "classes"):
        print(f"  exchange classes/tier: "
              f"{[sum(1 for c in eng.classes if c.tier == t) for t in range(len(eng.tiers))]}, "
              f"sync periods {periods} cycles (pod tier {periods[0] // periods[-1]}x "
              f"rarer than intra-pod)")
    else:
        n_bnd = sum(len(cs) for cs in eng.lowering.routes.values())
        print(f"  {eng.n_workers} free-running workers, {n_bnd} boundary "
              f"channels over shm rings, sync periods {periods} cycles "
              f"({eng.build_stats['n_signatures']} prebuilt granule "
              f"signature(s) for {eng.n_workers} workers)")

    t0 = time.perf_counter()
    sim = Simulation(eng).reset(jax.random.key(0))
    done = lambda s: allreduce_done(s.block_states[0], s.tables.active[0])  # noqa: E731
    sim.run(until=done, max_epochs=100_000, cache_key="allreduce")
    sim.block_until_ready()
    wall = time.perf_counter() - t0

    totals = np.asarray(eng.gather_group(sim.state, 0).total)
    want = expected_total(values)
    assert np.array_equal(totals, np.full_like(totals, want)), (
        f"allreduce mismatch: {np.unique(totals)[:5]} != {want}"
    )
    cycles = sim.cycle
    print(f"  all {R * C} cores converged to the global sum {want:.0f}")
    print(f"  {cycles} simulated cycles in {wall:.2f}s wall "
          f"(incl. compile) = {R * C * cycles / wall:.3e} core-cycles/s")
    print("OK — tiered exchange delivered every packet across both tiers")


if __name__ == "__main__":
    main()
