"""repro.core — the paper's primary contribution in JAX.

Switchboard's modular-simulation model (blocks + latency-insensitive
channels + SPSC queues + unsynchronized scale-out + rate-controlled
performance measurement), adapted to the TPU execution model.  See
DESIGN.md for the mechanism-by-mechanism mapping and the three-layer
architecture: Network description -> channel-graph IR + partition ->
engine backend.

  packet      SB packet layout (§III-A)
  queue       SPSC ring buffers, single-cycle + epoch bulk ops (§III-B)
  block       ready/valid Block protocol + bridge semantics (§II-A)
  network     SbNetwork analogue; build(engine=...) entry point (§III-F)
  session     Simulation facade: one reset/run/probe/tx/rx/save lifecycle
              over every engine, host TxPort/RxPort queue handles,
              monitors, checkpoints (DESIGN.md §4)
  graph       channel-graph IR + PartitionTree shared by every backend
              (DESIGN.md §1, §3)
  distributed epoch-batched shard_map GraphEngine (tiered per-tier sync
              rates, batched per-tier exchange) + GridEngine preset
  fused       fused-epoch fast path for ANY topology: depth-1 register
              channels + one compiled K-cycle epoch body (§Perf)
  perfmodel   rate control + N_meas error model (§II-C)
  fastgrid    hand-specialized systolic Pallas preset of the fused family
  pipeline    LM pipeline parallelism on the same channel semantics
  compat      jax.make_mesh (Auto axes) / jax.shard_map wrappers
"""
from .block import Block
from .network import Network, NetworkSim, NetworkState
from .graph import (
    ChannelGraph, PartitionLowering, PartitionTree, Tier, grid_partition,
    lower_partition, normalize_partition, normalize_tiers,
    tiered_grid_partition,
)
from .queue import QueueArray, make_queues, DEFAULT_CAPACITY
from .distributed import (
    GraphEngine, GraphState, GridEngine, edge_color_routes, fold_mesh,
    merge_compatible_classes, route_shift_groups,
)
from .fastgrid import RegisterGridEngine
from .fused import FusedEngine, FusedState
from .session import (
    DonatedStateError, Monitor, RxPort, Simulation, TxPort,
)
from .pipeline import Pipeline
from . import packet, perfmodel
