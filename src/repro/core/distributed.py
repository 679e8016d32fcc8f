"""Distributed epoch-batched simulation of a partitioned channel graph
(paper §II, §IV-B; DESIGN.md §2-§3).

This is the TPU-native adaptation of Switchboard's scale-out story,
generalized from a uniform grid to **any** topology the channel-graph IR
(``repro.core.graph``) can describe.  A **hierarchical partition**
(``graph.PartitionTree``) assigns every block instance to a *granule* (the
paper's network-of-networks node, here one device of a mesh) and groups the
granule axes into **tiers** — fast intra-pod ICI axes, the slow inter-pod
DCI axis — each with its own sync rate.  Each granule advances cycles of
pure local simulation (a ``lax.scan`` touching only granule-local state)
and exchanges the contents of boundary queues with its peers via
``lax.ppermute`` inside ``shard_map``:

    paper                      | here
    ---------------------------+---------------------------------
    single-netlist granule     | device, vmapped per-group step
    shm queue between granules | egress queue -> ppermute slab -> ingress
    free-running processes     | K-cycle epochs (bounded staleness)
    TCP bridge between hosts   | outer (slow) tier of the same ppermute,
                               | synchronized every K_outer * K_inner cycles
    ready/valid backpressure   | credit return on the reverse ppermute

**Tiered sync** (the paper's scale-out economics, §II-B/§IV): a boundary
channel is classified by the *outermost* tier it crosses.  The epoch loop
is nested — one epoch = ``K_0`` rounds of tier 1, each ``K_1`` rounds of
tier 2, ..., the innermost tier running ``K_inner`` granule-local cycles —
and tier t's exchange fires once per tier-t round, i.e. every
``prod(K_t .. K_inner)`` local cycles (its *period*).  Slow-tier channels
simply present deeper elastic buffering; the flat single-K engine is the
one-tier special case.

Functional correctness is *independent of every tier's K* for handshaked
dataflow because every cross-granule channel is latency-insensitive — the
exchange cadence only adds latency, which the channels tolerate by
construction.  This is property-tested against the single-netlist ground
truth (``tests/test_graph.py``, ``tests/test_tiered.py``); with every
K = 1 the exchanges run each cycle and the distributed simulation is
additionally *cycle-accurate*.

Arbitrary granule adjacency: each tier's boundary channels are grouped
into **routes** (one per directed granule pair) and routes are edge-colored
into **exchange classes**, each a partial permutation (every granule sends
on at most one route and receives on at most one route per class).  One
``ppermute`` moves a whole class's packet slabs.  The coloring uses the
König construction (regularize to a Δ-regular bipartite multigraph, peel
off Δ perfect matchings), so the class count *equals* the maximum granule
in/out-degree of the tier — property-tested in ``tests/test_tiered.py``.
``merge_compatible_classes`` then guards the invariant that the class
count never exceeds the number of distinct granule shifts of the tier (a
fixed coordinate delta is injective, hence one ``ppermute``) — a no-op on
König's optimal output, load-bearing for any other decomposition fed
through the table builder.  A
nearest-neighbor grid needs exactly two classes (east, south) — the
historical ``GridEngine`` schedule falls out as a special case, and
``GridEngine`` below is now just a partition-map preset over
``GraphEngine``.

**Batched tier exchange** (§Perf): a tier's classes are concatenated into
one ``(slots, E_t, W)`` slab table at build time, so an exchange is ONE
bulk ``drain`` of every egress queue in the tier, one ``ppermute`` per
remaining class (= per distinct shift), and ONE bulk ``fill`` of every
ingress queue — instead of a drain/permute/fill/credit chain per class.
Credits are carried per tier over the same concatenated slot axis.  Since
every egress/ingress queue belongs to exactly one channel of exactly one
class, the batched schedule is bit-identical to the per-class chain.
``run_epochs``/``run_until`` donate the engine state into the compiled
loop (``jax.jit(..., donate_argnums=0)``), so an epoch updates the wafer
state in place instead of copying it through HBM.

Credit protocol (DESIGN.md §3): the receiver of a boundary channel
advertises ``free(ingress)`` after each fill; the sender drains at most
that many packets at its tier's next exchange.  Safety: only the sender
fills the ingress queue, so the advertised credit can only be consumed by
the sender's own future sends.
"""
from __future__ import annotations

import warnings
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import queue as qmod
from ..obs import trace as _trace
from ..obs.registry import REGISTRY
from .block import Block
from .compat import make_mesh, shard_map
from .graph import (
    ChannelGraph, PartitionTree, Tier, _rank_within, grid_partition,
    lower_partition, normalize_partition, normalize_tiers,
)
from .struct import pytree_dataclass, static_field
from ..kernels import granule_step

PyTree = Any


@pytree_dataclass
class GraphTables:
    """Per-granule lookup tables (device-varying, constant over time).

    All leaves carry the leading device dims; index values are *local*
    queue ids (0 = NULL_RX sentinel, 1 = NULL_TX sentinel).  The exchange
    tables are concatenated per *tier* (batched exchange): slot ``j`` of
    tier ``t`` belongs to the class whose ``[col0, col0+cmax)`` column
    window contains ``j``.
    """

    rx_idx: tuple  # per group: (dev..., n_slot, n_in) int32
    tx_idx: tuple  # per group: (dev..., n_slot, n_out) int32
    active: tuple  # per group: (dev..., n_slot) bool — padding slots False
    send_idx: tuple  # per tier: (dev..., S_t) int32 local egress queue ids
    send_mask: tuple  # per tier: (dev..., S_t) bool
    recv_idx: tuple  # per tier: (dev..., S_t) int32 local ingress queue ids
    recv_mask: tuple  # per tier: (dev..., S_t) bool
    # Signature-batched exchange (PR 6, ``batch_axes``): per tier, the
    # batch-row gather maps of the on-device slab move.  Empty when the
    # engine runs unbatched.  ``bat_fwd[t][dev..., bd, col] = bs`` — on the
    # *source* device, send-buffer row ``bd`` (the receiver's batch row)
    # reads slab row ``bs``; ``bat_rev[t][dev..., bs, col] = bd`` — on the
    # *dest* device, the credit-return row ``bs`` reads credit row ``bd``.
    # 0-padded; garbage rows are killed by the send/recv masks downstream.
    bat_fwd: tuple = ()
    bat_rev: tuple = ()


@pytree_dataclass
class GraphState:
    """All leaves carry leading device dims, sharded over the granule axes."""

    queues: qmod.QueueArray  # (dev..., n_local, ...) granule-local queues
    block_states: tuple  # per group: leaves (dev..., n_slot, ...)
    credits: tuple  # per tier: (dev..., S_t) int32 send credits
    cycle: jax.Array  # (dev...,) int32 local cycle counters
    epoch: jax.Array  # (dev...,) int32
    tables: GraphTables


@pytree_dataclass
class _ExchangeClass:
    """One partial permutation of boundary routes (static aux data)."""

    perm: tuple = static_field(default=())  # ((src_granule, dst_granule), ...)
    cmax: int = static_field(default=0)  # max channels on any route
    tier: int = static_field(default=0)  # which tier's exchange runs this class
    depth: int = static_field(default=1)  # slab depth E = min(period, cap-1)
    col0: int = static_field(default=0)  # column offset in the tier slab
    # batched engines only: the deduped ((src_device, dst_device), ...)
    # ppermute over the *real* mesh axes; () = the whole class moves
    # between batch rows of one device (no collective at all).  None on
    # unbatched engines (where ``perm`` itself is the ppermute).
    real_perm: tuple | None = static_field(default=None)


def fold_mesh(axis_sizes: dict[str, int], devices=None):
    """``(mesh, batch_axes)`` for a granule layout on the devices that exist.

    ``axis_sizes`` names the partition's granule axes, outermost first.
    The longest leading run of them whose sizes multiply to at most the
    device count becomes the mesh (over the first that-many devices); the
    remaining innermost axes fold onto each device as ``batch_axes`` — the
    suffix form ``GraphEngine`` requires.  With one device every axis is
    folded; ``batch_axes`` is None when none is."""
    devs = list(jax.devices() if devices is None else devices)
    names = list(axis_sizes)
    n_real, n_dev = 0, 1
    while (n_real < len(names)
           and n_dev * axis_sizes[names[n_real]] <= len(devs)):
        n_dev *= axis_sizes[names[n_real]]
        n_real += 1
    real = names[:n_real]
    if real:
        mesh = make_mesh(tuple(axis_sizes[a] for a in real), tuple(real),
                         devices=devs[:n_dev])
    else:
        mesh = make_mesh((1,), ("device",), devices=devs[:1])
    batch = {a: int(axis_sizes[a]) for a in names[n_real:]}
    return mesh, (batch or None)


def _dealias_for_donation(tree: PyTree) -> PyTree:
    """Copy pytree leaves that share a device buffer with an earlier leaf.

    XLA refuses to donate the same buffer twice, and block ``init_state``
    implementations legitimately reuse one array for several state fields
    (e.g. ``CoreState(value=v, own=v, acc=v)``).  Donating entry points
    route their input through this first; leaves already distinct (the
    steady state, since compiled-loop *outputs* never alias) pass through
    untouched.
    """
    seen: set[int] = set()

    def fix(x):
        if isinstance(x, jax.Array):
            try:
                key = x.unsafe_buffer_pointer()
            except Exception:  # sharded: key on the first local shard
                try:
                    key = x.addressable_shards[0].data.unsafe_buffer_pointer()
                except Exception:
                    key = id(x)
            if key in seen:
                return jnp.copy(x)
            seen.add(key)
        return x

    return jax.tree.map(fix, tree)


def _sq(tree: PyTree, nd: int) -> PyTree:
    """Strip the leading (1,) * nd device dims inside shard_map."""
    return jax.tree.map(lambda x: x.reshape(x.shape[nd:]), tree)


def _unsq(tree: PyTree, nd: int) -> PyTree:
    return jax.tree.map(lambda x: x.reshape((1,) * nd + x.shape), tree)


def _first(x: jax.Array) -> jax.Array:
    """Scalar view of a per-granule counter: the leaf itself when the local
    view is one granule (unbatched), row 0 of the (B,) batch otherwise
    (every batched granule steps in lockstep, so the rows agree)."""
    return x if x.ndim == 0 else x.reshape(-1)[0]


def _perfect_matching(adj: np.ndarray) -> np.ndarray:
    """Perfect matching in a regular bipartite multigraph (Kuhn's algorithm).

    adj[s, d] = remaining parallel-edge count.  Returns match[s] = d.
    A Δ-regular bipartite multigraph always has one (Hall's theorem), so
    failure here means the caller's regularization is broken.
    """
    G = adj.shape[0]
    match_r = np.full((G,), -1, np.int64)  # right node -> matched left node

    def augment(s: int, visited: np.ndarray) -> bool:
        for d in range(G):
            if adj[s, d] > 0 and not visited[d]:
                visited[d] = True
                if match_r[d] < 0 or augment(int(match_r[d]), visited):
                    match_r[d] = s
                    return True
        return False

    for s in range(G):
        if not augment(s, np.zeros((G,), bool)):
            raise AssertionError("regular bipartite graph lost its matching")
    match = np.full((G,), -1, np.int64)
    match[match_r] = np.arange(G, dtype=np.int64)
    return match


def edge_color_routes(
    pairs: Sequence[tuple[int, int]], n_granules: int
) -> list[list[tuple[int, int]]]:
    """Partition directed granule pairs into partial permutations.

    König construction: pad the route digraph (a bipartite graph senders ->
    receivers) with dummy edges until it is Δ-regular, then peel off Δ
    perfect matchings.  The number of classes therefore *equals*
    Δ = max over granules of (out-degree, in-degree) — the optimum, since
    some granule must appear in Δ distinct classes.  Deterministic.
    """
    if not pairs:
        return []
    G = n_granules
    real = np.zeros((G, G), np.int64)
    for s, d in pairs:
        real[s, d] += 1
    out_deg, in_deg = real.sum(axis=1), real.sum(axis=0)
    delta = int(max(out_deg.max(), in_deg.max()))

    # Regularize: total left deficiency == total right deficiency, so the
    # two-pointer pairing below always terminates with both sides at Δ.
    total = real.copy()
    od, idg = out_deg.copy(), in_deg.copy()
    si = di = 0
    while si < G:
        if od[si] >= delta:
            si += 1
            continue
        while idg[di] >= delta:
            di += 1
        add = min(delta - od[si], delta - idg[di])
        total[si, di] += add
        od[si] += add
        idg[di] += add

    classes: list[list[tuple[int, int]]] = []
    for _ in range(delta):
        match = _perfect_matching(total)
        cls: list[tuple[int, int]] = []
        for s in range(G):
            d = int(match[s])
            total[s, d] -= 1
            if real[s, d] > 0:  # prefer consuming a real route over a dummy
                real[s, d] -= 1
                cls.append((s, d))
        if cls:
            classes.append(cls)
    assert real.sum() == 0, "edge coloring failed to cover every route"
    return classes


def merge_compatible_classes(
    classes: Sequence[Sequence[tuple[int, int]]]
) -> list[list[tuple[int, int]]]:
    """Merge exchange classes that compose into one granule permutation.

    Two classes are *compatible* when no granule sends in both and no
    granule receives in both — their union is then still a partial
    permutation, i.e. one ``ppermute``.  Identical (duplicate) classes are
    collapsed outright: exchanging the same permutation twice per sync is
    never needed, the slab depth already covers the traffic.  Greedy,
    deterministic, order-preserving.

    NOTE: on the König coloring the engine uses this is a *guard*, not an
    optimization — König already emits the optimal Δ classes, and the
    granule realizing Δ appears in every one of them, so nothing merges.
    It exists so ANY class decomposition fed through the table builder
    (hand-written schedules, future colorings) keeps the invariant that
    the class count never exceeds the distinct granule shifts
    (``route_shift_groups``) — asserted at build time.
    """
    merged: list[dict[int, int]] = []  # src -> dst maps
    for cls in classes:
        cmap = dict(cls)
        for m in merged:
            if m == cmap:  # duplicate permutation: plain dedup
                break
            if not (m.keys() & cmap.keys()) and not (
                set(m.values()) & set(cmap.values())
            ):
                m.update(cmap)
                break
        else:
            merged.append(cmap)
    return [sorted(m.items()) for m in merged]


def route_shift_groups(
    pairs: Sequence[tuple[int, int]], dev_shape: Sequence[int]
) -> dict[tuple[int, ...], list[tuple[int, int]]]:
    """Group directed granule routes by their coordinate *shift*.

    The shift of a route is the plain per-axis difference of the granule
    coordinates (no modular wrap), so a 2-D torus tiling has exactly four:
    east, east-wrap, south, south-wrap.  A fixed shift is injective, hence
    every group is automatically a partial permutation — one ``ppermute``.
    The distinct-shift count therefore upper-bounds the class count any
    decomposition needs, and lower-bounds nothing: König (max in/out
    degree) is always <= it, which ``GraphEngine`` asserts at build time.
    """
    dev_shape = tuple(int(s) for s in dev_shape)
    groups: dict[tuple[int, ...], list[tuple[int, int]]] = {}
    for s, d in pairs:
        sc = np.unravel_index(int(s), dev_shape)
        dc = np.unravel_index(int(d), dev_shape)
        shift = tuple(int(b) - int(a) for a, b in zip(sc, dc))
        groups.setdefault(shift, []).append((int(s), int(d)))
    return groups


def granule_local_cycle(groups, n_local: int, W: int, dtype, st):
    """One cycle of a granule-local network.

    Identical semantics to ``NetworkSim.step`` — same pre-cycle queue
    snapshot, same sentinel handling, same clock-divider rate control —
    but driven by granule-local tables read from the state
    (``st.tables.rx_idx/tx_idx`` per group, local-queue-id space).

    ``st`` is any pytree with ``queues`` (n_local rows), ``tables``,
    ``block_states`` (per group, n_slot-leading) and ``cycle``; the
    leading device dims must already be squeezed.  Shared by
    ``GraphEngine._local_cycle`` (inside shard_map) and the multiprocess
    workers (``repro.runtime.worker``): because the tables are runtime
    inputs, every same-shaped granule traces to the same jaxpr — the
    prebuilt-simulator-cache property — and both engine families step
    granules with literally the same code.
    """
    from .graph import NULL_RX as NRX, NULL_TX as NTX

    q = st.queues
    tb = st.tables
    fronts, valids = qmod.peek(q)
    readies = ~qmod.full(q)
    valids = valids.at[NRX].set(False)
    readies = readies.at[NTX].set(True)

    push_payload = jnp.zeros((n_local, W), dtype)
    push_valid = jnp.zeros((n_local,), bool)
    pop_ready = jnp.zeros((n_local,), bool)

    new_states = []
    for gi, grp in enumerate(groups):
        blk = grp.block
        rxm, txm = tb.rx_idx[gi], tb.tx_idx[gi]
        rx = {
            port: (fronts[rxm[:, p]], valids[rxm[:, p]])
            for p, port in enumerate(blk.in_ports)
        }
        tx_ready = {port: readies[txm[:, p]] for p, port in enumerate(blk.out_ports)}
        bst = st.block_states[gi]
        new_st, rx_ready, tx = jax.vmap(blk.step)(bst, rx, tx_ready)

        if blk.clock_divider > 1:
            en = (st.cycle % blk.clock_divider) == 0
            new_st = jax.tree.map(lambda n, o: jnp.where(en, n, o), new_st, bst)
            rx_ready = {k: v & en for k, v in rx_ready.items()}
            tx = {k: (p, v & en) for k, (p, v) in tx.items()}
        new_states.append(new_st)

        for p, port in enumerate(blk.in_ports):
            pop_ready = pop_ready.at[rxm[:, p]].max(rx_ready[port])
        for p, port in enumerate(blk.out_ports):
            pay, val = tx[port]
            push_payload = push_payload.at[txm[:, p]].set(
                pay.astype(dtype), mode="drop"
            )
            push_valid = push_valid.at[txm[:, p]].max(val)

    push_valid = push_valid.at[NTX].set(False)
    pop_ready = pop_ready.at[NRX].set(False)
    q2, _, _ = qmod.cycle(q, push_payload, push_valid, pop_ready)
    return st.replace(
        queues=q2, block_states=tuple(new_states), cycle=st.cycle + 1
    )


class GraphEngine:
    """Epoch-batched distributed interpreter of a partitioned ChannelGraph.

    graph:     the channel-graph IR (``Network.graph()`` or a builder).
    partition: a ``graph.PartitionTree`` (hierarchical: carries both the
               instance -> granule map and the tier structure), or any flat
               instance -> granule map ``normalize_partition`` accepts;
               granules are the devices of ``mesh`` along ``axes``,
               flattened row-major (outermost tier first).
    K:         innermost sync rate — cycles of local simulation per
               innermost exchange (the paper's "max simulation rate"
               analogue, swept in Fig. 15).  Ignored when ``partition`` is
               a PartitionTree or ``tiers`` is given.
    tiers:     optional per-tier spec (``graph.Tier`` or ``(axes, K)``
               pairs, outermost first) grouping the mesh axes into sync
               tiers; tier t's boundary channels are exchanged every
               ``prod(K_t .. K_inner)`` cycles.  Default: one tier spanning
               ``axes`` with rate ``K`` — the flat engine.
    batch_axes: signature batching (PR 6).  Names an innermost suffix of
               the granule axes to run as an on-device *batch* dimension
               instead of mesh shards: all granules along those axes stack
               on one leading axis and step with a single vmapped dispatch
               per cycle, and their tier exchanges become local slab
               gathers (no collective).  Pass a sequence of axis names
               (sizes from the mesh / PartitionTree) or a ``{name: size}``
               mapping for axes that are not mesh axes at all — e.g.
               ``mesh=Mesh(1 device), batch_axes={"g": 8}`` folds an
               8-granule wafer onto one device.  Granules batched together
               should share ``granule_signature`` (one traced stepper);
               the engine works regardless (tables are runtime inputs) but
               the speedup argument is per-signature.
    overlap:   overlapped exchange (ISSUE 7).  When on, every tier exchange
               splits into an *issue* phase (drain + start the transfer, at
               the end of an epoch window) and a *commit* phase (finish the
               transfer + fill, at the start of the NEXT window), so XLA's
               latency-hiding scheduler can overlap the collective with the
               intervening compute.  Bit-identical to the serial schedule
               by construction: a slab drained at the end of window ``w``
               is only consumed from the ingress queue at the start of
               window ``w+1``, and issue/commit touch disjoint queue rows
               (egress vs ingress) and per-tier credit windows.  "auto"
               (default off) — the ``REPRO_OVERLAP`` env var overrides
               auto, an explicit bool overrides both (the ``resolve_mode``
               precedence from PR 6).
    """

    engine_kind = "graph"

    def __init__(
        self,
        graph: ChannelGraph,
        partition,
        mesh: Mesh,
        K: int = 1,
        axes: Sequence[str] | None = None,
        tiers: Sequence | None = None,
        batch_axes=None,
        overlap: Any = "auto",
    ):
        self.graph = graph
        self.mesh = mesh
        # resolved at build time (env read once): explicit arg > env > auto
        self.overlap = granule_step.resolve_overlap(overlap)
        if batch_axes is None:
            bmap: dict[str, int | None] = {}
        elif isinstance(batch_axes, dict):
            bmap = {str(a): int(s) for a, s in batch_axes.items()}
        else:
            bmap = {str(a): None for a in batch_axes}

        def axis_size(a: str) -> int:
            s = bmap.get(a)
            if s is not None:
                return s
            if a not in mesh.shape:
                raise ValueError(
                    f"axis {a!r} is not a mesh axis; pass its size via "
                    f"batch_axes={{{a!r}: size}}"
                )
            return int(mesh.shape[a])

        if isinstance(partition, PartitionTree):
            if tiers is not None:
                raise ValueError("pass tiers via the PartitionTree or the "
                                 "tiers kwarg, not both")
            if axes is not None:
                raise ValueError(
                    "axes is derived from the PartitionTree's tiers — "
                    "pass the axis order there"
                )
            ptree = partition
            mesh_shape = tuple(
                sz if (a in bmap and bmap[a] is None) else axis_size(a)
                for a, sz in zip(ptree.axes, ptree.dev_shape)
            )
            if mesh_shape != ptree.dev_shape:
                raise ValueError(
                    f"PartitionTree device shape {ptree.dev_shape} does not "
                    f"match mesh/batch axes {ptree.axes} = {mesh_shape}"
                )
            if ptree.part.shape != (graph.n_instances,):
                raise ValueError(
                    f"PartitionTree covers {ptree.part.size} instances, "
                    f"graph has {graph.n_instances}"
                )
        else:
            if tiers is not None:
                if axes is not None:
                    raise ValueError(
                        "axes is derived from the tier spec when tiers is "
                        "given — pass the axis order via the tiers entries"
                    )
                tspec = normalize_tiers(tiers)
            else:
                t_axes = tuple(axes) if axes is not None else tuple(mesh.axis_names)
                tspec = (Tier(axes=t_axes, K=int(K)),)
            all_axes = tuple(a for t in tspec for a in t.axes)
            n_gran = int(np.prod([axis_size(a) for a in all_axes]))
            part = normalize_partition(graph, partition, n_gran)
            ptree = PartitionTree(
                part, tspec, {a: axis_size(a) for a in all_axes}
            )
        self.ptree = ptree
        self.tiers = ptree.tiers
        self.axes = ptree.axes
        self.dev_shape = ptree.dev_shape
        self.nd = len(self.dev_shape)
        unknown = set(bmap) - set(ptree.axes)
        if unknown:
            raise ValueError(f"batch_axes {sorted(unknown)} are not "
                             f"granule axes {ptree.axes}")
        self.batch_axes = tuple(a for a in ptree.axes if a in bmap)
        self.nd_real = self.nd - len(self.batch_axes)
        if self.batch_axes != tuple(ptree.axes[self.nd_real:]):
            raise ValueError(
                f"batch_axes {self.batch_axes} must be a contiguous "
                f"innermost suffix of the granule axes {ptree.axes} (state "
                f"leaves shard on the leading real axes)"
            )
        self.real_axes = tuple(ptree.axes[: self.nd_real])
        self.real_shape = ptree.dev_shape[: self.nd_real]
        self.batch_shape = ptree.dev_shape[self.nd_real:]
        self.B = int(np.prod(self.batch_shape)) if self.batch_shape else 1
        self._batched = bool(self.batch_axes)
        self.G = ptree.n_granules
        self.K_tiers = ptree.K_tiers
        self.periods = ptree.periods()
        self.cycles_per_epoch = ptree.cycles_per_epoch
        self.K = self.K_tiers[-1]  # innermost rate (back-compat accessor)
        # max packets per boundary channel per *its tier's* exchange
        self.E_tiers = tuple(
            min(p, graph.capacity - 1) for p in self.periods
        )
        self.E = self.E_tiers[-1]
        self.W = graph.payload_words
        self.capacity = graph.capacity
        self.dtype = graph.dtype
        self.part = ptree.part
        self._spec = P(*self.real_axes)
        self._jit_cache: dict[Any, Callable] = {}
        self._build_tables()

    # ------------------------------------------------- host-side compilation
    def _build_tables(self) -> None:
        """Lower (graph, partition) to per-granule tables — all vectorized.

        The mesh-independent half (queue-id assignment, per-group member
        placement, boundary routes) is ``graph.lower_partition`` — shared
        with the multiprocess runtime, so both families simulate the same
        granule-local state layout.  This method adds the shard_map
        specifics: per-tier exchange-class coloring and the concatenated
        slab tables the batched ppermute exchange consumes.
        """
        g, G = self.graph, self.G
        low = lower_partition(g, self.ptree)
        self.lowering = low
        tx_local, rx_local = low.tx_local, low.rx_local
        self.n_local = low.n_local
        self._tx_local, self._rx_local = tx_local, rx_local
        self._chan_owner = low.chan_owner
        self._ent = low.ent
        self._rx_tables, self._tx_tables = low.rx_tables, low.tx_tables
        self._act_tables = low.act_tables
        self._member_of = low.member_of
        self._member_granule = low.member_granule
        self._member_slot = low.member_slot
        self._n_slot = low.n_slot
        routes = low.routes  # (tier, src granule, dst granule) -> channels

        # Per tier: König classes, then compatible-permutation merging, then
        # concatenation into ONE (G, S_t) slab table — the batched exchange.
        # Under ``batch_axes`` the coloring is refined per *real-axis* shift
        # first: all routes of one class then share a single injective
        # device->device map (its ``real_perm`` ppermute, () when the class
        # never leaves the device), and the within-device move becomes the
        # ``bat_fwd``/``bat_rev`` batch-row gathers.
        G_real = int(np.prod(self.real_shape)) if self.real_shape else 1
        self.classes: list[_ExchangeClass] = []
        self.tier_classes: list[list[_ExchangeClass]] = []
        send_i, send_m, recv_i, recv_m = [], [], [], []
        bat_f, bat_r = [], []
        for t in range(len(self.tiers)):
            pairs = sorted((s, d) for tt, s, d in routes if tt == t)
            if self._batched:
                shift_groups: dict[tuple, list[tuple[int, int]]] = {}
                for s, d in pairs:
                    sc = np.unravel_index(s, self.dev_shape)
                    dc = np.unravel_index(d, self.dev_shape)
                    shift = tuple(
                        int(dc[i]) - int(sc[i]) for i in range(self.nd_real)
                    )
                    shift_groups.setdefault(shift, []).append((s, d))
                colors, rperms = [], []
                for shift in sorted(shift_groups):
                    for color in merge_compatible_classes(
                        edge_color_routes(shift_groups[shift], G)
                    ):
                        colors.append(color)
                        if any(shift):
                            rperms.append(tuple(sorted(
                                {(s // self.B, d // self.B) for s, d in color}
                            )))
                        else:
                            rperms.append(())
            else:
                colors = merge_compatible_classes(edge_color_routes(pairs, G))
                rperms = [None] * len(colors)
                if pairs:
                    # a fixed shift is one permutation, so no decomposition
                    # ever needs more classes than distinct shifts (König:
                    # fewer)
                    n_shifts = len(route_shift_groups(pairs, self.dev_shape))
                    assert len(colors) <= n_shifts, (len(colors), n_shifts)
            cmaxes = [
                max(len(routes[(t, s, d)]) for s, d in color) for color in colors
            ]
            S_t = sum(cmaxes)
            si = np.zeros((G, S_t), np.int64)
            sm = np.zeros((G, S_t), bool)
            ri = np.zeros((G, S_t), np.int64)
            rm = np.zeros((G, S_t), bool)
            bf = np.zeros((G_real, self.B, S_t), np.int64)
            br = np.zeros((G_real, self.B, S_t), np.int64)
            cls_t: list[_ExchangeClass] = []
            col0 = 0
            for color, cmax, rperm in zip(colors, cmaxes, rperms):
                for s, d in color:
                    chans = routes[(t, s, d)]
                    k = len(chans)
                    si[s, col0:col0 + k] = tx_local[chans]
                    sm[s, col0:col0 + k] = True
                    ri[d, col0:col0 + k] = rx_local[chans]
                    rm[d, col0:col0 + k] = True
                    if self._batched:
                        rs, bs = divmod(s, self.B)
                        rd, bd = divmod(d, self.B)
                        bf[rs, bd, col0:col0 + k] = bs
                        br[rd, bs, col0:col0 + k] = bd
                cls = _ExchangeClass(
                    perm=tuple(color), cmax=cmax, tier=t,
                    depth=self.E_tiers[t], col0=col0, real_perm=rperm,
                )
                cls_t.append(cls)
                self.classes.append(cls)
                col0 += cmax
            self.tier_classes.append(cls_t)
            send_i.append(si.astype(np.int32))
            send_m.append(sm)
            recv_i.append(ri.astype(np.int32))
            recv_m.append(rm)
            bat_f.append(bf.astype(np.int32))
            bat_r.append(br.astype(np.int32))
        self._send_idx, self._send_mask = send_i, send_m
        self._recv_idx, self._recv_mask = recv_i, recv_m
        self._bat_fwd = bat_f if self._batched else []
        self._bat_rev = bat_r if self._batched else []

        # Trailing tiers with NO exchange classes never synchronize, so
        # their loop nesting is pure overhead: tiers >= _fold_from run as
        # one contiguous inner-cycle block of prod(K_t..K_inner) cycles.
        # (A single-granule engine folds the whole epoch into one loop.)
        f = len(self.tiers)
        while f > 0 and not self.tier_classes[f - 1]:
            f -= 1
        self._fold_from = f
        self._publish_ici_plan()

    def _publish_ici_plan(self) -> None:
        """Publish, per tier t, what one exchange moves between devices:
        gauges ``exchange.ici_permutes.<t>`` (the ``ppermute``s it runs:
        slab and count forward, credit back, for each class that leaves
        its device) and ``exchange.ici_bytes.<t>`` (the bytes those
        move, every device pair's whole column window, padding rows
        included).  Both read 0 when every class stays on its device."""
        packet = jnp.dtype(self.dtype).itemsize * self.W
        for t, cls_t in enumerate(self.tier_classes):
            # a slot's packets, then its int32 count and int32 credit
            slot = self.E_tiers[t] * packet + 4 + 4
            permutes = nbytes = 0
            for cl in cls_t:
                # unbatched, the class's own granule permutation is the
                # collective, one granule per device
                perm, rows = ((cl.perm, 1) if cl.real_perm is None
                              else (cl.real_perm, self.B))
                if not perm:
                    continue
                permutes += 3
                nbytes += len(perm) * rows * cl.cmax * slot
            REGISTRY.set(f"exchange.ici_permutes.{t}", permutes)
            REGISTRY.set(f"exchange.ici_bytes.{t}", nbytes)

    def _dev(self, arr: np.ndarray) -> jax.Array:
        """(G, ...) host table -> (dev_shape..., ...) device array."""
        return jnp.asarray(arr.reshape(self.dev_shape + arr.shape[1:]))

    def _dev_bat(self, arr: np.ndarray) -> jax.Array:
        """(G_real, B, S_t) batch-gather table -> (dev_shape..., S_t).

        The batch-row axis unflattens into the batch axes so every
        GraphTables leaf carries the same ``dev_shape`` leading dims (the
        local view flattens them back to one (B, S_t))."""
        return jnp.asarray(
            arr.reshape(self.real_shape + self.batch_shape + arr.shape[2:])
        )

    def tables(self) -> GraphTables:
        return GraphTables(
            rx_idx=tuple(self._dev(t) for t in self._rx_tables),
            tx_idx=tuple(self._dev(t) for t in self._tx_tables),
            active=tuple(self._dev(t) for t in self._act_tables),
            send_idx=tuple(self._dev(t) for t in self._send_idx),
            send_mask=tuple(self._dev(t) for t in self._send_mask),
            recv_idx=tuple(self._dev(t) for t in self._recv_idx),
            recv_mask=tuple(self._dev(t) for t in self._recv_mask),
            bat_fwd=tuple(self._dev_bat(t) for t in self._bat_fwd),
            bat_rev=tuple(self._dev_bat(t) for t in self._bat_rev),
        )

    # ------------------------------------------------------------------ init
    def _init_block_states(
        self, key: jax.Array, group_params: dict[int, PyTree] | None
    ) -> list[PyTree]:
        """Per-group stacked block states in granule layout (shared by
        ``FusedEngine.init`` so per-member init stays engine-invariant)."""
        states = []
        for gi, grp in enumerate(self.graph.groups):
            blk = grp.block
            params = grp.params
            if group_params is not None and gi in group_params:
                params = group_params[gi]
            # Same key derivation as NetworkSim.init (group index + global
            # member order), so per-member init is bit-identical across
            # engines even for key-consuming blocks.
            keys = jax.random.split(jax.random.fold_in(key, gi), grp.n_members)
            mo = self._member_of[gi].reshape(self.dev_shape + (self._n_slot[gi],))
            keys_l = keys[mo]
            init = blk.init_state
            for _ in range(self.nd + 1):
                init = jax.vmap(init)
            if params is not None:
                params_l = jax.tree.map(lambda x: jnp.asarray(x)[mo], params)
                st = init(keys_l, params_l)
            else:
                st = init(keys_l)
            states.append(st)
        return states

    def init(self, key: jax.Array, group_params: dict[int, PyTree] | None = None) -> GraphState:
        """Initial state.  ``group_params[gi]`` overrides the IR's stacked
        per-member params for group ``gi`` (leading dim = n_members, in
        global instantiation order — the same order ``NetworkSim`` uses, so
        per-member init is bit-identical across engines)."""
        states = self._init_block_states(key, group_params)
        q = qmod.make_queues(self.n_local, self.W, self.capacity, self.dtype)
        queues = jax.tree.map(
            lambda x: jnp.broadcast_to(x, self.dev_shape + x.shape), q
        )
        cap1 = self.capacity - 1
        credits = tuple(
            jnp.full(self.dev_shape + (si.shape[1],), cap1, jnp.int32)
            for si in self._send_idx
        )
        return GraphState(
            queues=queues,
            block_states=tuple(states),
            credits=credits,
            cycle=jnp.zeros(self.dev_shape, jnp.int32),
            epoch=jnp.zeros(self.dev_shape, jnp.int32),
            tables=self.tables(),
        )

    def shardings(self):
        """Sharding for every GraphState leaf (granule-major).

        When EVERY granule axis is batched there is nothing to shard —
        ``NamedSharding(mesh, P())`` would *replicate* the state over the
        whole mesh and make each jit redundantly re-execute the batch on
        every device (an 8-device mesh pays 8x the work for identical
        answers).  The all-batch engine therefore pins state to one
        device."""
        if self._batched and not self.real_axes:
            return jax.sharding.SingleDeviceSharding(
                self.mesh.devices.flat[0]
            )
        return NamedSharding(self.mesh, self._spec)

    def place(self, state: GraphState) -> GraphState:
        sh = self.shardings()
        return jax.tree.map(lambda x: jax.device_put(x, sh), state)

    # -------------------------------------------------- local <-> global view
    def _local_view(self, state: PyTree) -> PyTree:
        """Per-device view of the state: strip the (1,)*nd_real shard dims
        and flatten the batch axes into ONE leading (B,) axis (no-op
        reshape when unbatched — then this is plain ``_sq``)."""
        if not self._batched:
            return _sq(state, self.nd)
        return jax.tree.map(
            lambda x: x.reshape((self.B,) + x.shape[self.nd:]), state
        )

    def _global_view(self, local: PyTree) -> PyTree:
        if not self._batched:
            return _unsq(local, self.nd)
        return jax.tree.map(
            lambda x: x.reshape(
                (1,) * self.nd_real + self.batch_shape + x.shape[1:]
            ),
            local,
        )

    def _wrap(self, fn: Callable) -> Callable:
        """shard_map over the real mesh axes — or ``fn`` unwrapped when
        every granule axis is batched (single-device: no collectives at
        all, the whole epoch is one local computation)."""
        if not self.real_axes:
            return fn
        return shard_map(
            fn, mesh=self.mesh, in_specs=self._spec, out_specs=self._spec
        )

    # ----------------------------------------------------------- local cycle
    def _local_cycle(self, st: GraphState) -> GraphState:
        """One cycle of the granule-local network (pre-squeezed state) —
        the shared ``granule_local_cycle`` body (also the multiprocess
        workers' stepper, so the two families stay bit-identical)."""
        return granule_local_cycle(
            self.graph.groups, self.n_local, self.W, self.dtype, st
        )

    # ---------------------------------------------------------------- epoch
    def _pshift(self, x: jax.Array, perm) -> jax.Array:
        if not perm:
            return jnp.zeros_like(x)
        return jax.lax.ppermute(x, self.axes, list(perm))

    @jax.named_scope(_trace.PERMUTE)
    def _class_shift(self, x: jax.Array, t: int, rev: bool = False):
        """Move the tier-t slab columns class by class — one ``ppermute``
        per class (each a partial permutation of granules); ``rev`` runs
        the reverse permutations (the credit return)."""
        parts = []
        for cl in self.tier_classes[t]:
            perm = tuple((d, s) for s, d in cl.perm) if rev else cl.perm
            parts.append(self._pshift(x[cl.col0:cl.col0 + cl.cmax], perm))
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, 0)

    @jax.named_scope(_trace.PERMUTE)
    def _bat_move(self, x, tbl, t: int, rev: bool = False):
        """The batched slab move: within-device share of every class is a
        ``bat_fwd``/``bat_rev`` batch-row gather instead of a collective;
        only classes whose ``real_perm`` is non-empty pay a ppermute (none
        do when every granule axis is batched).  Garbage rows from the
        0-padded gather tables are killed by the same send/recv masks that
        already guard slab padding."""
        parts = []
        for cl in self.tier_classes[t]:
            w = x[:, cl.col0:cl.col0 + cl.cmax]
            g = tbl[:, cl.col0:cl.col0 + cl.cmax]
            g = g.reshape(g.shape + (1,) * (w.ndim - 2))
            part = jnp.take_along_axis(w, g, axis=0)
            perm = cl.real_perm
            if perm:
                if rev:
                    perm = tuple((d, s) for s, d in perm)
                part = jax.lax.ppermute(part, self.real_axes, list(perm))
            parts.append(part)
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, 1)

    @jax.named_scope(_trace.DRAIN)
    def _exchange_issue(self, st: GraphState, t: int):
        """Tier t's exchange, ISSUE half: drain every egress queue of the
        tier (credit-bounded) and start the transfer — the forward
        ``ppermute`` per class.  Returns ``(st, pending)`` where pending
        is the in-flight ``(slab_in, cnt_in)`` pair (``None`` when the
        tier has no exchange classes).  Reads egress queues + this tier's
        credit window only, so it commutes bit-exactly with other tiers'
        commits (disjoint queue rows, per-tier credits)."""
        if self._batched:
            return self._exchange_issue_batched(st, t)
        cls_t = self.tier_classes[t]
        if not cls_t:
            return st, None
        q = st.queues
        tb = st.tables
        sidx, smask = tb.send_idx[t], tb.send_mask[t]
        # drain all egress queues of the tier, bounded by receiver credit
        sub = qmod.QueueArray(
            buf=q.buf[sidx], head=q.head[sidx], tail=q.tail[sidx],
            capacity=q.capacity,
        )
        limit = jnp.where(smask, st.credits[t], 0)
        sub2, slab, cnt = qmod.drain(sub, self.E_tiers[t], limit=limit)
        q = q.replace(tail=q.tail.at[sidx].set(sub2.tail))
        slab_in = self._class_shift(slab, t)
        cnt_in = jnp.where(tb.recv_mask[t], self._class_shift(cnt, t), 0)
        return st.replace(queues=q), (slab_in, cnt_in)

    @jax.named_scope(_trace.FILL)
    def _exchange_commit(self, st: GraphState, t: int, pending) -> GraphState:
        """Tier t's exchange, COMMIT half: land the in-flight slab in the
        ingress queues (ONE bulk ``fill``) and return fresh credits to the
        senders on the reverse permutations.  Writes ingress queues + this
        tier's credit window only."""
        if self._batched:
            return self._exchange_commit_batched(st, t, pending)
        if pending is None:
            return st
        slab_in, cnt_in = pending
        tb = st.tables
        ridx, rmask = tb.recv_idx[t], tb.recv_mask[t]
        q = qmod_fill_at(st.queues, ridx, slab_in, cnt_in)
        # receivers advertise new free space; returns to the senders on the
        # reverse permutations
        cred = jnp.where(rmask, jnp.take(qmod.free(q), ridx), 0)
        new_credits = list(st.credits)
        new_credits[t] = self._class_shift(cred, t, rev=True)
        return st.replace(queues=q, credits=tuple(new_credits))

    def _exchange_issue_batched(self, st: GraphState, t: int):
        """ISSUE half with the granules stacked on a (B,) batch axis —
        credit-bounded ``stage_drain`` per row + the forward ``bat_fwd``
        slab move (collective only for classes with a real shift)."""
        cls_t = self.tier_classes[t]
        if not cls_t:
            return st, None
        tb = st.tables
        sidx, smask = tb.send_idx[t], tb.send_mask[t]  # (B, S_t)
        limit = jnp.where(smask, st.credits[t], 0)
        q, slab, cnt = jax.vmap(
            lambda qb, si, lim: qmod.stage_drain(
                qb, si, self.E_tiers[t], limit=lim
            )
        )(st.queues, sidx, limit)
        slab_in = self._bat_move(slab, tb.bat_fwd[t], t)
        cnt_in = jnp.where(
            tb.recv_mask[t], self._bat_move(cnt, tb.bat_fwd[t], t), 0
        )
        return st.replace(queues=q), (slab_in, cnt_in)

    def _exchange_commit_batched(self, st: GraphState, t: int, pending):
        """COMMIT half on the batch layout: ``stage_fill`` per row + the
        ``bat_rev`` credit return."""
        if pending is None:
            return st
        slab_in, cnt_in = pending
        tb = st.tables
        ridx, rmask = tb.recv_idx[t], tb.recv_mask[t]
        q = jax.vmap(qmod.stage_fill)(st.queues, ridx, slab_in, cnt_in)
        cred = jnp.where(
            rmask, jnp.take_along_axis(qmod.free(q), ridx, axis=1), 0
        )
        new_credits = list(st.credits)
        new_credits[t] = self._bat_move(cred, tb.bat_rev[t], t, rev=True)
        return st.replace(queues=q, credits=tuple(new_credits))

    def _exchange_tier(self, st: GraphState, t: int) -> GraphState:
        """Run tier t's batched exchange (runs inside shard_map).

        ONE bulk ``drain`` empties every egress queue of the tier into the
        concatenated ``(S_t, E_t, W)`` slab (each slot bounded by the
        receiver's advertised credit), one ``ppermute`` per class moves
        that class's column window, ONE bulk ``fill`` lands everything in
        the ingress queues, and fresh credits return to the senders on the
        reverse permutations.  Egress/ingress queues are disjoint across
        classes, so this is bit-identical to the historical per-class
        drain/permute/fill chain — with ~1/#classes of the gather/scatter
        traffic.  Other tiers' queues and credit windows are untouched.

        The serial schedule is literally commit∘issue — the overlapped
        schedule (``overlap=True``) runs the same two halves with compute
        in between, which is why the two are bit-identical.
        """
        st, pending = self._exchange_issue(st, t)
        return self._exchange_commit(st, t, pending)

    def _inner_cycles(self, st: GraphState, K: int) -> GraphState:
        """K granule-local cycles — the innermost hot loop.  ``FusedEngine``
        overrides this with the fused-epoch kernel."""
        cyc = (jax.vmap(self._local_cycle) if self._batched
               else self._local_cycle)
        return jax.lax.scan(
            lambda s, _: (cyc(s), None), st, None, length=K
        )[0]

    def _tier_round(self, st: GraphState, t: int) -> GraphState:
        """One round of tier t: K_t sub-rounds (granule-local cycles at the
        innermost tier, tier-(t+1) rounds otherwise), then tier t's
        exchange — so tier t synchronizes every ``periods[t]`` cycles.
        Exchange-free trailing tiers are folded into one contiguous
        inner-cycle block (no loop nesting, no no-op exchanges)."""
        if t >= self._fold_from:
            return self._inner_cycles(st, int(np.prod(self.K_tiers[t:])))
        if t == len(self.tiers) - 1:
            st = self._inner_cycles(st, self.tiers[t].K)
        else:
            body = lambda s, _: (self._tier_round(s, t + 1), None)  # noqa: E731
            st = jax.lax.scan(body, st, None, length=self.tiers[t].K)[0]
        return self._exchange_tier(st, t)

    # --------------------------------------------- overlapped (split) schedule
    def _pend_tiers(self, t0: int) -> tuple:
        """Static tier order of the pending chain ``_round_split(st, t0)``
        returns: the suffix of tiers whose exchanges fire *at the end* of a
        tier-t0 round, deepest first — issued there, committed by the
        caller at the start of its next window (``_commit_chain``)."""
        if t0 >= self._fold_from:
            return ()
        inner = () if t0 == len(self.tiers) - 1 else self._pend_tiers(t0 + 1)
        return inner + ((t0,) if self.tier_classes[t0] else ())

    def _commit_chain(self, st: GraphState, t0: int, pend: tuple) -> GraphState:
        """Commit a pending chain from ``_round_split(·, t0)`` — fills land
        deepest tier first, the same order the serial schedule fills them
        (they are disjoint across tiers either way)."""
        tiers = self._pend_tiers(t0)
        assert len(tiers) == len(pend), (tiers, len(pend))
        for t, p in zip(tiers, pend):
            st = self._exchange_commit(st, t, p)
        return st

    def _round_split(self, st: GraphState, t: int):
        """One round of tier t with *split* exchanges: every sub-round's
        boundary transfers are ISSUED at its window end and COMMITTED at
        the start of the next sub-round's window (inside the scan body:
        commit-previous, then compute — so the in-flight data crosses a
        loop iteration and XLA's scheduler can overlap the transfer with
        the next window's compute).  The final boundary's chain — tier t's
        own exchange stacked on the inner tiers that fired with it — is
        returned *pending* for the caller to commit at ITS next window.

        Bit-identity with ``_tier_round``: issue reads egress queues +
        credits[t] only, commit writes ingress queues + credits[t] only,
        and those row sets are disjoint across all tiers — so hoisting
        commits past later issues reorders nothing; and every commit still
        precedes the first cycle that could consume the filled packets
        (the start of window ``w+1`` for a slab drained at the end of
        ``w``), which is exactly where the serial schedule fills them
        relative to the dataflow."""
        if t >= self._fold_from:
            return self._inner_cycles(st, int(np.prod(self.K_tiers[t:]))), ()
        if t == len(self.tiers) - 1:
            st, pend = self._inner_cycles(st, self.tiers[t].K), ()
        else:
            st, pend = self._round_split(st, t + 1)
            if self.tiers[t].K > 1:

                def body(carry, _):
                    s, p = carry
                    s = self._commit_chain(s, t + 1, p)
                    return self._round_split(s, t + 1), None

                (st, pend), _ = jax.lax.scan(
                    body, (st, pend), None, length=self.tiers[t].K - 1
                )
        if self.tier_classes[t]:
            st, p_t = self._exchange_issue(st, t)
            pend = pend + (p_t,)
        return st, pend

    def _epoch(self, st: GraphState) -> GraphState:
        """One outermost round = ``cycles_per_epoch`` local cycles, every
        tier exchanged at its own cadence (runs inside shard_map).  Under
        ``overlap`` the split schedule runs instead; the last boundary's
        chain commits before returning (epoch boundaries are host-I/O
        points, so no transfer may stay in flight across them)."""
        if self.overlap:
            st, pend = self._round_split(st, 0)
            st = self._commit_chain(st, 0, pend)
        else:
            st = self._tier_round(st, 0)
        return st.replace(epoch=st.epoch + 1)

    # ------------------------------------------------------------------ run
    def epoch_fn(self):
        """shard_map'd single-epoch function (used by dryrun + benchmarks)."""

        def run(state):
            return self._global_view(self._epoch(self._local_view(state)))

        return self._wrap(run)

    def run_epochs(
        self, state: GraphState, n_epochs: int, *, donate: bool = True
    ) -> GraphState:
        """Advance ``n_epochs`` outermost epochs.

        ``donate=True`` (default) donates the state buffers into the
        compiled loop (``jax.jit(..., donate_argnums=0)``): the wafer state
        is updated in place instead of being copied through HBM on every
        call, and the *input* state must not be reused afterwards.  Pass
        ``donate=False`` to keep the input alive.
        """
        key = ("run", n_epochs, donate)
        if key not in self._jit_cache:
            REGISTRY.inc(f"{self.engine_kind}.compile.count")

            def run(state):
                local = self._local_view(state)
                out = jax.lax.scan(
                    lambda s, _: (self._epoch(s), None), local, None, length=n_epochs
                )[0]
                return self._global_view(out)

            self._jit_cache[key] = jax.jit(
                self._wrap(run),
                donate_argnums=(0,) if donate else (),
            )
        if donate:
            state = _dealias_for_donation(state)
        REGISTRY.inc(f"{self.engine_kind}.dispatch.count")
        REGISTRY.inc(f"{self.engine_kind}.epochs", float(n_epochs))
        return self._jit_cache[key](state)

    def run_cycles(self, state: GraphState, n_cycles: int) -> GraphState:
        """Advance ``ceil(n_cycles / cycles_per_epoch)`` outermost epochs
        (>= n_cycles local cycles)."""
        return self.run_epochs(state, -(-n_cycles // self.cycles_per_epoch))

    def _done_view(self, local: GraphState):
        """What ``run_until``'s predicate sees (the granule-local state).

        Subclasses narrow the view instead of overriding ``run_until`` —
        that keeps the public signature and the jit-cache keying defined in
        exactly one place, so a subclass call can never silently miss the
        cache or drift from the base signature.
        """
        return local

    def run_until(
        self,
        state: GraphState,
        done_fn: Callable[[Any], jax.Array],
        max_epochs: int,
        *,
        cache_key: Any = None,
        donate: bool = True,
    ) -> GraphState:
        """Run epochs until ``done_fn(self._done_view(local))`` holds on
        every granule, or at most ``max_epochs`` MORE epochs from the
        input state (a relative budget: the compiled loop is reusable
        from any starting epoch, so interactive callers never retrace).

        For ``GraphEngine`` the view is the granule-local (squeezed)
        GraphState — padding slots are live in ``block_states``, mask with
        ``local.tables.active[gi]`` when the partition is uneven.
        ``GridEngine`` narrows the view to the cell states.

        The compiled loop is cached per (predicate, max_epochs).  The cache
        pins the predicate object (``cache_key`` if given, else ``done_fn``)
        so a garbage-collected function's recycled id can never alias a
        stale compilation; pass ``cache_key`` when the predicate is a fresh
        lambda per call but semantically constant.

        ``donate=True`` (default) donates the state buffers into the
        compiled loop — see ``run_epochs``; the input state must not be
        reused afterwards.
        """
        anchor = cache_key if cache_key is not None else done_fn
        key = ("until", id(anchor), max_epochs, donate)
        if key not in self._jit_cache:

            @jax.named_scope(_trace.DONE)
            def not_done(s):
                # Local sum first (covers a (B,)-shaped batched predicate),
                # then psum over the real mesh axes if there are any.
                nd_ = jnp.sum(
                    1 - done_fn(self._done_view(s)).astype(jnp.int32)
                )
                if self.real_axes:
                    nd_ = jax.lax.psum(nd_, self.real_axes)
                return nd_

            def run(state):
                local = self._local_view(state)
                e0 = _first(local.epoch)

                # The global done flag is computed in the *body* and carried,
                # so the while condition itself contains no collectives.
                def cond(carry):
                    s, pending = carry
                    return (pending > 0) & (_first(s.epoch) - e0 < max_epochs)

                def body(carry):
                    s, _ = carry
                    s = self._epoch(s)
                    return s, not_done(s)

                # An already-done state runs zero epochs, so chunked callers
                # (the session's monitor cadence) can re-enter safely.
                out, _ = jax.lax.while_loop(
                    cond, body, (local, not_done(local))
                )
                return self._global_view(out)

            self._jit_cache[key] = (
                anchor,  # strong ref: keeps the keyed id alive
                jax.jit(
                    self._wrap(run),
                    donate_argnums=(0,) if donate else (),
                ),
            )
        if donate:
            state = _dealias_for_donation(state)
        return self._jit_cache[key][1](state)

    # ------------------------------------------------------- host utilities
    def gather_group(self, state: GraphState, gi: int) -> PyTree:
        """Group ``gi``'s member states in global instantiation order."""
        n_slot = self._n_slot[gi]
        idx = self._member_granule[gi] * n_slot + self._member_slot[gi]

        def pick(x):
            x = np.asarray(x)
            flat = x.reshape((self.G * n_slot,) + x.shape[self.nd + 1:])
            return flat[idx]

        return jax.tree.map(pick, jax.device_get(state.block_states[gi]))

    def group_state(self, state: GraphState, inst) -> PyTree:
        """One instance's (unstacked) state — mirrors NetworkSim.group_state."""
        inst_id = inst if isinstance(inst, int) else inst.inst_id
        gi, k = self.graph.locate(inst_id)
        didx = np.unravel_index(int(self._member_granule[gi][k]), self.dev_shape)
        slot = int(self._member_slot[gi][k])
        return jax.tree.map(
            lambda x: jax.device_get(x)[didx + (slot,)], state.block_states[gi]
        )

    # ---------------------- host-side external ports (PySbTx/PySbRx analogue)
    # External channels are *homed* on the granule that owns their simulated
    # endpoint (``ChannelGraph.ext_home``): host I/O touches only that
    # granule's queue slab, wherever it sits on the mesh.  ``host_push``/
    # ``host_pop`` (+ batched ``_many``) are the primitives the session's
    # Tx/Rx ports drive at epoch boundaries; ``push_external``/
    # ``pop_external`` remain as deprecation shims.
    def _ext_loc(self, cid: int) -> tuple[tuple[int, ...], int]:
        g = int(self._chan_owner[cid])
        didx = tuple(int(i) for i in np.unravel_index(g, self.dev_shape))
        lid = int(max(self._rx_local[cid], self._tx_local[cid]))
        return didx, lid

    def _ext_idx(self, table: dict, name: str) -> tuple:
        didx, lid = self._ext_loc(table[name])
        return didx + (lid,)

    def port_stats(self, state: GraphState) -> dict:
        """Per external port: occupancy/credit of the queue row homed on
        the owning granule — the uniform ``Simulation.stats()["ports"]``
        schema (``_ext_loc`` is the only engine-specific piece, so the
        fused engine inherits this as-is).  Nested by direction so a name
        serving BOTH directions reports each channel's own queue."""
        head = np.asarray(jax.device_get(state.queues.head))
        tail = np.asarray(jax.device_get(state.queues.tail))

        def rec(cid):
            didx, lid = self._ext_loc(cid)
            size = int((head[didx + (lid,)] - tail[didx + (lid,)])
                       % self.capacity)
            return {"occupancy": size, "credit": self.capacity - 1 - size}

        return {
            "tx": {n: rec(c) for n, c in self.graph.ext_in.items()},
            "rx": {n: rec(c) for n, c in self.graph.ext_out.items()},
        }

    def host_push(self, state: GraphState, name: str, payload):
        q2, ok = qmod.host_push(
            state.queues, self._ext_idx(self.graph.ext_in, name),
            jnp.asarray(payload, self.dtype),
        )
        return state.replace(queues=q2), ok

    def host_pop(self, state: GraphState, name: str):
        q2, front, valid = qmod.host_pop(
            state.queues, self._ext_idx(self.graph.ext_out, name)
        )
        return state.replace(queues=q2), front, valid

    def host_push_many(self, state: GraphState, name: str, payloads):
        payloads = jnp.asarray(payloads, self.dtype).reshape(-1, self.W)
        q2, n = qmod.host_push_many(
            state.queues, self._ext_idx(self.graph.ext_in, name), payloads
        )
        return state.replace(queues=q2), n

    def host_pop_many(self, state: GraphState, name: str, max_n: int):
        q2, pays, cnt = qmod.host_pop_many(
            state.queues, self._ext_idx(self.graph.ext_out, name), max_n
        )
        return state.replace(queues=q2), pays, cnt

    def push_external(self, state: GraphState, name: str, payload):
        warnings.warn(
            "push_external is deprecated; use the Simulation session's "
            "tx(name).send(...) (or engine.host_push)",
            DeprecationWarning, stacklevel=2,
        )
        return self.host_push(state, name, payload)

    def pop_external(self, state: GraphState, name: str):
        warnings.warn(
            "pop_external is deprecated; use the Simulation session's "
            "rx(name).recv() (or engine.host_pop)",
            DeprecationWarning, stacklevel=2,
        )
        return self.host_pop(state, name)


class GridEngine(GraphEngine):
    """Uniform R×C grid preset over GraphEngine (the paper's §IV-B manycore).

    cell: Block with ports in=(w_in, n_in), out=(e_out, s_out).
    R, C: global grid shape; mesh: 2-D Mesh with axes (axis_r, axis_c).
    K: cycles per epoch.

    The grid topology is lowered to the channel-graph IR by the vectorized
    ``ChannelGraph.grid`` builder and partitioned block-tile onto the device
    grid; the exchange-class coloring then reduces to exactly the historic
    east + south slab schedule.
    """

    def __init__(
        self,
        cell: Block,
        R: int,
        C: int,
        mesh: Mesh,
        K: int,
        payload_words: int = 2,
        capacity: int = qmod.DEFAULT_CAPACITY,
        dtype: Any = jnp.float32,
        axis_r: str = "gr",
        axis_c: str = "gc",
    ):
        Dr, Dc = mesh.shape[axis_r], mesh.shape[axis_c]
        if R % Dr or C % Dc:
            raise ValueError(f"grid {R}x{C} not divisible by device tile {Dr}x{Dc}")
        graph = ChannelGraph.grid(
            cell, R, C, payload_words=payload_words, dtype=dtype, capacity=capacity
        )
        super().__init__(
            graph, grid_partition(R, C, Dr, Dc), mesh, K=K, axes=(axis_r, axis_c)
        )
        self.cell = cell
        self.R, self.C = R, C
        self.Dr, self.Dc = Dr, Dc
        self.Tr, self.Tc = R // Dr, C // Dc

    def init(self, key: jax.Array, cell_params: PyTree) -> GraphState:
        """cell_params: pytree with leading (R, C) dims (global)."""
        flat = jax.tree.map(
            lambda x: jnp.reshape(jnp.asarray(x), (self.R * self.C,) + jnp.shape(x)[2:]),
            cell_params,
        )
        return super().init(key, group_params={0: flat})

    def _done_view(self, local):
        """``run_until`` predicates see the granule-local cell states,
        leaves (Tr*Tc, ...) — not the whole GraphState."""
        return local.block_states[0]

    def gather_cells(self, state: GraphState) -> PyTree:
        """Return cell states reassembled to global (R, C, ...) layout."""
        flat = self.gather_group(state, 0)
        return jax.tree.map(
            lambda x: x.reshape((self.R, self.C) + x.shape[1:]), flat
        )


def qmod_fill_at(q: qmod.QueueArray, idx: jax.Array, payloads: jax.Array, count: jax.Array) -> qmod.QueueArray:
    """Fill a subset of queues (rows ``idx``) of a QueueArray.

    payloads: (len(idx), max_n, W); count: (len(idx),).  Rows with
    ``count == 0`` are written back unchanged, so duplicate padding indices
    are harmless.
    """
    sub = qmod.QueueArray(
        buf=q.buf[idx], head=q.head[idx], tail=q.tail[idx], capacity=q.capacity
    )
    sub2 = qmod.fill(sub, payloads, count)
    return q.replace(
        buf=q.buf.at[idx].set(sub2.buf),
        head=q.head.at[idx].set(sub2.head),
    )
