"""Fused-epoch engine — the fused fast path for ANY channel graph
(§Perf; the generalization of ``fastgrid`` promised by DESIGN.md).

``GraphEngine`` interprets a granule cycle over deep SPSC queues: every
cycle peeks, steps, pushes and pops a ``(n_local, capacity, W)`` buffer —
~10 XLA ops of full-buffer traffic per simulated cycle.  This engine
lowers the same partitioned ``ChannelGraph`` to a *fused* per-granule
epoch instead:

  * **intra-granule channels are depth-1 elastic registers** — a
    (value, valid) pair per channel, the same legal latency-insensitive
    refinement ``fastgrid`` uses.  The per-cycle state shrinks from
    ``(n_local, capacity, W)`` to ``(n_reg, W)`` — 8-62x less data
    touched per cycle — and the ring arithmetic disappears;
  * **boundary + external channels stay real queues** (a small
    ``(n_q, capacity, W)`` array, typically ~10% of channels for a good
    partition), so the batched tier exchange, slab depths and credit
    protocol are *bit-identical* to ``GraphEngine`` — the two engines
    interoperate with the same sync schedule and the same partition tree;
  * the whole ``K_inner``-cycle tier-inner epoch executes as ONE fused
    body (``kernels.granule_step.epoch_loop``): a ``fori_loop`` of the
    cycle body inside the epoch's one jitted XLA computation.

Correctness contract (property-tested in ``tests/test_fused.py``):

  * handshaked results are **bit-exact** vs ``GraphEngine``/``NetworkSim``
    for any topology, any hierarchical partition and any per-tier rates —
    channel depth is latency the handshakes tolerate by construction;
  * with ``capacity=2`` the depth-1 registers are *cycle-identical* to the
    SPSC queues (a capacity-2 ring holds exactly one packet with the same
    pre-cycle snapshot semantics), so at K=(1,1) the fused engine is
    additionally cycle-accurate vs the single netlist;
  * the network must be deadlock-free at channel depth 1 (true for every
    latency-insensitive design shipped here; a design that *requires*
    deeper elastic buffering should run on ``GraphEngine``).

Select it with ``Network.build(engine="fused", ...)``; ``FusedEngine.grid``
is the uniform-grid preset (the ``GridEngine`` analogue).
"""
from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from . import queue as qmod
from ..obs import trace as _trace
from ..obs.registry import REGISTRY
from .block import Block
from .distributed import GraphEngine, _dealias_for_donation, _rank_within
from .graph import ChannelGraph, grid_partition
from .struct import pytree_dataclass
from ..kernels import granule_step

PyTree = Any


def _rows(pay: jax.Array, flags: jax.Array, fdt) -> Any:
    """Payload rows ``(n, W)`` and flag columns ``(n, k)`` as the port
    gathers move them: one ``(n, W + k)`` table when the payload dtype is
    the 32-bit flag dtype ``fdt``, else the payload beside an ``fdt`` flag
    array."""
    flags = flags.astype(fdt)
    if pay.dtype == flags.dtype:
        return jnp.concatenate([pay, flags], axis=-1)
    return pay, flags


def _take(rows: Any, idx: jax.Array, W: int) -> tuple:
    """``rows[idx]`` (a ``_rows`` table) split back into (payload, flag
    columns)."""
    if isinstance(rows, tuple):
        return rows[0][idx], rows[1][idx]
    g = rows[idx]
    return g[..., :W], g[..., W:]


@pytree_dataclass
class FusedTables:
    """Fused-engine lookup tables (device-varying, constant over time).

    Extends the ``GraphTables`` port/exchange tables with the *inverse*
    port maps: because channels are SPSC, every combined channel id has at
    most one local producer and one local consumer, so the per-cycle
    drive/commit step is static **gathers** (producer payload with its
    valid flag, consumer ready) instead of scatters — the XLA-CPU/TPU
    friendly formulation.
    """

    rx_idx: tuple  # per group: (dev..., n_slot, n_in) int32 combined ids
    tx_idx: tuple  # per group: (dev..., n_slot, n_out) int32 combined ids
    active: tuple  # per group: (dev..., n_slot) bool
    send_idx: tuple  # per tier: (dev..., S_t) int32 queue rows
    send_mask: tuple  # per tier: (dev..., S_t) bool
    recv_idx: tuple  # per tier: (dev..., S_t) int32 queue rows
    recv_mask: tuple  # per tier: (dev..., S_t) bool
    inv_tx: jax.Array  # (dev..., n_reg + n_q) int32 flat producer index
    inv_tx_mask: jax.Array  # (dev..., n_reg + n_q) bool
    inv_rx: jax.Array  # (dev..., n_reg + n_q) int32 flat consumer index
    inv_rx_mask: jax.Array  # (dev..., n_reg + n_q) bool
    # signature-batched exchange gather maps (see GraphTables.bat_fwd/
    # bat_rev) — empty tuples when the engine runs unbatched
    bat_fwd: tuple = ()
    bat_rev: tuple = ()


@pytree_dataclass
class FusedState:
    """All leaves carry leading device dims, sharded over the granule axes.

    ``reg_val``/``reg_v`` are the depth-1 intra-granule channel registers
    (ids 0/1 are the NULL_RX / NULL_TX sentinels: ``reg_v`` is pinned
    False there, so 0 never reads valid and 1 always looks free).
    ``queues`` holds only boundary egress/ingress + external channels.
    """

    reg_val: jax.Array  # (dev..., n_reg, W)
    reg_v: jax.Array  # (dev..., n_reg) bool
    queues: qmod.QueueArray  # (dev..., n_q, capacity, W)
    block_states: tuple  # per group: leaves (dev..., n_slot, ...)
    credits: tuple  # per tier: (dev..., S_t) int32 send credits
    cycle: jax.Array  # (dev...,) int32
    epoch: jax.Array  # (dev...,) int32
    tables: FusedTables


class FusedEngine(GraphEngine):
    """Fused-epoch distributed engine over an arbitrary partitioned graph.

    Accepts everything ``GraphEngine`` accepts.  ``batch_axes``
    (signature batching — see ``GraphEngine``) additionally unlocks the
    *resident multi-epoch program* here: every tier whose exchanges stay
    on-device (trailing batched tiers) folds into the fused epoch body,
    so one ``epoch_program`` — one traced XLA body — runs the whole
    K_outer x K_inner span with registers, queues and credits in its
    carry.
    """

    engine_kind = "fused"

    def __init__(
        self,
        graph: ChannelGraph,
        partition,
        mesh: Mesh,
        K: int = 1,
        axes: Sequence[str] | None = None,
        tiers: Sequence | None = None,
        *,
        batch_axes=None,
        overlap: Any = "auto",
    ):
        super().__init__(
            graph, partition, mesh, K=K, axes=axes, tiers=tiers,
            batch_axes=batch_axes, overlap=overlap,
        )
        self._build_fused_tables()
        # First tier index from which EVERY exchange is on-device (batched
        # classes with an empty real_perm; exchange-free tiers trivially
        # qualify): tiers [_resident_from:] run as ONE epoch_program — the
        # resident multi-epoch program.  Unbatched engines keep the plain
        # fold region (real_perm is None there, never ()).
        r = len(self.tiers)
        while r > 0 and all(
            cl.real_perm == () for cl in self.tier_classes[r - 1]
        ):
            r -= 1
        self._resident_from = min(r, self._fold_from)
        self._program_cache: dict[int, tuple] = {}
        self._t6_rows_cache: tuple | None = None

    # ---------------------------------------------------- uniform-grid preset
    @classmethod
    def grid(
        cls,
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
        **kw,
    ) -> "FusedEngine":
        """Uniform R×C grid preset — the fused ``GridEngine`` analogue."""
        Dr, Dc = mesh.shape[axis_r], mesh.shape[axis_c]
        graph = ChannelGraph.grid(
            cell, R, C, payload_words=payload_words, dtype=dtype,
            capacity=capacity,
        )
        return cls(
            graph, grid_partition(R, C, Dr, Dc), mesh, K=K,
            axes=(axis_r, axis_c), **kw,
        )

    # ------------------------------------------------- host-side compilation
    def _build_fused_tables(self) -> None:
        """Re-lower the granule-local queue id space onto registers + queues.

        Every (granule, local queue) entity becomes either a depth-1
        register (intra-granule channels) or a row of the small boundary
        queue array (egress/ingress/external).  Combined addressing keeps
        one flat id space for the port tables: ids ``[0, n_reg)`` are
        registers (0/1 the sentinels), ``[n_reg, n_reg + n_q)`` queues.
        """
        G = self.G
        g = self.graph
        ent_g, ent_c, ent_kind, lid = self._ent
        # external channels (host-facing) need real multi-packet queues
        ext = (g.chan_src[ent_c] < 0) | (g.chan_dst[ent_c] < 0)
        is_reg = (ent_kind == 0) & ~ext

        reg_rank, reg_counts = _rank_within(ent_g[is_reg], G)
        q_rank, q_counts = _rank_within(ent_g[~is_reg], G)
        self.n_reg = int(2 + (reg_counts.max() if reg_counts.size else 0))
        # queue row 0 is a scratch sentinel: exchange-table *padding* points
        # there, so masked slots can never scatter stale head/tail/buf
        # copies over a real channel's row (rows are written back whole)
        self.n_q = int(1 + (q_counts.max() if q_counts.size else 0))

        lid2comb = np.zeros((G, self.n_local), np.int64)
        lid2comb[:, 1] = 1
        lid2comb[ent_g[is_reg], lid[is_reg]] = 2 + reg_rank
        lid2comb[ent_g[~is_reg], lid[~is_reg]] = self.n_reg + 1 + q_rank
        self._lid2comb = lid2comb

        gi = np.arange(G)[:, None, None]
        self._rx_tables_f = [
            lid2comb[gi, rxm].astype(np.int32) for rxm in self._rx_tables
        ]
        self._tx_tables_f = [
            lid2comb[gi, txm].astype(np.int32) for txm in self._tx_tables
        ]

        # exchange tables move from local-queue-id space to queue-row space
        gq = np.arange(G)[:, None]

        def to_qrow(idx, mask):
            comb = lid2comb[gq, idx]
            assert (comb[mask] >= self.n_reg).all(), (
                "boundary channel lowered to a register"
            )
            return np.where(mask, comb - self.n_reg, 0).astype(np.int32)

        self._send_idx_f = [
            to_qrow(si, sm) for si, sm in zip(self._send_idx, self._send_mask)
        ]
        self._recv_idx_f = [
            to_qrow(ri, rm) for ri, rm in zip(self._recv_idx, self._recv_mask)
        ]

        # Inverse port maps: channel -> (unique) flat producer/consumer slot.
        # SPSC guarantees uniqueness for real channels; the sentinels (many
        # writers/readers, all dropped) and remotely-driven channels
        # (ingress: producer on the peer granule; egress: consumer there)
        # are masked out.
        n_tot = self.n_reg + self.n_q
        inv_tx = np.zeros((G, n_tot), np.int64)
        inv_tx_m = np.zeros((G, n_tot), bool)
        inv_rx = np.zeros((G, n_tot), np.int64)
        inv_rx_m = np.zeros((G, n_tot), bool)
        garange = np.arange(G)[:, None]
        off = 0
        for txm in self._tx_tables_f:
            _, n_slot, n_out = txm.shape
            flat = np.broadcast_to(
                off + np.arange(n_slot * n_out), (G, n_slot * n_out)
            )
            inv_tx[garange, txm.reshape(G, -1)] = flat
            inv_tx_m[garange, txm.reshape(G, -1)] = True
            off += n_slot * n_out
        off = 0
        for rxm in self._rx_tables_f:
            _, n_slot, n_in = rxm.shape
            flat = np.broadcast_to(
                off + np.arange(n_slot * n_in), (G, n_slot * n_in)
            )
            inv_rx[garange, rxm.reshape(G, -1)] = flat
            inv_rx_m[garange, rxm.reshape(G, -1)] = True
            off += n_slot * n_in
        inv_tx_m[:, :2] = False  # sentinels never drive/commit anything
        inv_rx_m[:, :2] = False
        self._inv_tx, self._inv_tx_mask = inv_tx.astype(np.int32), inv_tx_m
        self._inv_rx, self._inv_rx_mask = inv_rx.astype(np.int32), inv_rx_m
        if self._batched:
            self._build_flat_tables()

    def _build_flat_tables(self) -> None:
        """Flatten the batch of B same-device granules into ONE granule.

        ``jax.vmap`` of the cycle body turns every port-table lookup into a
        gather with a *batching dimension* — which XLA:CPU lowers to a
        scalar loop (measured ~5x off linear scaling).  Instead the batch
        is folded into the channel/slot axes: row r's registers live at
        ``r*n_reg + c``, its queue rows at ``B*n_reg + r*n_q + k``, its
        group slots at ``r*n_slot + s`` — and the cycle body runs
        UNVMAPPED on (B*n,)-shaped arrays with ordinary (fast) gathers.
        Rows need not share table *values*: each row's window gets its own
        granule's table, so heterogeneous same-signature members batch
        exactly.  Tier exchange keeps the (B, n_q) vmap layout — the local
        view bridges with free reshapes at tier boundaries only."""
        G, B = self.G, self.B
        G_real = G // B
        n_reg, n_q = self.n_reg, self.n_q

        def fmap(t: np.ndarray) -> np.ndarray:
            # (G_real, B, ...) combined ids -> flat combined ids
            r = np.arange(B).reshape((1, B) + (1,) * (t.ndim - 2))
            return np.where(
                t < n_reg, r * n_reg + t, B * n_reg + r * n_q + (t - n_reg)
            )

        def flat_ports(tbls):
            out = []
            for tbl in tbls:
                _, n_slot, n_p = tbl.shape
                t = fmap(tbl.reshape(G_real, B, n_slot, n_p))
                out.append(t.reshape(G_real, B * n_slot, n_p).astype(np.int32))
            return out

        self._rx_flat = flat_ports(self._rx_tables_f)
        self._tx_flat = flat_ports(self._tx_tables_f)

        # Inverse maps over the flat id space — same construction as the
        # per-granule inverses (SPSC uniqueness holds per row, and rows map
        # into disjoint flat windows), with every row's sentinels masked.
        n_tot = B * (n_reg + n_q)
        inv_tx = np.zeros((G_real, n_tot), np.int64)
        inv_tx_m = np.zeros((G_real, n_tot), bool)
        inv_rx = np.zeros((G_real, n_tot), np.int64)
        inv_rx_m = np.zeros((G_real, n_tot), bool)
        grange = np.arange(G_real)[:, None]
        off = 0
        for txm in self._tx_flat:
            _, n_fs, n_out = txm.shape
            flat = np.broadcast_to(
                off + np.arange(n_fs * n_out), (G_real, n_fs * n_out)
            )
            inv_tx[grange, txm.reshape(G_real, -1)] = flat
            inv_tx_m[grange, txm.reshape(G_real, -1)] = True
            off += n_fs * n_out
        off = 0
        for rxm in self._rx_flat:
            _, n_fs, n_in = rxm.shape
            flat = np.broadcast_to(
                off + np.arange(n_fs * n_in), (G_real, n_fs * n_in)
            )
            inv_rx[grange, rxm.reshape(G_real, -1)] = flat
            inv_rx_m[grange, rxm.reshape(G_real, -1)] = True
            off += n_fs * n_in
        sent = (np.arange(B)[:, None] * n_reg + np.array([0, 1])).ravel()
        inv_tx_m[:, sent] = False
        inv_rx_m[:, sent] = False
        self._inv_tx_flat = inv_tx.astype(np.int32)
        self._inv_tx_mask_flat = inv_tx_m
        self._inv_rx_flat = inv_rx.astype(np.int32)
        self._inv_rx_mask_flat = inv_rx_m

    def _dev_flat(self, arr: np.ndarray) -> jax.Array:
        """(G_real, ...) flat table -> (real_shape..., ...) device array."""
        return jnp.asarray(arr.reshape(self.real_shape + arr.shape[1:]))

    def tables(self) -> FusedTables:
        # Batched engines carry the FLAT port/inverse tables (real_shape
        # leading dims; the batch is folded into the slot/channel axes) —
        # exchange tables keep the per-granule (dev_shape) layout the tier
        # exchange consumes.
        if self._batched:
            port = dict(
                rx_idx=tuple(self._dev_flat(t) for t in self._rx_flat),
                tx_idx=tuple(self._dev_flat(t) for t in self._tx_flat),
                inv_tx=self._dev_flat(self._inv_tx_flat),
                inv_tx_mask=self._dev_flat(self._inv_tx_mask_flat),
                inv_rx=self._dev_flat(self._inv_rx_flat),
                inv_rx_mask=self._dev_flat(self._inv_rx_mask_flat),
            )
        else:
            port = dict(
                rx_idx=tuple(self._dev(t) for t in self._rx_tables_f),
                tx_idx=tuple(self._dev(t) for t in self._tx_tables_f),
                inv_tx=self._dev(self._inv_tx),
                inv_tx_mask=self._dev(self._inv_tx_mask),
                inv_rx=self._dev(self._inv_rx),
                inv_rx_mask=self._dev(self._inv_rx_mask),
            )
        return FusedTables(
            active=tuple(self._dev(t) for t in self._act_tables),
            send_idx=tuple(self._dev(t) for t in self._send_idx_f),
            send_mask=tuple(self._dev(t) for t in self._send_mask),
            recv_idx=tuple(self._dev(t) for t in self._recv_idx_f),
            recv_mask=tuple(self._dev(t) for t in self._recv_mask),
            bat_fwd=tuple(self._dev_bat(t) for t in self._bat_fwd),
            bat_rev=tuple(self._dev_bat(t) for t in self._bat_rev),
            **port,
        )

    # ------------------------------------------------------------------ init
    def init(self, key: jax.Array, group_params: dict[int, PyTree] | None = None) -> FusedState:
        """Initial state — same per-member block init as every other engine
        (bit-identical results), fused channel representation."""
        states = self._init_block_states(key, group_params)
        q = qmod.make_queues(self.n_q, self.W, self.capacity, self.dtype)
        queues = jax.tree.map(
            lambda x: jnp.broadcast_to(x, self.dev_shape + x.shape), q
        )
        cap1 = self.capacity - 1
        credits = tuple(
            jnp.full(self.dev_shape + (si.shape[1],), cap1, jnp.int32)
            for si in self._send_idx
        )
        return FusedState(
            reg_val=jnp.zeros(self.dev_shape + (self.n_reg, self.W), self.dtype),
            reg_v=jnp.zeros(self.dev_shape + (self.n_reg,), bool),
            queues=queues,
            block_states=tuple(states),
            credits=credits,
            cycle=jnp.zeros(self.dev_shape, jnp.int32),
            epoch=jnp.zeros(self.dev_shape, jnp.int32),
            tables=self.tables(),
        )

    # ----------------------------------------------------------- local cycle
    @staticmethod
    def _tables6(tb: FusedTables):
        """The (loop-invariant) table leaves the cycle body actually reads —
        passed to the epoch body as read-only consts, NOT loop carry."""
        return (
            tb.rx_idx, tb.tx_idx,
            tb.inv_tx, tb.inv_tx_mask, tb.inv_rx, tb.inv_rx_mask,
        )

    # ------------------------------------------------ flat-batch local views
    def _local_view(self, state: FusedState) -> FusedState:
        """Batched fused engines run the FLAT layout: the batch axes fold
        into the register/queue/slot axes (matching the flat port tables),
        so the cycle body runs unvmapped with ordinary gathers.  Exchange
        state (credits + exchange tables) keeps the (B, S_t) layout the
        tier exchange consumes; ``queues`` bridge by reshape at tier
        boundaries.  A scratch-only queue array ((B, 1) rows, no boundary
        channels anywhere) drops to its first row so the queue machinery
        still vanishes from the compiled body."""
        if not self._batched:
            return super()._local_view(state)
        B, nd, nd_r = self.B, self.nd, self.nd_real

        fold = lambda x: x.reshape(  # noqa: E731 — batch into first data dim
            (B * x.shape[nd],) + x.shape[nd + 1:]
        )
        bat = lambda x: x.reshape((B,) + x.shape[nd:])  # noqa: E731
        q_fold = fold if self.n_q > 1 else lambda x: bat(x)[0]
        tb = state.tables
        tables = tb.replace(
            rx_idx=jax.tree.map(lambda x: x.reshape(x.shape[nd_r:]), tb.rx_idx),
            tx_idx=jax.tree.map(lambda x: x.reshape(x.shape[nd_r:]), tb.tx_idx),
            inv_tx=tb.inv_tx.reshape(tb.inv_tx.shape[nd_r:]),
            inv_tx_mask=tb.inv_tx_mask.reshape(tb.inv_tx_mask.shape[nd_r:]),
            inv_rx=tb.inv_rx.reshape(tb.inv_rx.shape[nd_r:]),
            inv_rx_mask=tb.inv_rx_mask.reshape(tb.inv_rx_mask.shape[nd_r:]),
            active=jax.tree.map(fold, tb.active),
            send_idx=jax.tree.map(bat, tb.send_idx),
            send_mask=jax.tree.map(bat, tb.send_mask),
            recv_idx=jax.tree.map(bat, tb.recv_idx),
            recv_mask=jax.tree.map(bat, tb.recv_mask),
            bat_fwd=jax.tree.map(bat, tb.bat_fwd),
            bat_rev=jax.tree.map(bat, tb.bat_rev),
        )
        return state.replace(
            reg_val=fold(state.reg_val),
            reg_v=fold(state.reg_v),
            queues=jax.tree.map(q_fold, state.queues),
            block_states=jax.tree.map(fold, state.block_states),
            credits=jax.tree.map(bat, state.credits),
            cycle=bat(state.cycle)[0],  # lockstep rows share one counter
            epoch=bat(state.epoch),
            tables=tables,
        )

    def _global_view(self, local: FusedState) -> FusedState:
        if not self._batched:
            return super()._global_view(local)
        B, nd_r = self.B, self.nd_real
        lead = (1,) * nd_r + self.batch_shape

        unfold = lambda x: x.reshape(  # noqa: E731
            lead + (x.shape[0] // B,) + x.shape[1:]
        )
        unbat = lambda x: x.reshape(lead + x.shape[1:])  # noqa: E731
        q_unfold = (
            unfold if self.n_q > 1
            else lambda x: jnp.broadcast_to(x, lead + x.shape)
        )
        tb = local.tables
        readd = lambda x: x.reshape((1,) * nd_r + x.shape)  # noqa: E731
        tables = tb.replace(
            rx_idx=jax.tree.map(readd, tb.rx_idx),
            tx_idx=jax.tree.map(readd, tb.tx_idx),
            inv_tx=readd(tb.inv_tx),
            inv_tx_mask=readd(tb.inv_tx_mask),
            inv_rx=readd(tb.inv_rx),
            inv_rx_mask=readd(tb.inv_rx_mask),
            active=jax.tree.map(unfold, tb.active),
            send_idx=jax.tree.map(unbat, tb.send_idx),
            send_mask=jax.tree.map(unbat, tb.send_mask),
            recv_idx=jax.tree.map(unbat, tb.recv_idx),
            recv_mask=jax.tree.map(unbat, tb.recv_mask),
            bat_fwd=jax.tree.map(unbat, tb.bat_fwd),
            bat_rev=jax.tree.map(unbat, tb.bat_rev),
        )
        return local.replace(
            reg_val=unfold(local.reg_val),
            reg_v=unfold(local.reg_v),
            queues=jax.tree.map(q_unfold, local.queues),
            block_states=jax.tree.map(unfold, local.block_states),
            credits=jax.tree.map(unbat, local.credits),
            cycle=jnp.broadcast_to(local.cycle, self.dev_shape[:0] + lead),
            epoch=unbat(local.epoch),
            tables=tables,
        )

    def _q_batch_view(self, q):
        """Flat (B*n_q, ...) queue leaves -> (B, n_q, ...) for the exchange."""
        return jax.tree.map(
            lambda x: x.reshape((self.B, self.n_q) + x.shape[1:]), q
        )

    def _q_flat_view(self, q):
        return jax.tree.map(
            lambda x: x.reshape((self.B * self.n_q,) + x.shape[2:]), q
        )

    def _exchange_issue_batched(self, st: FusedState, t: int):
        """Exchange halves on the flat layout: reshape the queue block to
        the (B, n_q) batch layout, run the inherited slab staging, flatten
        back — free reshapes at tier boundaries only."""
        st2, pending = super()._exchange_issue_batched(
            st.replace(queues=self._q_batch_view(st.queues)), t
        )
        return st2.replace(queues=self._q_flat_view(st2.queues)), pending

    def _exchange_commit_batched(self, st: FusedState, t: int, pending):
        st2 = super()._exchange_commit_batched(
            st.replace(queues=self._q_batch_view(st.queues)), t, pending
        )
        return st2.replace(queues=self._q_flat_view(st2.queues))

    # ------------------------------------------------- per-row resident rows
    def _t6_row(self, r: int):
        """Row r's port/inverse tables in its OWN combined id space — the
        consts for one batch row's cycle body.  Per-row tables (not one
        shared set) so heterogeneous same-signature members batch exactly;
        XLA sees each row's tables as ordinary constants."""
        if self._t6_rows_cache is None:
            # host-side numpy, NOT jnp: the cache is built lazily — possibly
            # under a jit trace, where a jnp constant would be a tracer that
            # must not outlive that trace.  numpy consts embed per-trace.
            rows = []
            for g in range(self.B):
                rows.append((
                    tuple(np.asarray(t[g]) for t in self._rx_tables_f),
                    tuple(np.asarray(t[g]) for t in self._tx_tables_f),
                    np.asarray(self._inv_tx[g]),
                    np.asarray(self._inv_tx_mask[g]),
                    np.asarray(self._inv_rx[g]),
                    np.asarray(self._inv_rx_mask[g]),
                ))
            self._t6_rows_cache = tuple(rows)
        return self._t6_rows_cache[r]

    @jax.named_scope(_trace.ROWS_SPLIT)
    def _rows_split(self, st: FusedState) -> tuple:
        """Flat local state -> per-row cycle carries.

        Each row's registers/queues/block slots become SEPARATE buffers:
        XLA:CPU keeps a <=granule-sized working set cache-resident through
        a whole exchange-free cycle window, where the fused flat arrays
        fall off a sharp elementwise-cost cliff (measured ~4x above ~512
        rows on one core).  Split once per epoch, not per cycle."""
        B, n_reg, n_q = self.B, self.n_reg, self.n_q
        rows = []
        for r in range(B):
            q_r = (
                jax.tree.map(
                    lambda x: x[r * n_q:(r + 1) * n_q], st.queues
                )
                if n_q > 1 else st.queues  # shared scratch row: never read
            )
            bst_r = tuple(
                jax.tree.map(
                    lambda x, nsg=jax.tree.leaves(bs)[0].shape[0] // B:
                        x[r * nsg:(r + 1) * nsg],
                    bs,
                )
                for bs in st.block_states
            )
            rows.append((
                st.reg_val[r * n_reg:(r + 1) * n_reg],
                st.reg_v[r * n_reg:(r + 1) * n_reg],
                q_r,
                bst_r,
                st.cycle,
            ))
        return tuple(rows)

    @jax.named_scope(_trace.ROWS_JOIN)
    def _rows_join(self, st: FusedState, rows: tuple, credits) -> FusedState:
        """Per-row carries -> the flat local layout (inverse of
        ``_rows_split``; rows run in lockstep so row 0's cycle counter
        stands for all)."""
        cat = lambda xs: jnp.concatenate(xs, axis=0)  # noqa: E731
        queues = (
            jax.tree.map(lambda *xs: cat(xs), *(r[2] for r in rows))
            if self.n_q > 1 else rows[0][2]
        )
        return st.replace(
            reg_val=cat([r[0] for r in rows]),
            reg_v=cat([r[1] for r in rows]),
            queues=queues,
            block_states=tuple(
                jax.tree.map(lambda *xs: cat(xs), *(r[3][g] for r in rows))
                for g in range(len(st.block_states))
            ),
            cycle=rows[0][4],
            credits=credits,
        )

    @jax.named_scope(_trace.DRAIN)
    def _rows_exchange_issue(self, rows: tuple, credits, t: int, tb):
        """ISSUE half of the per-row on-device exchange: credit-bounded
        ``stage_drain`` per row, one tiny (B, S_t, E_t, W) slab moved by
        the ``bat_fwd`` batch-row gather.  Only the staged slab is ever
        materialized across rows — the queue buffers stay per-row."""
        sidx, smask = tb.send_idx[t], tb.send_mask[t]  # (B, S_t)
        rmask = tb.recv_mask[t]
        bfw = tb.bat_fwd[t]
        limit = jnp.where(smask, credits[t], 0)
        new_rows, slabs, cnts = [], [], []
        for r in range(self.B):
            q2, slab, cnt = qmod.stage_drain(
                rows[r][2], sidx[r], self.E_tiers[t], limit=limit[r]
            )
            rv, rb, _, bs, cyc = rows[r]
            new_rows.append((rv, rb, q2, bs, cyc))
            slabs.append(slab)
            cnts.append(cnt)
        slab = jnp.stack(slabs)  # (B, S_t, E_t, W)
        cnt = jnp.stack(cnts)    # (B, S_t)
        slab_in = self._bat_move(slab, bfw, t)
        cnt_in = jnp.where(rmask, self._bat_move(cnt, bfw, t), 0)
        return tuple(new_rows), (slab_in, cnt_in)

    @jax.named_scope(_trace.FILL)
    def _rows_exchange_commit(self, rows: tuple, credits, t: int, tb,
                              pending):
        """COMMIT half: ``stage_fill`` per row + the ``bat_rev`` credit
        return."""
        ridx, rmask = tb.recv_idx[t], tb.recv_mask[t]
        slab_in, cnt_in = pending
        new_rows, frees = [], []
        for r in range(self.B):
            q3 = qmod.stage_fill(rows[r][2], ridx[r], slab_in[r], cnt_in[r])
            rv, rb, _, bs, cyc = rows[r]
            new_rows.append((rv, rb, q3, bs, cyc))
            frees.append(qmod.free(q3))
        cred = jnp.where(
            rmask, jnp.take_along_axis(jnp.stack(frees), ridx, axis=1), 0
        )
        credits = (credits[:t] + (self._bat_move(cred, tb.bat_rev[t], t),)
                   + credits[t + 1:])
        return tuple(new_rows), credits

    def _rows_exchange(self, rows: tuple, credits, t: int, tb) -> tuple:
        """Tier t's on-device exchange on per-row queues — literally
        commit∘issue, so the serial and overlapped schedules share every
        instruction and differ only in ordering."""
        rows, pending = self._rows_exchange_issue(rows, credits, t, tb)
        return self._rows_exchange_commit(rows, credits, t, tb, pending)

    def _local_cycle(self, st: FusedState) -> FusedState:
        """One granule-local cycle on registers + boundary queues."""
        carry = (st.reg_val, st.reg_v, st.queues, st.block_states, st.cycle)
        out = self._cycle_body(carry, self._tables6(st.tables))
        return st.replace(
            reg_val=out[0], reg_v=out[1], queues=out[2],
            block_states=out[3], cycle=out[4],
        )

    def _cycle_body(self, carry, tables6):
        """One granule-local cycle on registers + boundary queues.

        Same pre-cycle snapshot semantics as ``NetworkSim.step`` /
        ``GraphEngine._local_cycle`` — fronts, valids and readies are all
        taken before any block steps — with channel storage split between
        the register file and the small boundary queue array.  Pure in
        its explicit arguments (no captured engine state), so one body
        serves ``epoch_loop``, ``epoch_program`` and the per-row walk.
        """
        reg_val_in, reg_v_in, q, block_states, cycle = carry
        rx_tbl, tx_tbl, inv_tx, inv_tx_mask, inv_rx, inv_rx_mask = tables6
        # Dims come from the carry, not the engine: the SAME body then serves
        # the per-granule layout (n_reg rows) and the signature-batched flat
        # layout (B*n_reg rows with per-row offset tables) unchanged.
        n_reg, W = reg_val_in.shape
        # A 1-row queue array is only the scratch sentinel: this granule set
        # has no boundary/external channels, so the queue machinery vanishes
        # from the compiled body entirely (host-static decision).
        have_q = q.buf.shape[0] > 1
        # No flag is gathered as ``pred``: XLA:TPU packs four to a 32-bit
        # word and gathers them one element at a time.  Valid and ready
        # flags move as 0/1 in a 32-bit dtype instead — the payload's own,
        # as extra columns of the payload rows, where that is 32-bit.
        fdt = jnp.dtype(self.dtype if jnp.dtype(self.dtype).itemsize == 4
                        else jnp.int32)

        with jax.named_scope(_trace.READ):
            if have_q:
                qsize = (q.head - q.tail) % q.capacity
                qfronts = jnp.take_along_axis(
                    q.buf, q.tail[:, None, None], axis=1
                )[:, 0, :]
                # combined channel views: registers first, queue rows after
                fronts = jnp.concatenate([reg_val_in, qfronts], axis=0)
                valids = jnp.concatenate([reg_v_in, qsize > 0], axis=0)
                readies = jnp.concatenate(
                    [~reg_v_in, qsize < q.capacity - 1], axis=0)
            else:
                fronts, valids, readies = reg_val_in, reg_v_in, ~reg_v_in
            # one per-channel table: [fronts | valid | ready]
            chans = _rows(fronts, jnp.stack([valids, readies], 1), fdt)

        new_states = []
        pay_parts, val_parts, rr_parts = [], [], []
        for gi, grp in enumerate(self.graph.groups):
            blk = grp.block
            with jax.named_scope(_trace.READ):
                rxm, txm = rx_tbl[gi], tx_tbl[gi]
                # (n_slot, n_in, W) fronts and their valids: one gather
                f_all, fl = _take(chans, rxm, W)
                v_all = fl[..., 0] != 0
                r_all = _take(chans, txm, W)[1][..., 1] != 0
                rx = {
                    port: (f_all[:, p], v_all[:, p])
                    for p, port in enumerate(blk.in_ports)
                }
                tx_ready = {port: r_all[:, p]
                            for p, port in enumerate(blk.out_ports)}
            bst = block_states[gi]
            with jax.named_scope(_trace.STEP):
                new_st, rx_ready, tx = jax.vmap(blk.step)(bst, rx, tx_ready)

                if blk.clock_divider > 1:
                    en = (cycle % blk.clock_divider) == 0
                    new_st = jax.tree.map(lambda n, o: jnp.where(en, n, o),
                                          new_st, bst)
                    rx_ready = {k: v & en for k, v in rx_ready.items()}
                    tx = {k: (p, v & en) for k, (p, v) in tx.items()}
            new_states.append(new_st)

            with jax.named_scope(_trace.WRITE):
                # flags leave the block as 32-bit per port, before the
                # stack, so no ``pred`` array is relaid out either
                if blk.in_ports:
                    rr_parts.append(
                        jnp.stack([rx_ready[p].astype(fdt)
                                   for p in blk.in_ports], 1)
                        .reshape(-1)
                    )
                if blk.out_ports:
                    pay_parts.append(
                        jnp.stack([tx[p][0] for p in blk.out_ports], 1)
                        .reshape(-1, W).astype(self.dtype)
                    )
                    val_parts.append(
                        jnp.stack([tx[p][1].astype(fdt)
                                   for p in blk.out_ports], 1)
                        .reshape(-1, 1)
                    )

        def _cat(parts, empty):
            if not parts:
                return empty
            return parts[0] if len(parts) == 1 else jnp.concatenate(parts, 0)

        with jax.named_scope(_trace.WRITE):
            # [payload | valid] per producer port; ready per consumer port
            txs = _rows(
                _cat(pay_parts, jnp.zeros((1, W), self.dtype)),
                _cat(val_parts, jnp.zeros((1, 1), fdt)), fdt,
            )
            rr_all = _cat(rr_parts, jnp.zeros((1,), fdt))

            # SPSC: the static inverse maps pick each channel's unique
            # producer and consumer — gathers only, no scatters anywhere in
            # the cycle.  Gather straight into the register/queue halves (no
            # full-width intermediate to slice).
            inv_tx_r, inv_rx_r = inv_tx[:n_reg], inv_rx[:n_reg]

            # registers: depth-1 elastic commit (push into empty, pop drains)
            pay_r, val_r = _take(txs, inv_tx_r, W)
            do_push_r = (val_r[:, 0] != 0) & inv_tx_mask[:n_reg] & ~reg_v_in
            do_pop_r = (rr_all[inv_rx_r] != 0) & inv_rx_mask[:n_reg] & reg_v_in
            reg_val = jnp.where(do_push_r[:, None], pay_r, reg_val_in)
            reg_v = (reg_v_in & ~do_pop_r) | do_push_r

            if have_q:
                # boundary/external queues: the standard ring handshake
                pay_q, val_q = _take(txs, inv_tx[n_reg:], W)
                q2, _, _ = qmod.cycle(
                    q,
                    pay_q,
                    (val_q[:, 0] != 0) & inv_tx_mask[n_reg:],
                    (rr_all[inv_rx[n_reg:]] != 0) & inv_rx_mask[n_reg:],
                )
            else:
                q2 = q
            return (reg_val, reg_v, q2, tuple(new_states), cycle + 1)

    # ------------------------------------------------------------ fused epoch
    def _inner_cycles(self, st: FusedState, K: int) -> FusedState:
        """The K_inner hot loop as ONE fused epoch body (the tentpole).

        Only the mutating leaves ride the loop carry; port tables enter as
        read-only consts, and the exchange tables/credits/epoch counter
        never enter the loop at all.  Batched engines step the whole
        granule batch in this same single dispatch (flat layout).
        """
        carry = (st.reg_val, st.reg_v, st.queues, st.block_states, st.cycle)
        # Batched engines run the same UNVMAPPED body on the flat layout —
        # one dispatch per epoch AND plain gathers (vmap would lower every
        # table lookup to a batched gather, a scalar loop on XLA:CPU).
        out = granule_step.epoch_loop(
            self._cycle_body, carry, K,
            consts=self._tables6(st.tables),
        )
        return st.replace(
            reg_val=out[0], reg_v=out[1], queues=out[2],
            block_states=out[3], cycle=out[4],
        )

    # ------------------------------------------- resident multi-epoch program
    def _resident_program(self, t0: int) -> tuple:
        """The ("C", n)/("X", t) op list realizing tiers [t0:] — the same
        recursion as ``_tier_round``, flattened so the whole span executes
        as ONE ``epoch_program`` body (adjacent cycle blocks merged,
        exchange-free tiers elided).  Under ``overlap`` every boundary's
        run of ("X", t) ops is rewritten to all-issues-then-all-commits
        (``granule_step.overlap_program``) so transfers are in flight
        across the sync point."""
        if t0 not in self._program_cache:

            def prog(t):
                if t >= self._fold_from:
                    return [("C", int(np.prod(self.K_tiers[t:])))]
                if t == len(self.tiers) - 1:
                    ops = [("C", self.tiers[t].K)]
                else:
                    ops = prog(t + 1) * self.tiers[t].K
                if self.tier_classes[t]:
                    ops = ops + [("X", t)]
                return ops

            merged: list[tuple] = []
            for op, arg in prog(t0):
                if op == "C" and merged and merged[-1][0] == "C":
                    merged[-1] = ("C", merged[-1][1] + arg)
                else:
                    merged.append((op, arg))
            program = tuple(merged)
            if self.overlap:
                program = granule_step.overlap_program(program)
            self._program_cache[t0] = program
        return self._program_cache[t0]

    def _resident_cycle(self, carry, consts):
        """Cycle body on the resident carry (the 5-leaf cycle carry plus
        the per-tier credit tuple, which only exchanges touch)."""
        return self._cycle_body(carry[:5], consts[0]) + (carry[5],)

    @jax.named_scope(_trace.DRAIN)
    def _resident_exchange_issue(self, carry, t: int, consts):
        """ISSUE half of tier t's exchange *inside* the resident body.

        Every class of a resident tier has an empty ``real_perm`` (that is
        what admitted it), so the issue is slab staging on the local fused
        queue rows: credit-bounded ``stage_drain`` into the
        (B, S_t, E_t, W) slab + the ``bat_fwd`` batch-row gather.  The
        returned pending pair rides ``epoch_program``'s walk as plain
        values until the tier's commit."""
        reg_val, reg_v, q, block_states, cycle, credits = carry
        sidx, smask, _, rmask, bfw, _ = (x[t] for x in consts[1])
        q = self._q_batch_view(q)  # flat rows -> (B, n_q) for the slab move
        limit = jnp.where(smask, credits[t], 0)
        q, slab, cnt = jax.vmap(
            lambda qb, si, lim: qmod.stage_drain(
                qb, si, self.E_tiers[t], limit=lim
            )
        )(q, sidx, limit)
        slab_in = self._bat_move(slab, bfw, t)
        cnt_in = jnp.where(rmask, self._bat_move(cnt, bfw, t), 0)
        carry = (reg_val, reg_v, self._q_flat_view(q), block_states, cycle,
                 credits)
        return carry, (slab_in, cnt_in)

    @jax.named_scope(_trace.FILL)
    def _resident_exchange_commit(self, carry, t: int, pending, consts):
        """COMMIT half: ``stage_fill`` the in-flight slab + the ``bat_rev``
        credit return."""
        reg_val, reg_v, q, block_states, cycle, credits = carry
        _, _, ridx, rmask, _, brv = (x[t] for x in consts[1])
        slab_in, cnt_in = pending
        q = self._q_batch_view(q)
        q = jax.vmap(qmod.stage_fill)(q, ridx, slab_in, cnt_in)
        cred = jnp.where(
            rmask, jnp.take_along_axis(qmod.free(q), ridx, axis=1), 0
        )
        credits = credits[:t] + (self._bat_move(cred, brv, t),) + credits[t + 1:]
        return (reg_val, reg_v, self._q_flat_view(q), block_states, cycle,
                credits)

    def _resident_exchange(self, carry, t: int, consts):
        """Tier t's serial exchange inside the resident body — commit∘issue
        (see the halves above), run between the program's epoch loops."""
        carry, pending = self._resident_exchange_issue(carry, t, consts)
        return self._resident_exchange_commit(carry, t, pending, consts)

    def _rows_program(self, rows: tuple, credits, tb, t0: int) -> tuple:
        """Walk tiers [t0:] on the per-row carries: each ("C", n) op runs
        every row's n-cycle window as its own ``epoch_loop`` over that
        row's private buffers, each ("X", t) op is ``_rows_exchange``'s
        slab staging (split into the ("XI", t)/("XC", t) halves under
        ``overlap``).  Rows are independent between exchanges, so running
        row r's whole window before row r+1 is legal — and keeps one
        granule's working set cache-resident per window."""
        pending: dict[int, tuple] = {}
        for op, arg in self._resident_program(t0):
            if op == "C":
                rows = tuple(
                    granule_step.epoch_loop(
                        self._cycle_body, c_r, arg,
                        consts=self._t6_row(r),
                    )
                    for r, c_r in enumerate(rows)
                )
            elif op == "XI":
                rows, pending[arg] = self._rows_exchange_issue(
                    rows, credits, arg, tb
                )
            elif op == "XC":
                rows, credits = self._rows_exchange_commit(
                    rows, credits, arg, tb, pending.pop(arg)
                )
            else:
                rows, credits = self._rows_exchange(rows, credits, arg, tb)
        assert not pending, f"uncommitted exchanges: {sorted(pending)}"
        return rows, credits

    def run_epochs(
        self, state: FusedState, n_epochs: int, *, donate: bool = True
    ) -> FusedState:
        """Pure-batch engines scan whole epochs on the per-row carries —
        split once per ``run_epochs`` call, not once per epoch.  Keeping
        the row structure in the scan carry lets XLA update each row's
        queue buffers in place across every epoch instead of copying the
        flat state apart and back together ``n_epochs`` times.  Mixed
        real+batch and unbatched engines take the inherited path."""
        if not (self._batched and not self.real_axes):
            return super().run_epochs(state, n_epochs, donate=donate)
        key = ("run_rows", n_epochs, donate)
        if key not in self._jit_cache:
            REGISTRY.inc("fused.compile.count")

            def run(state):
                local = self._local_view(state)
                tb = local.tables

                def one(carry, _):
                    rows, credits, epoch = carry
                    rows, credits = self._rows_program(rows, credits, tb, 0)
                    return (rows, credits, epoch + 1), None

                carry = (self._rows_split(local), local.credits, local.epoch)
                rows, credits, epoch = jax.lax.scan(
                    one, carry, None, length=n_epochs
                )[0]
                out = self._rows_join(local, rows, credits)
                return self._global_view(out.replace(epoch=epoch))

            self._jit_cache[key] = jax.jit(
                self._wrap(run), donate_argnums=(0,) if donate else ()
            )
        if donate:
            state = _dealias_for_donation(state)
        REGISTRY.inc("fused.dispatch.count")
        REGISTRY.inc("fused.epochs", float(n_epochs))
        return self._jit_cache[key](state)

    def _tier_round(self, st: FusedState, t: int) -> FusedState:
        """Batched engines run every all-on-device span of the tier tree
        resident — registers, queues and credits stay in one traced body's
        carry across its inner epochs and tier boundaries — falling back
        to the inherited loop-and-exchange recursion above
        ``_resident_from``.

        Pure-batch engines (every mesh axis a batch axis) take the per-row
        blocked walk: each ("C", n) op runs every row's n-cycle window as
        its own ``epoch_loop`` over that row's private buffers (see
        ``_rows_split``), and each ("X", t) op is the slab exchange of
        ``_rows_exchange``.  Mixed real+batch engines keep the flat-carry
        ``epoch_program`` (one body under shard_map)."""
        if not (self._batched and t >= self._resident_from):
            return super()._tier_round(st, t)
        tb = st.tables
        if not self.real_axes:
            rows, credits = self._rows_program(
                self._rows_split(st), st.credits, tb, t
            )
            return self._rows_join(st, rows, credits)
        carry = (
            st.reg_val, st.reg_v, st.queues, st.block_states, st.cycle,
            st.credits,
        )
        consts = (
            self._tables6(tb),
            (tb.send_idx, tb.send_mask, tb.recv_idx, tb.recv_mask,
             tb.bat_fwd, tb.bat_rev),
        )
        out = granule_step.epoch_program(
            self._resident_cycle, carry, self._resident_program(t),
            exchange_fn=self._resident_exchange,
            issue_fn=self._resident_exchange_issue,
            commit_fn=self._resident_exchange_commit,
            consts=consts,
        )
        return st.replace(
            reg_val=out[0], reg_v=out[1], queues=out[2],
            block_states=out[3], cycle=out[4], credits=out[5],
        )

    def _pend_tiers(self, t0: int) -> tuple:
        """Resident spans commit their own split exchanges inside the
        ``epoch_program`` (its op list pairs every issue with a commit
        before the program ends — ``validate_program``), so they
        contribute nothing to the caller's pending chain."""
        if self._batched and t0 >= self._resident_from:
            return ()
        return super()._pend_tiers(t0)

    def _round_split(self, st: FusedState, t: int):
        """The overlapped round: resident spans run their (overlapped)
        op-list program as one body — split ops committed internally —
        and the tiers above take the inherited split recursion."""
        if self._batched and t >= self._resident_from:
            return self._tier_round(st, t), ()
        return super()._round_split(st, t)

    # ------------------------------------------------- host-side external I/O
    def _ext_loc(self, cid: int) -> tuple[tuple[int, ...], int]:
        gid = int(self._chan_owner[cid])
        didx = tuple(int(i) for i in np.unravel_index(gid, self.dev_shape))
        lid = int(max(self._rx_local[cid], self._tx_local[cid]))
        return didx, int(self._lid2comb[gid, lid]) - self.n_reg
