"""Per-granule worker process — a free-running prebuilt granule simulator
(paper §III-F / §IV-B; DESIGN.md §Runtime).

Each worker owns ONE granule of a partitioned ``ChannelGraph``: the
granule-local queues and block states, stepped by exactly the same
``granule_local_cycle`` body the shard_map engines use.  The worker
free-runs epochs — ``K_inner`` local cycles, then per-tier exchanges over
shared-memory rings — gated only by its own ingress/egress credits.
There is no global barrier anywhere: a worker waits only when one of ITS
channels' rings is empty (producer behind) or full (consumer behind), so
two granules drift apart by up to their connecting channel's tier period,
and unconnected granules drift arbitrarily (the paper's "simulations run
as fast as they can" free-running model, with the staleness bound made
explicit).

**Prebuilt-simulator cache** (the paper's flat-build-time claim): the
epoch stepper is AOT-compiled — ``jit(...).lower().compile()`` — from a
state *template* whose port/exchange tables are runtime inputs, so the
compiled artifact depends only on the granule's shape signature
(``PartitionLowering.granule_signature``): block kinds/configs, slot
counts, queue counts, tier rates.  N instances of the same block shape
therefore trace to the SAME jaxpr, the launcher compiles each distinct
signature once, and every worker's own compile is a hit in the JAX
persistent compilation cache — build time grows with *unique* granule
shapes, not with instance count.

Exchange protocol per boundary channel (bit-identical to the engines'
credit protocol, DESIGN.md §3): at the channel's tier cadence the sender
pops one credit record (pre-seeded with capacity-1 at reset), drains its
egress queue bounded by ``min(E_t, credit)``, and pushes one slab record;
the receiver pops one slab record per exchange, fills its ingress queue,
and pushes back its post-fill free space as the next credit.  One slab
record per exchange per channel — even when empty — is what makes the
free-running schedule deterministic and the traffic bit-identical to the
lockstep engines.
"""
from __future__ import annotations

import dataclasses
import os
import sys
import time
import traceback
from typing import Any

import numpy as np

from ..core import queue as qmod
from ..core.compile_cache import enable_compile_cache
from ..core.struct import pytree_dataclass
from ..obs import telemetry as _telem
from ..obs.trace import now_ns
from .fault_tolerance import (
    OP_CREDIT_POP, OP_CREDIT_PUSH, OP_SLAB_POP, OP_SLAB_PUSH, encode_blocked,
)
from .shmem import RingCorruptionError, RingTimeout, ShmRing, slab_slot_bytes

PyTree = Any


# ---------------------------------------------------------------- spec
@dataclasses.dataclass
class GroupSpec:
    """One block group's granule-local slice (all numpy, picklable)."""

    block: Any  # the Block instance (pickled by reference to its module)
    n_members: int  # GLOBAL member count (key-split shape, engine-invariant)
    n_slot: int
    member_of: np.ndarray  # (n_slot,) global member index (0 on padding)
    active: np.ndarray  # (n_slot,) bool
    rx_idx: np.ndarray  # (n_slot, n_in) local queue ids
    tx_idx: np.ndarray  # (n_slot, n_out)
    params_local: PyTree | None  # pre-sliced per-slot params (n_slot leading)


@dataclasses.dataclass
class TierSpec:
    """One tier's boundary channels as seen by this granule."""

    K: int
    E: int  # slab depth = min(period, capacity-1)
    egress_chans: tuple[int, ...]  # channel ids, canonical order
    egress_lqids: np.ndarray  # (n_e,) local queue ids
    ingress_chans: tuple[int, ...]
    ingress_lqids: np.ndarray


@dataclasses.dataclass
class GranuleSpec:
    """Everything a worker needs to build and free-run its granule."""

    granule: int
    signature: str
    payload_words: int
    capacity: int
    dtype: str
    n_local: int
    groups: list[GroupSpec]
    tiers: list[TierSpec]  # outermost first
    ext_ports: list[tuple[str, int, int, bool]]  # (name, chan, lqid, is_in)
    ring_prefix: str
    ring_depth: int
    timeout: float
    overlap: bool = False  # split issue/commit exchange (send-early/receive-late)

    @property
    def cycles_per_epoch(self) -> int:
        out = 1
        for t in self.tiers:
            out *= t.K
        return out


@dataclasses.dataclass
class BatchSpec:
    """``nb`` same-signature granules stepped as ONE leading-axis batch
    (``ProcsEngine(batch_signatures=True)``).

    All member specs share ``PartitionLowering.granule_signature`` — same
    block shapes, per-tier egress/ingress channel *counts* and ext-port
    count — so their epoch programs are identical and their per-granule
    tables stack into (nb, ...) arrays consumed by one vmapped stepper.
    The rings stay per channel; only the dispatch is batched.
    """

    members: tuple[int, ...]
    specs: list[GranuleSpec]

    @property
    def signature(self) -> str:
        return self.specs[0].signature


def data_ring_name(prefix: str, chan: int) -> str:
    return f"{prefix}d{chan}"


def credit_ring_name(prefix: str, chan: int) -> str:
    return f"{prefix}c{chan}"


def ext_ring_name(prefix: str, chan: int) -> str:
    return f"{prefix}x{chan}"


def heartbeat_name(prefix: str) -> str:
    return f"{prefix}hb"


# ------------------------------------------------------------- granule sim
class GranuleSim:
    """Pure compute half of a worker: granule state + AOT-compiled steppers.

    Constructed by workers AND by the launcher's prebuild pass (one
    instance per distinct signature) — both compile the same functions
    from the same templates, which is what makes the persistent-cache
    keying line up.
    """

    def __init__(self, spec: GranuleSpec):
        import jax
        import jax.numpy as jnp

        self.spec = spec
        self.jax, self.jnp = jax, jnp
        self.np_dtype = np.dtype(spec.dtype)
        self.dtype = jnp.dtype(self.np_dtype)
        self.W = spec.payload_words
        self.capacity = spec.capacity
        self.n_local = spec.n_local
        self.K_tiers = tuple(t.K for t in spec.tiers)
        self.program = self._build_program()
        self._compiled: dict[Any, Any] = {}

    # ---------------------------------------------------------- the program
    def _build_program(self) -> list[tuple[str, int]]:
        """Flatten the nested tier rounds into ("C", n_cycles) / ("X", tier)
        ops — the same schedule as ``GraphEngine._tier_round``, with
        trailing tiers that have no channels ON THIS GRANULE folded into
        one contiguous cycle block (pure local compute chunks bigger).

        With ``spec.overlap`` the serial exchanges are rewritten to split
        ("XI", t) / ("XC", t) phases by ``granule_step.overlap_program`` —
        at a multi-tier boundary all issues precede all commits, so every
        outgoing slab is pushed before the worker blocks on any incoming
        one (send-early/receive-late).  The compiled stepper set is
        unchanged: XI reuses the drain stepper, XC the fill stepper."""
        tiers = self.spec.tiers
        fold_from = len(tiers)
        while fold_from > 0 and not (
            tiers[fold_from - 1].egress_chans or tiers[fold_from - 1].ingress_chans
        ):
            fold_from -= 1

        def tier_round(t: int) -> list[tuple[str, int]]:
            if t >= fold_from:
                n = 1
                for tt in tiers[t:]:
                    n *= tt.K
                return [("C", n)] if n else []
            ops: list[tuple[str, int]] = []
            if t == len(tiers) - 1:
                ops.append(("C", tiers[t].K))
            else:
                for _ in range(tiers[t].K):
                    ops.extend(tier_round(t + 1))
            ops.append(("X", t))
            return ops

        program = tier_round(0)
        if self.spec.overlap:
            from ..kernels.granule_step import overlap_program

            program = overlap_program(program)
        return program

    # ------------------------------------------------------------- templates
    def init(self, key_data: np.ndarray,
             group_params: list[PyTree | None] | None = None):
        """Initial WorkerState — the same per-member key derivation as
        ``NetworkSim.init`` / ``GraphEngine._init_block_states`` (fold_in
        group index, split over GLOBAL member count, slice local members),
        so per-member init is bit-identical across all five engines."""
        jax, jnp = self.jax, self.jnp
        key = jax.random.wrap_key_data(jnp.asarray(key_data))
        states = []
        for gi, gs in enumerate(self.spec.groups):
            blk = gs.block
            params = gs.params_local
            if group_params is not None and group_params[gi] is not None:
                params = group_params[gi]
            keys = jax.random.split(jax.random.fold_in(key, gi), gs.n_members)
            keys_l = keys[jnp.asarray(gs.member_of)]
            init = jax.vmap(blk.init_state)
            if params is not None:
                params_l = jax.tree.map(jnp.asarray, params)
                st = init(keys_l, params_l)
            else:
                st = init(keys_l)
            states.append(st)
        queues = qmod.make_queues(
            self.n_local, self.W, self.capacity, self.dtype
        )
        from ..core.distributed import _dealias_for_donation

        # block init_state may legitimately reuse one array for several
        # fields; every compiled stepper donates its input, so aliased
        # buffers must be split once here (same rule as the engines)
        return _dealias_for_donation(WorkerState(
            queues=queues,
            block_states=tuple(states),
            cycle=jnp.zeros((), jnp.int32),
            epoch=jnp.zeros((), jnp.int32),
            tables=self.tables(),
        ))

    def tables(self):
        """Granule-local tables as a GraphTables pytree (runtime INPUTS to
        the compiled steppers — the prebuilt-cache property)."""
        from ..core.distributed import GraphTables

        jnp = self.jnp
        return GraphTables(
            rx_idx=tuple(jnp.asarray(g.rx_idx, jnp.int32) for g in self.spec.groups),
            tx_idx=tuple(jnp.asarray(g.tx_idx, jnp.int32) for g in self.spec.groups),
            active=tuple(jnp.asarray(g.active) for g in self.spec.groups),
            send_idx=tuple(jnp.asarray(t.egress_lqids, jnp.int32)
                           for t in self.spec.tiers),
            send_mask=tuple(jnp.ones((len(t.egress_chans),), bool)
                            for t in self.spec.tiers),
            recv_idx=tuple(jnp.asarray(t.ingress_lqids, jnp.int32)
                           for t in self.spec.tiers),
            recv_mask=tuple(jnp.ones((len(t.ingress_chans),), bool)
                            for t in self.spec.tiers),
        )

    # ----------------------------------------------------- compiled steppers
    def _cycles_fn(self, n: int):
        from ..core.distributed import granule_local_cycle

        groups = [g.block for g in self.spec.groups]

        class _G:  # granule_local_cycle wants .block per group
            def __init__(self, block):
                self.block = block

        gdefs = [_G(b) for b in groups]
        jax = self.jax

        def run(st):
            return jax.lax.scan(
                lambda s, _: (
                    granule_local_cycle(gdefs, self.n_local, self.W,
                                        self.dtype, s),
                    None,
                ),
                st, None, length=n,
            )[0]

        return run

    def _drain_fn(self, t: int):
        E = self.spec.tiers[t].E
        jnp = self.jnp

        def drain(st, credits):
            sidx = st.tables.send_idx[t]
            q = st.queues
            sub = qmod.QueueArray(
                buf=q.buf[sidx], head=q.head[sidx], tail=q.tail[sidx],
                capacity=q.capacity,
            )
            sub2, slab, cnt = qmod.drain(sub, E, limit=credits)
            q2 = q.replace(tail=q.tail.at[sidx].set(sub2.tail))
            return st.replace(queues=q2), slab, cnt.astype(jnp.int32)

        return drain

    def _fill_fn(self, t: int):
        from ..core.distributed import qmod_fill_at

        jnp = self.jnp
        cap = self.capacity

        def fill(st, slab, cnt):
            ridx = st.tables.recv_idx[t]
            q = qmod_fill_at(st.queues, ridx, slab, cnt)
            free = (cap - 1) - (q.head[ridx] - q.tail[ridx]) % cap
            return st.replace(queues=q), free.astype(jnp.int32)

        return fill

    def _ingest_fn(self):
        cap = self.capacity

        def ingest(st, lqid, payloads, avail):
            q = st.queues
            buf, head, n = qmod.fill_single(
                q.buf[lqid], q.head[lqid], q.tail[lqid], cap, payloads,
                limit=avail,
            )
            q2 = q.replace(
                buf=q.buf.at[lqid].set(buf), head=q.head.at[lqid].set(head)
            )
            return st.replace(queues=q2), n

        return ingest

    def _flush_fn(self):
        cap = self.capacity

        def flush(st, lqid, room):
            q = st.queues
            pays, tail, cnt = qmod.drain_single(
                q.buf[lqid], q.head[lqid], q.tail[lqid], cap, cap - 1,
                limit=room,
            )
            q2 = q.replace(tail=q.tail.at[lqid].set(tail))
            return st.replace(queues=q2), pays, cnt

        return flush

    def _epoch_tick_fn(self):
        def tick(st):
            return st.replace(epoch=st.epoch + 1)

        return tick

    def prebuild(self, template=None) -> dict:
        """AOT-compile every stepper this granule's epoch program needs.

        ``jit(fn).lower(template).compile()`` — the compiled artifacts land
        in the JAX persistent compilation cache (``enable_compile_cache``),
        so the next process with the same signature compiles ~for free.
        Returns {"seconds": total, "n_functions": count}.
        """
        jax, jnp = self.jax, self.jnp
        if template is None:
            template = self.init(
                np.asarray(jax.random.key_data(jax.random.key(0)))
            )
        t0 = time.perf_counter()
        n_fns = 0
        lengths = sorted({n for op, n in self.program if op == "C"})
        for n in lengths:
            self._compiled[("C", n)] = (
                jax.jit(self._cycles_fn(n), donate_argnums=0)
                .lower(template).compile()
            )
            n_fns += 1
        for t, ts in enumerate(self.spec.tiers):
            if ts.egress_chans:
                creds = jax.ShapeDtypeStruct((len(ts.egress_chans),), jnp.int32)
                self._compiled[("D", t)] = (
                    jax.jit(self._drain_fn(t), donate_argnums=0)
                    .lower(template, creds).compile()
                )
                n_fns += 1
            if ts.ingress_chans:
                n_in = len(ts.ingress_chans)
                slab = jax.ShapeDtypeStruct((n_in, ts.E, self.W), self.dtype)
                cnt = jax.ShapeDtypeStruct((n_in,), jnp.int32)
                self._compiled[("F", t)] = (
                    jax.jit(self._fill_fn(t), donate_argnums=0)
                    .lower(template, slab, cnt).compile()
                )
                n_fns += 1
        if self.spec.ext_ports:
            lqid = jax.ShapeDtypeStruct((), jnp.int32)
            scal = jax.ShapeDtypeStruct((), jnp.int32)
            pays = jax.ShapeDtypeStruct(
                (self.capacity - 1, self.W), self.dtype
            )
            self._compiled["ingest"] = (
                jax.jit(self._ingest_fn(), donate_argnums=0)
                .lower(template, lqid, pays, scal).compile()
            )
            self._compiled["flush"] = (
                jax.jit(self._flush_fn(), donate_argnums=0)
                .lower(template, lqid, scal).compile()
            )
            n_fns += 2
        self._compiled["tick"] = (
            jax.jit(self._epoch_tick_fn(), donate_argnums=0)
            .lower(template).compile()
        )
        n_fns += 1
        return {"seconds": time.perf_counter() - t0, "n_functions": n_fns}


class BatchedGranuleSim(GranuleSim):
    """GranuleSim over a signature batch: state leaves carry a leading
    (nb,) axis and every stepper is the base stepper under ``jax.vmap`` —
    one dispatch advances all nb granules (ISSUE 6's signature-batched
    stepping, procs flavor).  Host-facing ext-port ops address one batch
    row at a time (``row`` becomes a runtime input)."""

    def __init__(self, bspec: BatchSpec):
        self.bspec = bspec
        self.nb = len(bspec.specs)
        self.row_sims = [GranuleSim(s) for s in bspec.specs]
        super().__init__(bspec.specs[0])
        # same signature => same per-tier channel counts => same program
        assert all(rs.program == self.program for rs in self.row_sims), (
            "signature batch members disagree on epoch program"
        )

    def init(self, key_data: np.ndarray,
             group_params: list[list | None] | None = None):
        jnp = self.jnp
        states = [
            self.row_sims[r].init(
                key_data,
                group_params[r] if group_params is not None else None,
            )
            for r in range(self.nb)
        ]
        from ..core.distributed import _dealias_for_donation

        return _dealias_for_donation(
            self.jax.tree.map(lambda *xs: jnp.stack(xs), *states)
        )

    def _cycles_fn(self, n: int):
        jax = self.jax
        row = super()._cycles_fn(1)

        def run(st):
            return jax.lax.scan(
                lambda s, _: (jax.vmap(row)(s), None), st, None, length=n
            )[0]

        return run

    def _drain_fn(self, t: int):
        return self.jax.vmap(super()._drain_fn(t))

    def _fill_fn(self, t: int):
        return self.jax.vmap(super()._fill_fn(t))

    def _ingest_fn(self):
        cap = self.capacity

        def ingest(st, row, lqid, payloads, avail):
            q = st.queues
            buf, head, n = qmod.fill_single(
                q.buf[row, lqid], q.head[row, lqid], q.tail[row, lqid], cap,
                payloads, limit=avail,
            )
            q2 = q.replace(
                buf=q.buf.at[row, lqid].set(buf),
                head=q.head.at[row, lqid].set(head),
            )
            return st.replace(queues=q2), n

        return ingest

    def _flush_fn(self):
        cap = self.capacity

        def flush(st, row, lqid, room):
            q = st.queues
            pays, tail, cnt = qmod.drain_single(
                q.buf[row, lqid], q.head[row, lqid], q.tail[row, lqid], cap,
                cap - 1, limit=room,
            )
            q2 = q.replace(tail=q.tail.at[row, lqid].set(tail))
            return st.replace(queues=q2), pays, cnt

        return flush

    def prebuild(self, template=None) -> dict:
        jax, jnp = self.jax, self.jnp
        if template is None:
            template = self.init(
                np.asarray(jax.random.key_data(jax.random.key(0)))
            )
        t0 = time.perf_counter()
        n_fns = 0
        for n in sorted({n for op, n in self.program if op == "C"}):
            self._compiled[("C", n)] = (
                jax.jit(self._cycles_fn(n), donate_argnums=0)
                .lower(template).compile()
            )
            n_fns += 1
        for t, ts in enumerate(self.spec.tiers):
            if ts.egress_chans:
                creds = jax.ShapeDtypeStruct(
                    (self.nb, len(ts.egress_chans)), jnp.int32
                )
                self._compiled[("D", t)] = (
                    jax.jit(self._drain_fn(t), donate_argnums=0)
                    .lower(template, creds).compile()
                )
                n_fns += 1
            if ts.ingress_chans:
                n_in = len(ts.ingress_chans)
                slab = jax.ShapeDtypeStruct(
                    (self.nb, n_in, ts.E, self.W), self.dtype
                )
                cnt = jax.ShapeDtypeStruct((self.nb, n_in), jnp.int32)
                self._compiled[("F", t)] = (
                    jax.jit(self._fill_fn(t), donate_argnums=0)
                    .lower(template, slab, cnt).compile()
                )
                n_fns += 1
        if any(s.ext_ports for s in self.bspec.specs):
            scal = jax.ShapeDtypeStruct((), jnp.int32)
            pays = jax.ShapeDtypeStruct(
                (self.capacity - 1, self.W), self.dtype
            )
            self._compiled["ingest"] = (
                jax.jit(self._ingest_fn(), donate_argnums=0)
                .lower(template, scal, scal, pays, scal).compile()
            )
            self._compiled["flush"] = (
                jax.jit(self._flush_fn(), donate_argnums=0)
                .lower(template, scal, scal, scal).compile()
            )
            n_fns += 2
        self._compiled["tick"] = (
            jax.jit(self._epoch_tick_fn(), donate_argnums=0)
            .lower(template).compile()
        )
        n_fns += 1
        return {"seconds": time.perf_counter() - t0, "n_functions": n_fns}


@pytree_dataclass
class WorkerState:
    """One granule's device state (no leading device dims) — the squeezed
    analogue of ``GraphState``, stepped by the shared
    ``granule_local_cycle``.  ``tables`` ride in the state so they are
    runtime inputs to the compiled steppers (the prebuilt-cache property);
    credits do NOT — they live in the shm credit rings between exchanges."""

    queues: qmod.QueueArray  # (n_local, capacity, W)
    block_states: tuple  # per group: leaves (n_slot, ...)
    cycle: Any  # () int32
    epoch: Any  # () int32
    tables: Any  # GraphTables (granule-local)


# ----------------------------------------------------------------- worker
class Worker:
    """The free-running process: rings + compiled steppers + command loop."""

    def __init__(self, spec: GranuleSpec, conn, hb: np.ndarray | None,
                 faults=()):
        self.spec = spec
        self.conn = conn
        # (4,) f64 view: [epochs_completed, wallclock, blocked-status, spare]
        self.hb = hb
        self.sim = GranuleSim(spec)
        self.state = None
        self.epochs_done = 0
        self.timeout = spec.timeout
        # Ring waits get twice the launcher's heartbeat timeout: when the
        # whole fleet blocks (deadlock), the launcher's stall diagnoser
        # fires FIRST and names the credit cycle; the worker-side
        # RingTimeout is the backstop, not the headline diagnosis.
        self.ring_timeout = spec.timeout * 2
        self.wait_s = 0.0  # time blocked on peer rings (credits/slabs)
        self.run_s = 0.0  # wallclock inside "run" commands
        self.telem = None  # TelemetryWriter once the entry attaches a ring
        self._init_faults(faults)
        cap_b = spec.capacity
        itemsize = np.dtype(spec.dtype).itemsize
        self.rings: dict[tuple[str, int], ShmRing] = {}
        for ts in spec.tiers:
            for c in ts.egress_chans + ts.ingress_chans:
                self.rings[("d", c)] = ShmRing.attach(
                    data_ring_name(spec.ring_prefix, c),
                    spec.ring_depth + 1, slab_slot_bytes(ts.E, spec.payload_words, itemsize),
                    checked=True, label=f"slab:c{c}",
                )
                self.rings[("c", c)] = ShmRing.attach(
                    credit_ring_name(spec.ring_prefix, c),
                    spec.ring_depth + 2, 4,
                )
        for name, chan, lqid, is_in in spec.ext_ports:
            self.rings[("x", chan)] = ShmRing.attach(
                ext_ring_name(spec.ring_prefix, chan),
                cap_b, spec.payload_words * itemsize,
                checked=True, label=f"ext:{name}",
            )

    def _init_faults(self, faults) -> None:
        from .faultinject import WorkerFaultInjector

        self.injector = WorkerFaultInjector(faults) if faults else None
        self.slow_per_epoch = 0.0  # faultinject "slow" straggler knob
        self.hb_muted = False      # faultinject "mute" (drop heartbeats)

    def corruptible_ring(self, chan: int | None) -> ShmRing:
        """The data ring a ``corrupt`` fault targets: the given channel, or
        this worker's first egress channel when unspecified."""
        if chan is None:
            for ts in self.spec.tiers:
                if ts.egress_chans:
                    chan = ts.egress_chans[0]
                    break
        if chan is None or ("d", chan) not in self.rings:
            raise ValueError(f"no corruptible data ring for channel {chan}")
        return self.rings[("d", chan)]

    def beat(self) -> None:
        if self.hb is not None and not self.hb_muted:
            self.hb[0] = float(self.epochs_done)
            self.hb[1] = time.time()

    def _set_status(self, code: int) -> None:
        """Publish "blocked on ring X" (0 = running) in the heartbeat shm —
        the raw material of the launcher's credit wait-for graph."""
        if self.hb is not None:
            self.hb[2] = float(code)

    def _probe(self, gi: int, slot: int, row: int):
        import jax

        return jax.tree.map(lambda x: x[slot], self.state.block_states[gi])

    # ------------------------------------------------------------ the epoch
    def _ingest_ext(self) -> None:
        jnp = self.sim.jnp
        for name, chan, lqid, is_in in self.spec.ext_ports:
            if not is_in:
                continue
            ring = self.rings[("x", chan)]
            avail = ring.size()
            if not avail:
                continue
            k = min(avail, self.spec.capacity - 1)
            pays = ring.peek_packets(k, self.sim.np_dtype, self.sim.W)
            pad = np.zeros((self.spec.capacity - 1, self.sim.W),
                           self.sim.np_dtype)
            pad[:k] = pays
            self.state, n = self.sim._compiled["ingest"](
                self.state, jnp.int32(lqid), jnp.asarray(pad), jnp.int32(k)
            )
            ring.advance(int(n))

    def _flush_ext(self) -> None:
        """Move ext-out packets from the local queue into the host ring.

        Contract vs the in-process engines: the worker flushes at every
        boundary whether or not the host is draining, so an UNdrained
        output port buffers up to one extra ring (capacity-1 packets) of
        output before backpressuring the producer.  A host that drains at
        boundaries — the session scripts — therefore sees per-boundary
        bit-identical traffic; a host that lets output accumulate sees an
        identical packet *sequence* with producer stalls engaging one ring
        later (the same flavor of contract as the fused engine's
        capacity-2 cycle-accuracy clause; DESIGN.md §Runtime)."""
        jnp = self.sim.jnp
        for name, chan, lqid, is_in in self.spec.ext_ports:
            if is_in:
                continue
            ring = self.rings[("x", chan)]
            room = ring.free()
            if not room:
                continue
            self.state, pays, cnt = self.sim._compiled["flush"](
                self.state, jnp.int32(lqid), jnp.int32(room)
            )
            cnt = int(cnt)
            if cnt:
                landed = ring.push_packets(np.asarray(pays)[:cnt])
                assert landed == cnt  # room was the drain limit

    def _timed(self, fn, *args, status: int = 0):
        """Run one potentially-blocking ring op, accumulating its wallclock
        into ``wait_s`` (the procs blocking-wait metric; same accounting in
        serial and overlapped schedules, so the fraction is comparable).
        ``status`` publishes the blocked-on-ring word for the stall
        diagnoser; deliberately left set when the op raises, so a timed-out
        worker's last status word names the ring it died waiting on."""
        if status:
            self._set_status(status)
        t0 = time.perf_counter()
        try:
            out = fn(*args)
        finally:
            self.wait_s += time.perf_counter() - t0
        if status:
            self._set_status(0)
        return out

    def _pop_order(self, rings, codes=None):
        """Yield ring indices as each becomes non-empty (round-robin poll):
        the receive-late fill consumes whichever peer's slab lands first
        instead of serializing on channel order.  Poll time with no ring
        ready counts as blocking wait; past the deadline the remaining
        indices are yielded so the blocking pop raises ``RingTimeout``
        with its usual diagnostics."""
        pending = list(range(len(rings)))
        deadline = time.monotonic() + self.ring_timeout
        delay = 20e-6
        while pending:
            progressed = False
            for i in list(pending):
                if not rings[i].empty():
                    pending.remove(i)
                    progressed = True
                    yield i
            if pending and not progressed:
                if time.monotonic() > deadline:
                    while pending:
                        yield pending.pop(0)
                    return
                if codes is not None:
                    self._set_status(codes[pending[0]])
                t0 = time.perf_counter()
                time.sleep(delay)
                delay = min(delay * 2, 1e-3)
                self.wait_s += time.perf_counter() - t0
        if codes is not None:
            self._set_status(0)

    def _exchange_issue(self, t: int) -> None:
        """Window-end send: pop credits, drain egress queues, push slabs."""
        jnp = self.sim.jnp
        ts = self.spec.tiers[t]
        if not ts.egress_chans:
            return
        # pop one credit per egress channel: the receiver's post-fill
        # free space from the PREVIOUS exchange (seeded capacity-1)
        creds = np.array(
            [self._timed(self.rings[("c", c)].pop_u32_wait,
                         self.ring_timeout,
                         status=encode_blocked(OP_CREDIT_POP, c))
             for c in ts.egress_chans],
            np.int32,
        )
        self.state, slab, cnt = self.sim._compiled[("D", t)](
            self.state, jnp.asarray(creds)
        )
        slab = np.asarray(slab)
        cnt = np.asarray(cnt)
        for i, c in enumerate(ts.egress_chans):
            self._timed(self.rings[("d", c)].push_slab_wait,
                        int(cnt[i]), slab[i], self.ring_timeout,
                        status=encode_blocked(OP_SLAB_PUSH, c))

    def _exchange_commit(self, t: int) -> None:
        """Receive-late fill: pop slabs (first-ready order), fill ingress
        queues, push back post-fill free space as the next credits."""
        jnp = self.sim.jnp
        ts = self.spec.tiers[t]
        if not ts.ingress_chans:
            return
        n_in = len(ts.ingress_chans)
        slab_in = np.zeros((n_in, ts.E, self.sim.W), self.sim.np_dtype)
        cnt_in = np.zeros((n_in,), np.int32)
        rings = [self.rings[("d", c)] for c in ts.ingress_chans]
        codes = [encode_blocked(OP_SLAB_POP, c) for c in ts.ingress_chans]
        # receive-late is part of the overlap feature; the serial schedule
        # keeps strict channel-order blocking pops (the honest baseline)
        order = (self._pop_order(rings, codes) if self.spec.overlap
                 else range(n_in))
        for i in order:
            cnt_in[i], slab_in[i] = self._timed(
                rings[i].pop_slab_wait,
                (ts.E, self.sim.W), self.sim.np_dtype, self.ring_timeout,
                status=codes[i],
            )
        self.state, free = self.sim._compiled[("F", t)](
            self.state, jnp.asarray(slab_in), jnp.asarray(cnt_in)
        )
        free = np.asarray(free)
        for i, c in enumerate(ts.ingress_chans):
            self._timed(self.rings[("c", c)].push_u32,
                        int(free[i]), self.ring_timeout,
                        status=encode_blocked(OP_CREDIT_PUSH, c))

    def _exchange(self, t: int) -> None:
        self._exchange_issue(t)
        self._exchange_commit(t)

    def one_epoch(self) -> None:
        tl = self.telem
        if tl is not None and tl.enabled:
            return self._traced_epoch(tl)
        if self.injector is not None:
            # plan-driven faults fire at deterministic LOCAL epoch numbers,
            # before any of this epoch's effects — reproducible drills
            self.injector.before_epoch(self)
        if self.slow_per_epoch:
            time.sleep(self.slow_per_epoch)
        self._ingest_ext()
        for op, arg in self.sim.program:
            if op == "C":
                self.state = self.sim._compiled[("C", arg)](self.state)
            elif op == "XI":
                self._exchange_issue(arg)
            elif op == "XC":
                self._exchange_commit(arg)
            else:
                self._exchange(arg)
        self._flush_ext()
        self.state = self.sim._compiled["tick"](self.state)
        self.epochs_done += 1
        self.beat()

    def _traced_epoch(self, tl) -> None:
        """``one_epoch`` with per-phase telemetry records.  Mirrors the
        untraced walk exactly (same ring ops, same op order — traffic
        stays bit-identical); each phase costs one read of the profiler's
        clock (``now_ns``) and one non-blocking 48-byte ring push."""
        if self.injector is not None:
            self.injector.before_epoch(self)
        if self.slow_per_epoch:
            time.sleep(self.slow_per_epoch)
        wait0 = self.wait_s
        e0 = t0 = now_ns()
        self._ingest_ext()
        tl.phase(_telem.TEV_INGEST, 0.0, t0)
        for op, arg in self.sim.program:
            t0 = now_ns()
            if op == "C":
                self.state = self.sim._compiled[("C", arg)](self.state)
                tl.phase(_telem.TEV_STEP, float(arg), t0)
            elif op == "XI":
                self._exchange_issue(arg)
                tl.phase(_telem.TEV_ISSUE, float(arg), t0)
            elif op == "XC":
                self._exchange_commit(arg)
                tl.phase(_telem.TEV_COMMIT, float(arg), t0)
            else:
                self._exchange_issue(arg)
                tl.phase(_telem.TEV_ISSUE, float(arg), t0)
                t0 = now_ns()
                self._exchange_commit(arg)
                tl.phase(_telem.TEV_COMMIT, float(arg), t0)
        t0 = now_ns()
        self._flush_ext()
        tl.phase(_telem.TEV_FLUSH, 0.0, t0)
        self.state = self.sim._compiled["tick"](self.state)
        self.epochs_done += 1
        occ = n_d = 0
        for (kind, _c), ring in self.rings.items():
            if kind == "d":
                occ += ring.size()
                n_d += 1
        tl.emit(_telem.TEV_OCC, 0.0, now_ns(), 0.0,
                float(occ), float(n_d))
        tl.phase(_telem.TEV_EPOCH, float(self.epochs_done - 1), e0,
                 v0=self.wait_s - wait0)
        self.beat()

    # --------------------------------------------------------- command loop
    def serve(self) -> None:
        import jax

        while True:
            cmd = self.conn.recv()
            op = cmd[0]
            try:
                if op == "init":
                    _, key_data, group_params = cmd
                    self.state = self.sim.init(key_data, group_params)
                    self.epochs_done = 0
                    self.wait_s = 0.0
                    self.run_s = 0.0
                    self.beat()
                    self.conn.send(("ok", 0))
                elif op == "run":
                    t0 = time.perf_counter()
                    for _ in range(int(cmd[1])):
                        self.one_epoch()
                    self.run_s += time.perf_counter() - t0
                    self.conn.send(("ok", self.epochs_done))
                elif op == "probe":
                    _, gi, slot, *rest = cmd
                    out = jax.device_get(self._probe(
                        gi, slot, rest[0] if rest else 0
                    ))
                    self.conn.send(("ok", out))
                elif op == "view":
                    # the done-predicate view: tables are constants the
                    # launcher already holds, so strip them from the
                    # per-epoch pickle (it re-attaches its numpy copies)
                    self.conn.send(("ok", jax.device_get(
                        self.state.replace(tables=None)
                    )))
                elif op == "gather":
                    self.conn.send(("ok", jax.device_get(self.state)))
                elif op == "scatter":
                    _, tree, epochs = cmd
                    from ..core.distributed import _dealias_for_donation

                    self.state = _dealias_for_donation(jax.tree.map(
                        lambda x: self.sim.jnp.asarray(x), tree
                    ))
                    self.epochs_done = int(epochs)
                    self.beat()
                    self.conn.send(("ok", self.epochs_done))
                elif op == "stats":
                    self.conn.send(("ok", self._stats()))
                elif op == "telemetry":
                    on = bool(cmd[1])
                    if self.telem is not None:
                        self.telem.enabled = on
                    self.conn.send(("ok", on and self.telem is not None))
                elif op == "exit":
                    self.conn.send(("ok", None))
                    return
                else:
                    self.conn.send(("err", f"unknown command {op!r}"))
            except (RingCorruptionError, RingTimeout) as e:
                # recoverable fleet faults travel as a typed "fault" reply
                # (not a generic traceback) so the launcher can rebuild the
                # exception and route it into the recovery path
                sys.stderr.write(traceback.format_exc())
                sys.stderr.flush()
                payload = {"error": type(e).__name__, "message": str(e)}
                if isinstance(e, RingCorruptionError):
                    payload["args"] = e.to_payload()
                try:
                    self.conn.send(("fault", payload))
                except Exception:
                    return
            except Exception:  # noqa: BLE001 — reported to the launcher
                sys.stderr.write(traceback.format_exc())
                sys.stderr.flush()
                try:
                    self.conn.send(("err", traceback.format_exc()))
                except Exception:
                    return

    def _stats(self) -> dict:
        import jax

        q = jax.device_get(self.state.queues)
        size = (q.head - q.tail) % q.capacity
        ports = {}
        for name, chan, lqid, is_in in self.spec.ext_ports:
            ports[name] = {
                "occupancy": int(size[lqid]),
                "credit": int(q.capacity - 1 - size[lqid]),
                "is_input": bool(is_in),
            }
        return {
            "granule": self.spec.granule,
            "cycle": int(jax.device_get(self.state.cycle)),
            "epoch": self.epochs_done,
            "ports": ports,
            "signature": self.spec.signature,
            "wait_s": self.wait_s,
            "run_s": self.run_s,
            "wait_fraction": (self.wait_s / self.run_s) if self.run_s else 0.0,
            "telem_dropped": self.telem.dropped if self.telem else 0,
        }


class BatchedWorker(Worker):
    """One process stepping a whole signature batch: a single vmapped
    dispatch advances all nb granules per program op, while the ring
    protocol stays per channel — the batch merely refines the free-running
    schedule (its members run in lockstep, a legal schedule the credit
    chain already admits), so traffic stays bit-identical to per-granule
    workers."""

    def __init__(self, bspec: BatchSpec, conn, hb: np.ndarray | None,
                 faults=()):
        self.bspec = bspec
        self.specs = bspec.specs
        self.spec = bspec.specs[0]  # shared scalars (capacity/W/rings/...)
        self.conn = conn
        self.hb = hb
        self.sim = BatchedGranuleSim(bspec)
        self.state = None
        self.epochs_done = 0
        self.timeout = self.spec.timeout
        self.ring_timeout = self.spec.timeout * 2
        self.wait_s = 0.0
        self.run_s = 0.0
        self.telem = None
        self._init_faults(faults)
        itemsize = np.dtype(self.spec.dtype).itemsize
        self.rings: dict[tuple[str, int], ShmRing] = {}
        for s in self.specs:
            for ts in s.tiers:
                for c in ts.egress_chans + ts.ingress_chans:
                    if ("d", c) in self.rings:
                        continue
                    self.rings[("d", c)] = ShmRing.attach(
                        data_ring_name(s.ring_prefix, c),
                        s.ring_depth + 1,
                        slab_slot_bytes(ts.E, s.payload_words, itemsize),
                        checked=True, label=f"slab:c{c}",
                    )
                    self.rings[("c", c)] = ShmRing.attach(
                        credit_ring_name(s.ring_prefix, c),
                        s.ring_depth + 2, 4,
                    )
            for name, chan, lqid, is_in in s.ext_ports:
                if ("x", chan) not in self.rings:
                    self.rings[("x", chan)] = ShmRing.attach(
                        ext_ring_name(s.ring_prefix, chan),
                        s.capacity, s.payload_words * itemsize,
                        checked=True, label=f"ext:{name}",
                    )

    def _probe(self, gi: int, slot: int, row: int):
        import jax

        return jax.tree.map(
            lambda x: x[row, slot], self.state.block_states[gi]
        )

    def _ingest_ext(self) -> None:
        jnp = self.sim.jnp
        for r, s in enumerate(self.specs):
            for name, chan, lqid, is_in in s.ext_ports:
                if not is_in:
                    continue
                ring = self.rings[("x", chan)]
                avail = ring.size()
                if not avail:
                    continue
                k = min(avail, s.capacity - 1)
                pays = ring.peek_packets(k, self.sim.np_dtype, self.sim.W)
                pad = np.zeros((s.capacity - 1, self.sim.W),
                               self.sim.np_dtype)
                pad[:k] = pays
                self.state, n = self.sim._compiled["ingest"](
                    self.state, jnp.int32(r), jnp.int32(lqid),
                    jnp.asarray(pad), jnp.int32(k),
                )
                ring.advance(int(n))

    def _flush_ext(self) -> None:
        jnp = self.sim.jnp
        for r, s in enumerate(self.specs):
            for name, chan, lqid, is_in in s.ext_ports:
                if is_in:
                    continue
                ring = self.rings[("x", chan)]
                room = ring.free()
                if not room:
                    continue
                self.state, pays, cnt = self.sim._compiled["flush"](
                    self.state, jnp.int32(r), jnp.int32(lqid),
                    jnp.int32(room),
                )
                cnt = int(cnt)
                if cnt:
                    landed = ring.push_packets(np.asarray(pays)[:cnt])
                    assert landed == cnt

    def _exchange_issue(self, t: int) -> None:
        jnp = self.sim.jnp
        rows = [s.tiers[t] for s in self.specs]
        if not rows[0].egress_chans:
            return
        creds = np.array(
            [[self._timed(self.rings[("c", c)].pop_u32_wait,
                          self.ring_timeout,
                          status=encode_blocked(OP_CREDIT_POP, c))
              for c in ts.egress_chans] for ts in rows],
            np.int32,
        )
        self.state, slab, cnt = self.sim._compiled[("D", t)](
            self.state, jnp.asarray(creds)
        )
        slab = np.asarray(slab)
        cnt = np.asarray(cnt)
        for r, ts in enumerate(rows):
            for i, c in enumerate(ts.egress_chans):
                self._timed(self.rings[("d", c)].push_slab_wait,
                            int(cnt[r, i]), slab[r, i], self.ring_timeout,
                            status=encode_blocked(OP_SLAB_PUSH, c))

    def _exchange_commit(self, t: int) -> None:
        jnp = self.sim.jnp
        rows = [s.tiers[t] for s in self.specs]
        if not rows[0].ingress_chans:
            return
        n_in = len(rows[0].ingress_chans)
        nb = len(self.specs)
        slab_in = np.zeros((nb, n_in, rows[0].E, self.sim.W),
                           self.sim.np_dtype)
        cnt_in = np.zeros((nb, n_in), np.int32)
        flat = [(r, i, c, self.rings[("d", c)])
                for r, ts in enumerate(rows)
                for i, c in enumerate(ts.ingress_chans)]
        codes = [encode_blocked(OP_SLAB_POP, c) for _, _, c, _ in flat]
        order = (self._pop_order([ring for _, _, _, ring in flat], codes)
                 if self.spec.overlap else range(len(flat)))
        for k in order:
            r, i, c, ring = flat[k]
            cnt_in[r, i], slab_in[r, i] = self._timed(
                ring.pop_slab_wait,
                (rows[r].E, self.sim.W), self.sim.np_dtype,
                self.ring_timeout, status=codes[k],
            )
        self.state, free = self.sim._compiled[("F", t)](
            self.state, jnp.asarray(slab_in), jnp.asarray(cnt_in)
        )
        free = np.asarray(free)
        for r, ts in enumerate(rows):
            for i, c in enumerate(ts.ingress_chans):
                self._timed(self.rings[("c", c)].push_u32,
                            int(free[r, i]), self.ring_timeout,
                            status=encode_blocked(OP_CREDIT_PUSH, c))

    def _stats(self) -> list[dict]:
        import jax

        q = jax.device_get(self.state.queues)
        size = (q.head - q.tail) % q.capacity  # (nb, n_local)
        cycles = jax.device_get(self.state.cycle)
        out = []
        for r, s in enumerate(self.specs):
            ports = {}
            for name, chan, lqid, is_in in s.ext_ports:
                ports[name] = {
                    "occupancy": int(size[r, lqid]),
                    "credit": int(q.capacity - 1 - size[r, lqid]),
                    "is_input": bool(is_in),
                }
            out.append({
                "granule": s.granule,
                "cycle": int(cycles[r]),
                "epoch": self.epochs_done,
                "ports": ports,
                "signature": s.signature,
                "batch_row": r,
                "batch_size": len(self.specs),
                "wait_s": self.wait_s,
                "run_s": self.run_s,
                "wait_fraction": (self.wait_s / self.run_s)
                if self.run_s else 0.0,
                "telem_dropped": self.telem.dropped if self.telem else 0,
            })
        return out


HB_RECORD_BYTES = 32  # per-worker heartbeat: [epochs, wallclock, status, _]
HB_RECORD_F64 = HB_RECORD_BYTES // 8


def attach_heartbeat(hb_ring_name: str, index: int):
    """Attach one member's heartbeat record (4 f64: [progress counter,
    wallclock, blocked-status word, spare]) in the fleet heartbeat shm.
    Shared by granule workers (index = worker id) and bridge proxies
    (index = NW + local bridge index) — both are first-class members of
    the ProcessMonitor's liveness/stall surface.  Returns (shm, view);
    the caller keeps ``shm`` alive for the view's lifetime."""
    from .shmem import attach_shared_memory

    hb_shm = attach_shared_memory(hb_ring_name)
    hb = np.frombuffer(hb_shm.buf, np.float64, count=HB_RECORD_F64,
                       offset=index * HB_RECORD_BYTES)
    return hb_shm, hb


def worker_entry(conn, spec_pickle: bytes, worker_index: int,
                 log_path: str | None, cache_dir: str | None,
                 hb_ring_name: str | None,
                 faults_pickle: bytes | None = None,
                 telem_ring_name: str | None = None) -> None:
    """Process entry point (spawn context).  Builds the granule simulator
    (hitting the persistent compilation cache warmed by the launcher's
    prebuild pass), then serves the command loop until "exit".
    ``faults_pickle`` carries this worker's armed ``FaultAction``s for the
    current fleet incarnation (drills; empty in production)."""
    import pickle

    # Pin the single-CPU-device env HERE, not only in the parent: under
    # the forkserver context the child inherits the server's frozen env,
    # and XLA reads these at backend init (first use), which is always
    # after this point — no backend exists pre-fork.
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
              if "xla_force_host_platform_device_count" not in f]
    if _flags:
        os.environ["XLA_FLAGS"] = " ".join(_flags)
    else:
        os.environ.pop("XLA_FLAGS", None)

    if log_path:
        f = open(log_path, "w", buffering=1)
        os.dup2(f.fileno(), 1)
        os.dup2(f.fileno(), 2)
        sys.stdout = os.fdopen(1, "w", buffering=1)
        sys.stderr = os.fdopen(2, "w", buffering=1)
    try:
        enable_compile_cache(cache_dir)
        spec = pickle.loads(spec_pickle)
        faults = pickle.loads(faults_pickle) if faults_pickle else ()
        if isinstance(spec, BatchSpec):
            print(f"[worker {worker_index}] granules {spec.members} "
                  f"signature {spec.signature} starting (batched)",
                  flush=True)
        else:
            print(f"[worker {worker_index}] granule {spec.granule} "
                  f"signature {spec.signature} starting", flush=True)
        if faults:
            print(f"[worker {worker_index}] armed faults: {faults}",
                  flush=True)
        hb = hb_shm = None
        if hb_ring_name:
            hb_shm, hb = attach_heartbeat(hb_ring_name, worker_index)
        w = (BatchedWorker(spec, conn, hb, faults)
             if isinstance(spec, BatchSpec)
             else Worker(spec, conn, hb, faults))
        if telem_ring_name:
            # flight-recorder ring (repro.obs): worker is sole producer;
            # stored under ("t", 0) so the exit sweep below closes it
            tring = ShmRing.attach(telem_ring_name,
                                   _telem.TELEM_RING_RECORDS,
                                   _telem.TELEM_RECORD_BYTES)
            w.rings[("t", 0)] = tring
            w.telem = _telem.TelemetryWriter(tring)
        build = w.sim.prebuild()
        print(f"[worker {worker_index}] prebuilt {build['n_functions']} fns "
              f"in {build['seconds']:.2f}s", flush=True)
        conn.send(("ready", build))
        w.serve()
        # release every live view of shm before interpreter exit, or the
        # segments' __del__ dies with "cannot close: exported pointers
        # exist" noise in the worker log
        for ring in w.rings.values():
            ring.close()
        w.hb = None
        hb = None
        if hb_shm is not None:
            hb_shm.close()
        print(f"[worker {worker_index}] clean exit", flush=True)
    except Exception:  # noqa: BLE001
        traceback.print_exc()
        sys.stderr.flush()
        try:
            conn.send(("err", traceback.format_exc()))
        except Exception:
            pass
        raise
