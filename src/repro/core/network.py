"""Network construction — the ``SbNetwork`` analogue (paper §III-F).

Usage mirrors the paper's Listing 5::

    net = Network(payload_words=2)
    a = net.instantiate(MyBlock(), name="a")
    b = net.instantiate(MyBlock(), name="b")
    net.connect(a["out"], b["in"])          # internal channel
    host_in = net.external_in(a["in"])      # host -> network
    host_out = net.external_out(b["out"])   # network -> host
    sim = net.build()                       # Simulation session (single engine)
    sim.reset(jax.random.key(0))
    sim.tx(host_in).send([1.0, 0.0])        # host queue handles (PySbTx/PySbRx)
    sim.run(cycles=1000)                    # session owns + donates the state
    print(sim.rx(host_out).recv())

Key properties carried over from the paper:

  * **One compiled step per unique block type.**  Instances of the same
    ``Block`` object are stacked and stepped with a single ``vmap``-ed body;
    build (trace+compile) cost is O(#unique block types), not O(#instances).
  * **Channels are SPSC queues** with the §III-B ring semantics; bridges add
    one cycle each (N_TX = N_RX = 1).
  * **Rate control** (§II-C): each block type has a ``clock_divider``; a
    block steps only on cycles divisible by its divider, so simulated-clock
    ratios are matched *exactly* (deterministic analogue of the paper's
    sleep-based controller).

The builder lowers to the **channel-graph IR** (``repro.core.graph``), and
``build(engine=...)`` hands that IR to any backend (DESIGN.md §1, §4):

    sim = net.build()                          # single-netlist session
    sim = net.build(engine="graph",            # distributed GraphEngine
                    mesh=mesh, partition=part, K=8)
    sim = net.build(engine="register", ...)    # kernel-fused fast backend

Every variant returns a ``session.Simulation`` facade with ONE lifecycle
(``reset`` / ``run`` / ``probe`` / ``tx`` / ``rx`` / ``save`` / ``load``)
regardless of the engine; the raw engine stays reachable as
``sim.engine`` (or ``build(..., session=False)``), and the legacy
``init(key)``/``run(state, n)``/``push_external`` surface keeps working
through deprecation shims on the facade.

``NetworkSim`` (engine="single") interprets the whole IR as one pure
``step`` function, suitable for ``lax.scan`` and used as the cycle-accurate
ground truth for accuracy studies (Fig. 15).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable

import jax
import jax.numpy as jnp

from . import queue as qmod
from ..obs.registry import REGISTRY
from .block import Block
from .graph import ChannelGraph
from .struct import pytree_dataclass, static_field

PyTree = Any


@dataclasses.dataclass(frozen=True)
class PortRef:
    inst_id: int
    port: str
    is_output: bool


@dataclasses.dataclass
class Instance:
    inst_id: int
    block: Block
    name: str
    params: PyTree  # per-instance parameters (un-stacked pytree) or None

    def __getitem__(self, port: str) -> PortRef:
        if port in self.block.out_ports:
            return PortRef(self.inst_id, port, True)
        if port in self.block.in_ports:
            return PortRef(self.inst_id, port, False)
        raise KeyError(f"{self.block.type_name} has no port {port!r}")


@pytree_dataclass
class NetworkState:
    queues: qmod.QueueArray
    block_states: tuple[PyTree, ...]  # stacked per block group
    cycle: jax.Array  # () int32
    push_count: jax.Array  # (n_channels,) int32 — handshakes, for perf stats
    pop_count: jax.Array  # (n_channels,) int32


class Network:
    """Builder: instantiate blocks, wire channels, produce a simulator."""

    def __init__(
        self,
        payload_words: int = 2,
        dtype: Any = jnp.float32,
        capacity: int = qmod.DEFAULT_CAPACITY,
    ):
        self.payload_words = payload_words
        self.dtype = dtype
        self.capacity = capacity
        self._instances: list[Instance] = []
        self._connections: list[tuple[PortRef, PortRef]] = []
        self._external_in: dict[str, PortRef] = {}
        self._external_out: dict[str, PortRef] = {}

    # -- construction API ---------------------------------------------------
    def instantiate(self, block: Block, name: str | None = None, params: PyTree = None) -> Instance:
        inst = Instance(len(self._instances), block, name or f"i{len(self._instances)}", params)
        self._instances.append(inst)
        return inst

    def connect(self, tx: PortRef, rx: PortRef) -> None:
        if not tx.is_output or rx.is_output:
            raise ValueError("connect(tx, rx) needs an output then an input port")
        self._connections.append((tx, rx))

    def external_in(self, rx: PortRef, name: str | None = None) -> str:
        """Expose an input port to the host; returns the external-port name."""
        name = name or f"ext_in{len(self._external_in)}"
        self._external_in[name] = rx
        return name

    def external_out(self, tx: PortRef, name: str | None = None) -> str:
        name = name or f"ext_out{len(self._external_out)}"
        self._external_out[name] = tx
        return name

    # -- lowering ------------------------------------------------------------
    def graph(self) -> ChannelGraph:
        """Lower the builder state to the engine-agnostic channel-graph IR."""
        return ChannelGraph.from_network(self)

    def build(self, engine: str = "single", session: bool = True, **kw):
        """Lower to the IR and construct the selected backend (DESIGN.md §4).

        Returns a ``session.Simulation`` facade over the engine (the
        uniform ``reset``/``run``/``probe``/``tx``/``rx``/``save``/``load``
        lifecycle); pass ``session=False`` for the raw engine object.

        engine="single"    -> NetworkSim (this module); no extra kwargs.
        engine="graph"     -> distributed.GraphEngine; kwargs: mesh, K,
                              partition (instance->granule map or a
                              graph.PartitionTree), axes, tiers (per-tier
                              (axes, K) pairs or graph.Tier, outermost
                              first — hierarchical sync, DESIGN.md §3),
                              batch_axes (signature-batched stepping:
                              axis names, or {name: size} for batch-only
                              axes off the mesh — DESIGN.md §Perf),
                              overlap (split issue/commit exchange —
                              bit-identical pipelining of tier transfers
                              with compute; "auto"/bool, REPRO_OVERLAP
                              env override — DESIGN.md §Perf).
        engine="fused"     -> fused.FusedEngine — the fused-epoch fast
                              path for arbitrary topologies (§Perf):
                              same kwargs as "graph".
        engine="register"  -> fastgrid.RegisterGridEngine (systolic-grid
                              networks only); kwargs: mesh, K.
        engine="procs"     -> runtime.launcher.ProcsEngine — the free-
                              running multiprocess runtime (DESIGN.md
                              §Runtime): one prebuilt granule simulator
                              per OS process over shared-memory queues,
                              no mesh needed; kwargs: partition (flat map
                              or PartitionTree), n_workers, K, ring_depth,
                              timeout, prebuild, cache_dir, log_dir,
                              batch_signatures, overlap (send-early/
                              receive-late worker exchanges), on_fault
                              ("raise"|"recover" self-healing policy,
                              REPRO_ON_FAULT env override), snapshot_every,
                              max_restarts, backoff_s, fault_plan
                              (deterministic drills, REPRO_FAULT_PLAN),
                              hosts (multi-host fleet: granule->host
                              placement, DESIGN.md §Multi-host fleet;
                              REPRO_HOSTS env), host (which host this
                              launcher serves), base_port
                              (REPRO_BRIDGE_PORT; 0 = ephemeral).

        (The uniform-grid presets ``distributed.GridEngine`` and
        ``fused.FusedEngine.grid`` are constructed directly — they build
        their own grid IR without a Network.)
        """
        graph = self.graph()
        eng = self._build_engine(graph, engine, kw)
        if session:
            from .session import Simulation

            return Simulation(eng)
        return eng

    def _build_engine(self, graph: ChannelGraph, engine: str, kw: dict):
        if engine == "single":
            if kw:
                raise TypeError(f"engine='single' takes no kwargs, got {sorted(kw)}")
            return NetworkSim(graph)
        if engine in ("graph", "fused"):
            if engine == "graph":
                from .distributed import GraphEngine as Engine
            else:
                from .fused import FusedEngine as Engine
            extra = {}
            mesh = kw.pop("mesh")
            K = kw.pop("K", 1)
            tiers = kw.pop("tiers", None)
            axes = kw.pop("axes", None)  # engine defaults to mesh.axis_names
            partition = kw.pop("partition", None)
            if "batch_axes" in kw:  # signature-batched stepping (§Perf)
                extra["batch_axes"] = kw.pop("batch_axes")
            if "overlap" in kw:  # split issue/commit exchange (ISSUE 7)
                extra["overlap"] = kw.pop("overlap")
            if kw:
                raise TypeError(
                    f"unknown build kwargs for engine={engine!r}: {sorted(kw)}"
                )
            return Engine(
                graph, partition, mesh, K=K, axes=axes, tiers=tiers, **extra
            )
        if engine == "register":
            from .fastgrid import RegisterGridEngine

            return RegisterGridEngine.from_graph(graph, **kw)
        if engine == "procs":
            from ..runtime.launcher import ProcsEngine

            return ProcsEngine(graph, kw.pop("partition", None), **kw)
        raise ValueError(
            f"unknown engine {engine!r} "
            "(single | graph | fused | register | procs)"
        )


class NetworkSim:
    """Single-netlist simulator: a thin interpreter of the channel-graph IR.

    The step function is pure; ``run`` wraps it in ``jax.jit(lax.scan)``.
    """

    engine_kind = "single"
    cycles_per_epoch = 1  # host-sync granularity: every cycle is a boundary

    def __init__(self, graph: ChannelGraph):
        self.graph = graph
        self.group_blocks: list[Block] = [g.block for g in graph.groups]
        self.NULL_RX, self.NULL_TX = graph.NULL_RX, graph.NULL_TX
        self.n_channels = graph.n_channels
        self.rx_idx = graph.rx_idx
        self.tx_idx = graph.tx_idx
        self.ext_in_chan = graph.ext_in
        self.ext_out_chan = graph.ext_out
        self.payload_words = graph.payload_words
        self.dtype = graph.dtype
        self.capacity = graph.capacity
        # Compiled-run cache lives on the instance (keyed by n_cycles and
        # donation), so a collected simulator releases its executables and a
        # recycled id can never alias a stale compilation.
        self._jit_cache: dict[tuple[int, bool], Callable] = {}

    # -- state ---------------------------------------------------------------
    def init(self, key: jax.Array) -> NetworkState:
        states = []
        for gi, (g, blk) in enumerate(zip(self.graph.groups, self.group_blocks)):
            # Fold in the group *index* (deterministic, identical across
            # engine backends and process runs) — never id(blk), which is
            # allocation-dependent and would break cross-engine bit-equality
            # for blocks whose init_state consumes the key.
            keys = jax.random.split(jax.random.fold_in(key, gi), g.n_members)
            if g.params is not None:
                params = jax.tree.map(jnp.asarray, g.params)
                st = jax.vmap(blk.init_state)(keys, params)
            else:
                st = jax.vmap(blk.init_state)(keys)
            states.append(st)
        queues = qmod.make_queues(
            self.n_channels, self.payload_words, self.capacity, self.dtype
        )
        # distinct buffers (not one shared `zero`): donation-friendly
        return NetworkState(
            queues=queues,
            block_states=tuple(states),
            cycle=jnp.zeros((), jnp.int32),
            push_count=jnp.zeros((self.n_channels,), jnp.int32),
            pop_count=jnp.zeros((self.n_channels,), jnp.int32),
        )

    # -- one network cycle ----------------------------------------------------
    def step(self, state: NetworkState) -> NetworkState:
        q = state.queues
        fronts, valids = qmod.peek(q)  # (N,W), (N,)
        readies = ~qmod.full(q)  # (N,)
        # Sentinels: NULL_RX never valid; NULL_TX always ready.
        valids = valids.at[self.NULL_RX].set(False)
        readies = readies.at[self.NULL_TX].set(True)

        push_payload = jnp.zeros((self.n_channels, self.payload_words), self.dtype)
        push_valid = jnp.zeros((self.n_channels,), bool)
        pop_ready = jnp.zeros((self.n_channels,), bool)

        new_states = []
        for gi, blk in enumerate(self.group_blocks):
            rxm, txm = self.rx_idx[gi], self.tx_idx[gi]
            rx = {
                port: (fronts[rxm[:, p]], valids[rxm[:, p]])
                for p, port in enumerate(blk.in_ports)
            }
            tx_ready = {port: readies[txm[:, p]] for p, port in enumerate(blk.out_ports)}
            st = state.block_states[gi]
            new_st, rx_ready, tx = jax.vmap(blk.step)(st, rx, tx_ready)

            if blk.clock_divider > 1:
                en = (state.cycle % blk.clock_divider) == 0
                new_st = jax.tree.map(lambda n, o: jnp.where(en, n, o), new_st, st)
                rx_ready = {k: v & en for k, v in rx_ready.items()}
                tx = {k: (p, v & en) for k, (p, v) in tx.items()}
            new_states.append(new_st)

            for p, port in enumerate(blk.in_ports):
                pop_ready = pop_ready.at[rxm[:, p]].max(rx_ready[port])
            for p, port in enumerate(blk.out_ports):
                pay, val = tx[port]
                push_payload = push_payload.at[txm[:, p]].set(
                    pay.astype(self.dtype), mode="drop"
                )
                push_valid = push_valid.at[txm[:, p]].max(val)

        # Sentinel writes are dropped: never push to NULL_TX's storage, and
        # NULL_RX is never popped.
        push_valid = push_valid.at[self.NULL_TX].set(False)
        pop_ready = pop_ready.at[self.NULL_RX].set(False)

        q2, did_push, did_pop = qmod.cycle(q, push_payload, push_valid, pop_ready)
        return NetworkState(
            queues=q2,
            block_states=tuple(new_states),
            cycle=state.cycle + 1,
            push_count=state.push_count + did_push.astype(jnp.int32),
            pop_count=state.pop_count + did_pop.astype(jnp.int32),
        )

    def run(
        self, state: NetworkState, n_cycles: int, *, donate: bool = False
    ) -> NetworkState:
        """Advance ``n_cycles`` with a jitted scan (compiled once per length).

        ``donate=True`` reuses the input state's buffers for the output
        (no copy through HBM); the input must not be used afterwards.
        """
        key = (n_cycles, donate)
        if key not in self._jit_cache:
            REGISTRY.inc("single.compile.count")

            def impl(st):
                return jax.lax.scan(
                    lambda s, _: (self.step(s), None), st, None, length=n_cycles
                )[0]

            self._jit_cache[key] = jax.jit(
                impl, donate_argnums=(0,) if donate else ()
            )
        if donate:
            from .distributed import _dealias_for_donation

            state = _dealias_for_donation(state)
        REGISTRY.inc("single.dispatch.count")
        REGISTRY.inc("single.cycles", float(n_cycles))
        return self._jit_cache[key](state)

    def run_until(
        self,
        state: NetworkState,
        done_fn: Callable[[NetworkState], jax.Array],
        max_cycles: int,
        *,
        cache_key: Any = None,
        donate: bool = True,
    ) -> NetworkState:
        """Step until ``done_fn(state)`` holds, or at most ``max_cycles``
        MORE cycles from the input state (a relative budget, mirroring the
        engines' relative ``max_epochs`` — the compiled loop is reusable
        from any starting cycle).  An already-done state runs zero cycles,
        so chunked callers (the session's monitor cadence) can re-enter
        safely.  Donation defaults on, matching ``GraphEngine.run_until``
        (uniform engine protocol — ``run`` keeps its legacy donate=False).
        Cache keying follows ``GraphEngine.run_until``: pass ``cache_key``
        when the predicate is a fresh lambda per call."""
        anchor = cache_key if cache_key is not None else done_fn
        key = ("until", id(anchor), max_cycles, donate)
        if key not in self._jit_cache:

            def impl(st):
                c0 = st.cycle

                def cond(carry):
                    s, pending = carry
                    return (pending > 0) & (s.cycle - c0 < max_cycles)

                def body(carry):
                    s, _ = carry
                    s = self.step(s)
                    return s, 1 - done_fn(s).astype(jnp.int32)

                pending0 = 1 - done_fn(st).astype(jnp.int32)
                return jax.lax.while_loop(cond, body, (st, pending0))[0]

            self._jit_cache[key] = (
                anchor,  # strong ref: keeps the keyed id alive
                jax.jit(impl, donate_argnums=(0,) if donate else ()),
            )
        if donate:
            from .distributed import _dealias_for_donation

            state = _dealias_for_donation(state)
        return self._jit_cache[key][1](state)

    # -- host-side external port access (PySbTx / PySbRx analogue) -----------
    # ``host_push``/``host_pop`` (+ batched ``_many``) are the engine-level
    # primitives the session Tx/Rx ports drive; the historical
    # ``push_external``/``pop_external`` names remain as deprecation shims.
    def host_push(self, state: NetworkState, name: str, payload) -> tuple[NetworkState, jax.Array]:
        q2, ok = qmod.host_push(
            state.queues, self.ext_in_chan[name],
            jnp.asarray(payload, self.dtype),
        )
        return state.replace(queues=q2), ok

    def host_pop(self, state: NetworkState, name: str):
        q2, front, valid = qmod.host_pop(state.queues, self.ext_out_chan[name])
        return state.replace(queues=q2), front, valid

    def host_push_many(self, state: NetworkState, name: str, payloads):
        """Batched push: up to ``free`` packets land, the rest are refused
        (count returned).  payloads: (k, W)."""
        payloads = jnp.asarray(payloads, self.dtype).reshape(-1, self.payload_words)
        q2, n = qmod.host_push_many(
            state.queues, self.ext_in_chan[name], payloads
        )
        return state.replace(queues=q2), n

    def host_pop_many(self, state: NetworkState, name: str, max_n: int):
        """Batched pop: returns (state, payloads (max_n, W), count)."""
        q2, pays, cnt = qmod.host_pop_many(
            state.queues, self.ext_out_chan[name], max_n
        )
        return state.replace(queues=q2), pays, cnt

    def push_external(self, state: NetworkState, name: str, payload):
        warnings.warn(
            "push_external is deprecated; use the Simulation session's "
            "tx(name).send(...) (or engine.host_push)",
            DeprecationWarning, stacklevel=2,
        )
        return self.host_push(state, name, payload)

    def pop_external(self, state: NetworkState, name: str):
        warnings.warn(
            "pop_external is deprecated; use the Simulation session's "
            "rx(name).recv() (or engine.host_pop)",
            DeprecationWarning, stacklevel=2,
        )
        return self.host_pop(state, name)

    def group_state(self, state: NetworkState, inst: Instance | int):
        """Extract one instance's (unstacked) state from the network state."""
        inst_id = inst if isinstance(inst, int) else inst.inst_id
        gi, slot = self.graph.locate(inst_id)
        return jax.tree.map(lambda x: x[slot], state.block_states[gi])

    def port_stats(self, state: NetworkState) -> dict:
        """Per external port: live queue occupancy + remaining credit —
        the uniform ``Simulation.stats()["ports"]`` schema (one shape on
        every engine, shm-backed or in-process).  Nested by direction so
        a name serving BOTH directions reports each channel's own queue."""
        import numpy as np

        q = state.queues
        size = np.asarray(jax.device_get((q.head - q.tail) % q.capacity))

        def rec(cid):
            return {"occupancy": int(size[cid]),
                    "credit": int(q.capacity - 1 - size[cid])}

        return {
            "tx": {n: rec(c) for n, c in self.graph.ext_in.items()},
            "rx": {n: rec(c) for n, c in self.graph.ext_out.items()},
        }
