"""Simulation sessions — ONE user-facing lifecycle over every engine
(paper §III-E/§IV-A; DESIGN.md §4).

Switchboard's user surface is not "build a netlist and scan it": it is
host-side queue handles (``PySbTx``/``PySbRx``) pushing and popping packets
into a *running* simulation, plus monitors — that is what makes the
paper's interactive chiplet web app possible.  This module is that
surface.  ``Network.build(engine=...)`` returns a ``Simulation``:

    sim = net.build(engine="fused", mesh=mesh, partition=part, K=8)
    sim.reset(jax.random.key(0))          # engine state, placed + owned
    tx, rx = sim.tx("cmd.q"), sim.rx("resp.q")
    tx.send([41.0, 1.0])                  # host -> network queue handle
    sim.run(cycles=1000)                  # donation/de-aliasing inside
    print(rx.recv(), sim.cycle)
    sim.save("/tmp/ckpt")                 # checkpoint; sim.load() resumes

The same five lines drive all five engines — ``single`` | ``graph`` |
``fused`` | ``register`` | ``procs`` — because the facade speaks only the
uniform engine protocol (``engine_kind``, ``init``, ``run_epochs``/
``run``, ``run_until``, ``group_state``, ``host_push*``/``host_pop*``,
``cycles_per_epoch``).  The ``procs`` engine (the free-running
multiprocess runtime, DESIGN.md §Runtime) holds its state in worker
processes, so its "state" is a handle; the facade's save/load and
until-predicates route through the engine's ``gather_state``/
``scatter_state``/``eval_done`` hooks when present.

**The host is the outermost tier.**  Host packets enter and leave at
*boundaries* — every ``cycles_per_epoch`` simulated cycles, i.e. exactly
when the engines' tiered exchange already synchronizes (DESIGN.md §3) —
through the same SPSC ring machinery the inter-granule slabs use
(``queue.fill_single``/``drain_single`` batch ops on the external
channel's queue, homed on its owning granule per
``ChannelGraph.ext_home``).  A ``TxPort`` therefore never drops traffic:
packets that do not fit the device queue stay in a host-side buffer (the
host tier's credit) and are flushed at subsequent boundaries during
``run``.  Because boundaries land on the same cycles for every engine,
a host send/recv script produces bit-identical traffic on all of them
(property-tested in ``tests/test_session.py``).

**State ownership.**  The session owns the engine state: ``run`` donates
buffers into the compiled loops (``donate_argnums=0``), de-aliases
tied buffers first, and re-places distributed states at ``reset`` — the
sharp edges of the raw engine surface.  The legacy engine-state-threading
surface (``init(key)`` / ``run(state, n)`` / ``run_epochs(state, n)`` /
``push_external``) keeps working through deprecation shims on the facade,
and a state donated through a shim is *poisoned*: touching it afterwards
raises ``DonatedStateError`` instead of an opaque XLA deleted-buffer
crash.

**Probes and monitors** (the paper's PyMonitor): ``sim.probe(inst)``
returns one instance's live state on any engine; ``sim.stats()`` reports
cycle/epoch plus per-port handshake counters (and the single engine's
per-channel push/pop counts); ``sim.add_monitor(fn, every=...)`` samples a
host callback at epoch boundaries during ``run``.

**Spans and counters** (``repro.obs``; DESIGN.md §Observability).  Each
session boundary is an ``obs.trace.span`` — ``session.run``,
``session.dispatch`` (one per call of the engine's compiled loop),
``session.reset``, ``session.read`` (the cycle/epoch reads),
``session.tx_flush`` and ``session.rx_drain`` — on the profiler's clock,
so under ``jax.profiler`` they land beside the device's ops.  The same
boundaries bump the registry counters ``session.dispatches``,
``session.cycles``, ``session.tx.packets`` and ``session.rx.packets``.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import warnings
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from . import queue as qmod
from ..obs import trace as _trace
from ..obs.registry import REGISTRY
from ..obs.schema import STATS_SCHEMA

PyTree = Any

_ENGINE_KINDS = ("single", "graph", "fused", "register", "procs")
_DEFAULT_MAX_EPOCHS = 100_000


class DonatedStateError(RuntimeError):
    """A state whose buffers were donated into a compiled loop was reused."""


class _Donated:
    """Poison sentinel installed over a donated state's fields."""

    __slots__ = ("_api",)

    def __init__(self, api: str):
        object.__setattr__(self, "_api", api)

    def _fail(self, *a, **k):
        raise DonatedStateError(
            f"state was donated to {object.__getattribute__(self, '_api')}; "
            "use Simulation (which owns its state) or pass donate=False"
        )

    __getattr__ = __array__ = __iter__ = __len__ = __bool__ = _fail
    __getitem__ = __add__ = __mul__ = _fail

    def __repr__(self):
        return f"<donated state ({object.__getattribute__(self, '_api')})>"


def poison_donated(state: PyTree, api: str) -> None:
    """Overwrite a donated state's fields with a guard that raises a clear
    ``DonatedStateError`` on any later use (instead of XLA's deleted-buffer
    crash).  Mutates ``state`` in place; no-op for non-dataclass states."""
    if not dataclasses.is_dataclass(state):
        return
    guard = _Donated(api)
    for f in dataclasses.fields(state):
        object.__setattr__(state, f.name, guard)


class TxPort:
    """Host -> network queue handle for one ``external_in`` port (PySbTx).

    ``send``/``send_many`` never drop packets: what does not fit the
    device-side SPSC queue is buffered host-side (``pending``) and flushed
    at the next epoch boundary during ``Simulation.run`` — the host tier's
    credit protocol.
    """

    def __init__(self, sim: "Simulation", name: str):
        self._sim = sim
        self.name = name
        self.sent = 0  # handshakes into the device queue
        self._pending: collections.deque = collections.deque()

    @property
    def pending(self) -> int:
        """Packets buffered host-side, awaiting queue space."""
        return len(self._pending)

    def send(self, payload) -> bool:
        """Queue one packet.  Returns True if it landed in the device queue
        immediately (False: buffered until the next run boundary)."""
        return self.send_many([payload]) == 1

    def send_many(self, payloads) -> int:
        """Queue a batch (k, W).  Returns how many landed in the device
        queue now; the remainder is buffered and flushed during ``run``."""
        arr = np.atleast_2d(np.asarray(payloads, np.float64))
        for row in arr:
            self._pending.append(np.asarray(row))
        before = self.sent
        self._sim._flush_tx(self)
        return self.sent - before

    def __repr__(self):
        return (f"TxPort({self.name!r}, sent={self.sent}, "
                f"pending={self.pending})")


class RxPort:
    """Network -> host queue handle for one ``external_out`` port (PySbRx)."""

    def __init__(self, sim: "Simulation", name: str):
        self._sim = sim
        self.name = name
        self.received = 0

    def recv(self):
        """Pop one packet; returns its (W,) payload or None when empty."""
        out = self.drain(max_n=1)
        return out[0] if len(out) else None

    def drain(self, max_n: int | None = None) -> np.ndarray:
        """Pop up to ``max_n`` packets (all available by default).
        Returns a (k, W) array, k possibly 0."""
        return self._sim._drain_rx(self, max_n)

    def __repr__(self):
        return f"RxPort({self.name!r}, received={self.received})"


class Monitor:
    """A host callback sampled at epoch boundaries during ``run``.

    Cadence is counted on the GLOBAL boundary index (simulated cycle /
    period), not per ``run`` call — ten ``run(epochs=1)`` calls sample
    exactly like one ``run(epochs=10)``.
    """

    def __init__(self, sim: "Simulation", fn: Callable[["Simulation"], None],
                 every: int):
        self._sim = sim
        self.fn = fn
        self.every = max(int(every), 1)  # boundary cadence, in epochs
        self.samples = 0
        self._last = 0  # last global boundary index fired at

    def remove(self) -> None:
        if self in self._sim._monitors:
            self._sim._monitors.remove(self)

    def _fire(self):
        self.samples += 1
        self.fn(self._sim)


class Simulation:
    """One session facade over any engine (DESIGN.md §4).

    Lifecycle:  ``reset(key)`` -> [``tx``/``rx``/``probe``/``run``]* ->
    ``save``/``load``.  The raw engine stays reachable as ``.engine``;
    unknown attributes delegate to it, and the legacy state-threading
    surface keeps working via deprecation shims (with donated inputs
    poisoned — see ``DonatedStateError``).
    """

    def __init__(self, engine, *, period: int | None = None):
        kind = getattr(engine, "engine_kind", None)
        if kind not in _ENGINE_KINDS:
            raise TypeError(
                f"Simulation needs an engine with engine_kind in "
                f"{_ENGINE_KINDS}, got {type(engine).__name__}"
            )
        self.engine = engine
        self.kind = kind
        if period is not None and kind != "single":
            cpe = int(engine.cycles_per_epoch)
            if period % cpe:
                raise ValueError(
                    f"period={period} must be a multiple of the engine's "
                    f"epoch ({cpe} cycles)"
                )
        self._period = period
        self._state: PyTree | None = None
        self._tx_ports: dict[str, TxPort] = {}
        self._rx_ports: dict[str, RxPort] = {}
        self._monitors: list[Monitor] = []
        self._done_cache: dict[int, tuple] = {}  # anchor id -> (ref, jitted)
        # ``session.cycles`` accounting: the cycle the counted advances
        # reach, and whether an until-run (whose length only the device
        # knows) is still uncounted — settled at the next cycle read
        self._counted_cycle = 0
        self._uncounted = False
        graph = getattr(engine, "graph", None)
        self._ext_in = dict(graph.ext_in) if graph is not None else {}
        self._ext_out = dict(graph.ext_out) if graph is not None else {}
        # flight recorder: REPRO_TRACE=<path> arms the process-global
        # recorder (exported at interpreter exit); engines that carry
        # worker telemetry switch it on too
        if _trace.maybe_enable_from_env():
            st = getattr(engine, "set_tracing", None)
            if st is not None:
                st(True)

    # ------------------------------------------------------------- lifecycle
    @property
    def period(self) -> int:
        """Cycles between host boundaries (epoch length; the host tier's
        sync period).  Every engine's boundaries land on multiples of this,
        which is what makes host traffic engine-invariant."""
        if self._period is not None:
            return self._period
        return int(self.engine.cycles_per_epoch)

    def reset(self, key: int | jax.Array = 0, **init_kw) -> "Simulation":
        """(Re)initialize and take ownership of the engine state.

        ``key`` seeds per-block ``init_state`` (identically across engines;
        ignored by the register engine, whose operands live in the IR).
        Extra kwargs go to ``engine.init`` (e.g. ``cell_params=``,
        ``group_params=``).  Distributed states are placed on the mesh.
        """
        if self._uncounted:
            _ = self.cycle  # count the old state's until-runs first
        with _trace.span("session.reset"):
            if self.kind == "register":
                state = self.engine.init(**init_kw)
            else:
                if isinstance(key, int):
                    key = jax.random.key(key)
                state = self.engine.init(key, **init_kw)
            if hasattr(self.engine, "place"):
                state = self.engine.place(state)
        self._state = state
        self._counted_cycle = 0  # every engine's init starts at cycle 0
        for p in self._tx_ports.values():
            p.sent = 0
            p._pending.clear()
        for p in self._rx_ports.values():
            p.received = 0
        for m in self._monitors:
            m.samples = 0
            m._last = 0
        return self

    @property
    def state(self) -> PyTree:
        """The live engine state.  Read-only by convention: the session
        donates these buffers into the next ``run``, so hold results (e.g.
        from ``probe``), not this object."""
        return self._require_state()

    def _require_state(self) -> PyTree:
        if self._state is None:
            raise RuntimeError("call reset(key) before using the session")
        if isinstance(getattr(self._state, "cycle", None), _Donated):
            self._state.cycle._fail()  # raises DonatedStateError
        return self._state

    @property
    def cycle(self) -> int:
        """Current simulated cycle (identical on every granule at a
        boundary, which is the only time the host observes it).  Waits
        for the device, and counts into ``session.cycles`` the cycles of
        until-runs since the last read."""
        st = self._require_state()
        with _trace.span("session.read"):
            cyc = int(np.asarray(jax.device_get(st.cycle)).ravel()[0])
        if self._uncounted:
            REGISTRY.inc("session.cycles", float(cyc - self._counted_cycle))
            self._counted_cycle = cyc
            self._uncounted = False
        return cyc

    @property
    def epoch(self) -> int:
        st = self._require_state()
        if not hasattr(st, "epoch"):
            return self.cycle // max(self.period, 1)
        with _trace.span("session.read"):
            return int(np.asarray(jax.device_get(st.epoch)).ravel()[0])

    def block_until_ready(self) -> "Simulation":
        jax.block_until_ready(self._require_state())
        return self

    # ----------------------------------------------------------------- ports
    def tx(self, name: str) -> TxPort:
        """Host Tx queue handle for external-in port ``name``."""
        if name not in self._ext_in:
            have = sorted(self._ext_in) or "none (graph has no external-in)"
            raise KeyError(f"no external-in port {name!r}; available: {have}")
        if name not in self._tx_ports:
            self._tx_ports[name] = TxPort(self, name)
        return self._tx_ports[name]

    def rx(self, name: str) -> RxPort:
        """Host Rx queue handle for external-out port ``name``."""
        if name not in self._ext_out:
            have = sorted(self._ext_out) or "none (graph has no external-out)"
            raise KeyError(f"no external-out port {name!r}; available: {have}")
        if name not in self._rx_ports:
            self._rx_ports[name] = RxPort(self, name)
        return self._rx_ports[name]

    def _flush_tx(self, port: TxPort) -> int:
        """Push as many of ``port``'s pending packets as fit (host tier
        credit = the external queue's free space)."""
        st = self._require_state()
        cap = int(self.engine.capacity)
        moved = 0
        with _trace.span("session.tx_flush"):
            while port._pending:
                batch = [port._pending[i]
                         for i in range(min(len(port._pending), cap - 1))]
                st, n = self.engine.host_push_many(st, port.name,
                                                   np.stack(batch))
                n = int(n)
                for _ in range(n):
                    port._pending.popleft()
                port.sent += n
                moved += n
                if n < len(batch):
                    break  # queue full — the rest waits for the next boundary
        self._state = st
        REGISTRY.inc("session.tx.packets", float(moved))
        return moved

    def _flush_all_tx(self) -> None:
        for port in self._tx_ports.values():
            if port._pending:
                self._flush_tx(port)

    def _drain_rx(self, port: RxPort, max_n: int | None) -> np.ndarray:
        st = self._require_state()
        cap = int(self.engine.capacity)
        W = int(self.engine.W if hasattr(self.engine, "W")
                else self.engine.payload_words)
        out: list[np.ndarray] = []
        with _trace.span("session.rx_drain"):
            while max_n is None or len(out) < max_n:
                ask = (cap - 1 if max_n is None
                       else min(cap - 1, max_n - len(out)))
                st, pays, cnt = self.engine.host_pop_many(st, port.name, ask)
                cnt = int(cnt)
                out.extend(np.asarray(jax.device_get(pays))[:cnt])
                port.received += cnt
                if cnt < ask:
                    break
        self._state = st
        REGISTRY.inc("session.rx.packets", float(len(out)))
        if not out:
            return np.zeros((0, W), np.float32)
        return np.stack(out)

    # ------------------------------------------------------ probes / monitors
    def probe(self, inst) -> PyTree:
        """One instance's live (unstacked) state — uniform across engines.
        ``inst`` is an ``Instance`` or a global instance id."""
        return self.engine.group_state(self._require_state(), inst)

    def stats(self) -> dict:
        """Cycle/epoch counters plus per-port state, behind the ONE
        validated schema on every engine (``repro-stats-v1``; see
        ``repro.obs.schema.validate_stats``): each tx/rx entry nests the
        session counters (sent/pending resp. received) AND the port's
        live queue occupancy/credit — device-queue occupancy on the
        in-process engines, shm-ring + owning-worker occupancy on the
        ``procs`` runtime.  Engine-specific extras (e.g. the single
        engine's per-channel push/pop handshake counts) live under
        ``"detail"`` — the only key allowed to diverge per engine — and
        ``"metrics"`` is a snapshot of the process-global registry."""
        st = self._require_state()
        ps = getattr(self.engine, "port_stats", None)
        occ = ps(st) if ps is not None else {}

        def _occ(direction: str, name: str) -> dict:
            rec = occ.get(direction, {}).get(name, {})
            return {"occupancy": int(rec.get("occupancy", 0)),
                    "credit": int(rec.get("credit", 0))}

        d: dict[str, Any] = {
            "schema": STATS_SCHEMA,
            "engine": self.kind,
            "cycle": self.cycle,
            "epoch": self.epoch,
            "ports": {
                "tx": {n: {"sent": p.sent, "pending": p.pending,
                           **_occ("tx", n)}
                       for n, p in self._tx_ports.items()},
                "rx": {n: {"received": p.received, **_occ("rx", n)}
                       for n, p in self._rx_ports.items()},
            },
        }
        if self.kind == "single":
            d["detail"] = {
                "push_count": np.asarray(jax.device_get(st.push_count)),
                "pop_count": np.asarray(jax.device_get(st.pop_count)),
            }
        fs = getattr(self.engine, "fault_stats", None)
        if fs is not None:
            # the procs runtime's self-healing surface (ISSUE 8): policy,
            # restart count, snapshot cadence/epoch, replayed epochs
            d["faults"] = fs()
        bs = getattr(self.engine, "bridge_stats", None)
        if bs is not None:
            # multi-host fleets (ISSUE 9): one row per TCP ring bridge —
            # bytes/slabs/credits each way, credit RTT, wait fraction
            # (steady-state pump only; cold-start under "connect_s")
            rows = bs()
            if rows:
                d["bridges"] = rows
        d["metrics"] = REGISTRY.snapshot()
        return d

    @contextlib.contextmanager
    def trace(self, path: str):
        """Flight-recorder window: record span/instant events (and, on the
        procs engine, per-worker phase telemetry) for the body, then
        export a Perfetto/Chrome-loadable ``trace.json`` to ``path``::

            with sim.trace("/tmp/trace.json"):
                sim.run(epochs=200)

        Tracing changes no simulated behavior — final state and host Rx
        traffic stay bit-identical to an untraced run (tested in
        ``tests/test_obs.py``).  The ``REPRO_TRACE=<path>`` env knob is
        the non-contextual variant (exports at interpreter exit)."""
        rec = _trace.recorder()
        prev = rec.enabled
        rec.enabled = True
        st = getattr(self.engine, "set_tracing", None)
        if st is not None:
            st(True)
        try:
            yield self
        finally:
            try:
                flush = getattr(self.engine, "flush_telemetry", None)
                if flush is not None:
                    flush()
                if st is not None:
                    st(False)
            finally:
                rec.export(path)
                rec.enabled = prev

    def add_monitor(self, fn: Callable[["Simulation"], None],
                    every: int = 1) -> Monitor:
        """Register a host callback fired every ``every`` epoch boundaries
        during ``run`` (the paper's PyMonitor).  Returns a removable
        handle."""
        mon = Monitor(self, fn, every)
        self._monitors.append(mon)
        return mon

    # ------------------------------------------------------------------- run
    def _dispatch(self, call: Callable, cycles: int | None) -> None:
        """One call of the engine's compiled loop on the owned state (which
        it donates), as the ``session.dispatch`` span.  The span times the
        enqueue — JAX returns before the device is done — not the device's
        work.  ``cycles`` is how far the call advances, or None for an
        until-run, counted at the next cycle read."""
        st = self._require_state()
        with _trace.span("session.dispatch"):
            self._state = call(st)
        REGISTRY.inc("session.dispatches")
        if cycles is None:
            self._uncounted = True
        else:
            REGISTRY.inc("session.cycles", float(cycles))
            self._counted_cycle += cycles

    def _advance_epochs(self, n_epochs: int) -> None:
        """``n_epochs`` boundary periods through the engine's compiled
        loop, donating the owned state."""
        if n_epochs <= 0:
            return
        n_cycles = n_epochs * self.period
        if self.kind == "single":
            self._dispatch(
                lambda st: self.engine.run(st, n_cycles, donate=True),
                n_cycles)
        else:
            per = self.period // int(self.engine.cycles_per_epoch)
            self._dispatch(
                lambda st: self.engine.run_epochs(st, n_epochs * per,
                                                  donate=True),
                n_cycles)

    def _advance_cycles_single(self, n_cycles: int) -> None:
        if n_cycles > 0:
            self._dispatch(
                lambda st: self.engine.run(st, n_cycles, donate=True),
                n_cycles)

    def _host_done(self, done_fn, cache_key=None) -> bool:
        """Evaluate an engine-view predicate on the host (between chunks).

        The predicate sees exactly what the engine's compiled ``run_until``
        would show it: the full state (single), the granule-local state
        via ``_done_view`` (graph family), or the cell dict (register).
        The evaluator is jitted once per predicate (anchor-keyed like the
        engines' compiled loops), so per-epoch checks don't retrace.
        """
        st = self._require_state()
        if self.kind == "procs":
            # worker states never enter this process's jit: the engine
            # gathers each granule's view and evaluates host-side
            return bool(self.engine.eval_done(st, done_fn))
        anchor = cache_key if cache_key is not None else done_fn
        key = id(anchor)
        if key not in self._done_cache:
            if self.kind == "single":
                def ev(s):
                    return done_fn(s)
            elif self.kind == "register":
                G = self.engine.Dr * self.engine.Dc

                def ev(s):
                    flat = jax.tree.map(
                        lambda x: jnp.reshape(x, (G,) + jnp.shape(x)[2:]),
                        s.cell,
                    )
                    return jax.vmap(done_fn)(flat).all()
            else:
                nd, G = self.engine.nd, self.engine.G

                def ev(s):
                    local = jax.tree.map(
                        lambda x: jnp.reshape(x, (G,) + jnp.shape(x)[nd:]), s
                    )
                    return jax.vmap(
                        lambda g: done_fn(self.engine._done_view(g))
                    )(local).all()
            self._done_cache[key] = (anchor, jax.jit(ev))
        return bool(jax.device_get(self._done_cache[key][1](st)))

    def _session_run(
        self,
        cycles: int | None = None,
        *,
        epochs: int | None = None,
        until: Callable | None = None,
        max_cycles: int | None = None,
        max_epochs: int | None = None,
        cache_key: Any = None,
    ) -> "Simulation":
        """Advance the simulation (the one lifecycle verb) — this is the
        implementation behind ``run(cycles=... | epochs=... | until=...)``
        (``run`` itself also dispatches the legacy ``run(state, n)`` shim).

        cycles / epochs:  advance at least this far (cycles round UP to
            whole boundary periods on epoch-batched engines).
        until:  run until a predicate holds everywhere, within the
            ``max_cycles``/``max_epochs`` budget (relative to now; default
            100k epochs).  The predicate sees the engine's ``run_until``
            view.  ``cache_key`` pins the engine's compiled-loop cache
            when the predicate is a fresh lambda per call.

        Pending Tx packets are flushed and monitors sampled at every
        boundary (``period`` cycles); with no monitors and no pending
        traffic the whole run is a single compiled call.
        """
        if (cycles is None) + (epochs is None) + (until is None) < 2:
            raise TypeError("run() takes exactly one of cycles/epochs/until")
        self._require_state()
        with _trace.span("session.run"):
            return self._run_body(cycles, epochs, until, max_cycles,
                                  max_epochs, cache_key)

    def _run_body(self, cycles, epochs, until, max_cycles, max_epochs,
                  cache_key) -> "Simulation":
        self._flush_all_tx()

        if until is not None:
            return self._run_until(until, max_cycles, max_epochs, cache_key)
        if cycles is None and epochs is None:
            raise TypeError("run() needs cycles=, epochs= or until=")

        per = self.period
        n_ep = int(epochs) if epochs is not None else -(-int(cycles) // per)
        exact_cycles = (
            int(cycles) if (cycles is not None and self.kind == "single")
            else None
        )

        chunk = self._boundary_chunk()
        if chunk is None:  # no boundary work: one compiled call
            if exact_cycles is not None:
                self._advance_cycles_single(exact_cycles)
            else:
                self._advance_epochs(n_ep)
            return self

        total_c = exact_cycles if exact_cycles is not None else n_ep * per
        done_c = 0
        while done_c < total_c:
            if chunk == 1:
                step_c = min(per, total_c - done_c)
            else:
                # align chunks to the GLOBAL boundary grid so monitor
                # cadences are invariant to how runs are sliced
                cur_b = self.cycle // per
                step_c = min((chunk - cur_b % chunk) * per, total_c - done_c)
            if exact_cycles is not None:
                self._advance_cycles_single(step_c)
            else:
                self._advance_epochs(step_c // per)
            done_c += step_c
            self._boundary()
        return self

    def _boundary_chunk(self) -> int | None:
        """Epochs between host boundaries, or None when nothing needs
        them (single compiled call).  The gcd of the monitor cadences, so
        boundaries land on every multiple of every monitor's ``every``
        (min would silently skip non-dividing cadences)."""
        import math

        cadences = [m.every for m in self._monitors]
        if any(p._pending for p in self._tx_ports.values()):
            cadences.append(1)
        if not cadences:
            return None
        g = cadences[0]
        for c in cadences[1:]:
            g = math.gcd(g, c)
        return g

    def _boundary(self) -> None:
        self._flush_all_tx()
        if not self._monitors:
            return
        cyc = self.cycle
        if cyc % self.period:
            return  # mid-period (single-engine exact-cycle remainder)
        b = cyc // self.period  # global boundary index
        for mon in list(self._monitors):
            if b and b % mon.every == 0 and b != mon._last:
                mon._last = b
                mon._fire()
                REGISTRY.inc("session.monitor.fired")

    def _run_until(self, done_fn, max_cycles, max_epochs, cache_key):
        per = self.period
        if max_cycles is not None and max_epochs is not None:
            raise TypeError("pass max_cycles or max_epochs, not both")
        if max_epochs is None:
            max_epochs = (
                -(-int(max_cycles) // per) if max_cycles is not None
                else _DEFAULT_MAX_EPOCHS
            )
        chunk = self._boundary_chunk()
        if chunk is None:
            # straight to the engine's compiled while-loop; the budget is
            # relative, so repeated interactive calls share one compilation
            if self.kind == "single":
                budget = {"max_cycles": max_epochs * per}
            else:
                per_engine = per // int(self.engine.cycles_per_epoch)
                budget = {"max_epochs": max_epochs * per_engine}
            self._dispatch(
                lambda st: self.engine.run_until(
                    st, done_fn, cache_key=cache_key, donate=True, **budget),
                None)
            return self
        # chunked: cached one-epoch runs + the host-side predicate, checked
        # every epoch — the same cadence as the compiled while-loop, so an
        # attached monitor never changes where an until-run stops
        ran = 0
        while ran < max_epochs and not self._host_done(done_fn, cache_key):
            self._advance_epochs(1)
            ran += 1
            self._boundary()
        return self

    # ---------------------------------------------------------- checkpoints
    def save(self, path: str, step: int | None = None, *,
             keep_last: int = 3) -> str:
        """Checkpoint the session (engine state + host-port buffers) under
        ``path`` via ``checkpoint.checkpointing`` (atomic tmp+rename).
        Returns the written directory."""
        from ..checkpoint import checkpointing

        st = self._require_state()
        if hasattr(self.engine, "gather_state"):
            # engines whose state lives elsewhere (the multiprocess
            # runtime) hand the facade a shape-stable gathered tree
            st = self.engine.gather_state(st)
        if step is None:
            step = self.cycle
        meta = {
            "engine_kind": self.kind,
            "cycle": self.cycle,
            "ports": {
                "tx": {
                    n: {"sent": p.sent,
                        "pending": [np.asarray(r).tolist()
                                    for r in p._pending]}
                    for n, p in self._tx_ports.items()
                },
                "rx": {n: {"received": p.received}
                       for n, p in self._rx_ports.items()},
            },
        }
        return checkpointing.save(path, step, st, meta=meta,
                                  keep_last=keep_last)

    def load(self, path: str, step: int | None = None) -> "Simulation":
        """Restore a checkpoint into this session (elastic resharding: the
        current state is the template, so a different mesh works).  Call
        ``reset`` first so a template exists."""
        from ..checkpoint import checkpointing

        template = self._require_state()
        gathered = hasattr(self.engine, "gather_state")
        if gathered:
            template = self.engine.gather_state(template)
        tree, meta = checkpointing.restore(path, template, step)
        if meta.get("engine_kind") not in (None, self.kind):
            raise ValueError(
                f"checkpoint was saved from engine "
                f"{meta['engine_kind']!r}, this session is {self.kind!r}"
            )
        if self._uncounted:
            _ = self.cycle  # count the old state's until-runs first
        if gathered:
            self._state = self.engine.scatter_state(self._require_state(), tree)
        else:
            self._state = tree
        self._counted_cycle = int(meta.get("cycle", 0))
        for n, rec in meta.get("ports", {}).get("tx", {}).items():
            port = self.tx(n)
            port.sent = int(rec.get("sent", 0))
            port._pending = collections.deque(
                np.asarray(r) for r in rec.get("pending", [])
            )
        for n, rec in meta.get("ports", {}).get("rx", {}).items():
            self.rx(n).received = int(rec.get("received", 0))
        return self

    # ------------------------------------------------------ deprecation shims
    # The pre-session surface: explicit engine-state threading.  Each shim
    # warns, delegates to the engine, and poisons donated inputs so stale
    # reuse raises DonatedStateError instead of an XLA crash.
    def _shim(self, old: str, new: str) -> None:
        warnings.warn(
            f"Simulation.{old} is the legacy engine-state-threading surface;"
            f" use {new} (see DESIGN.md §4 migration notes)",
            DeprecationWarning, stacklevel=3,
        )

    def init(self, *args, **kw):
        self._shim("init(...)", "reset(key)")
        return self.engine.init(*args, **kw)

    def run_epochs(self, state, n_epochs, **kw):
        self._shim("run_epochs(state, n)", "run(epochs=n)")
        out = self.engine.run_epochs(state, n_epochs, **kw)
        if kw.get("donate", True):
            poison_donated(state, "run_epochs")
        return out

    def run_cycles(self, state, n_cycles):
        self._shim("run_cycles(state, n)", "run(cycles=n)")
        out = self.engine.run_cycles(state, n_cycles)
        poison_donated(state, "run_cycles")  # run_cycles always donates
        return out

    def run_until(self, state, done_fn, max_epochs, **kw):
        self._shim("run_until(state, ...)", "run(until=...)")
        out = self.engine.run_until(state, done_fn, max_epochs, **kw)
        if kw.get("donate", True):
            poison_donated(state, "run_until")
        return out

    def run_until_done(self, state, max_epochs, **kw):
        self._shim("run_until_done(state, ...)", "run(until=...)")
        out = self.engine.run_until_done(state, max_epochs, **kw)
        if kw.get("donate", True):
            poison_donated(state, "run_until_done")
        return out

    def push_external(self, state, name, payload):
        self._shim("push_external(state, ...)", "tx(name).send(...)")
        return self.engine.host_push(state, name, payload)

    def pop_external(self, state, name):
        self._shim("pop_external(state, ...)", "rx(name).recv()")
        return self.engine.host_pop(state, name)

    def run(self, *args, **kw):
        """``run(cycles=... | epochs=... | until=...)`` — see
        ``_session_run``.  Also accepts the legacy ``run(state, n_cycles)``
        call shape as a deprecation shim."""
        if args and not isinstance(args[0], (int, np.integer)):
            # legacy: run(state, n_cycles) on the single engine
            self._shim("run(state, n)", "run(cycles=n)")
            out = self.engine.run(*args, **kw)
            if kw.get("donate", False):
                poison_donated(args[0], "run")
            return out
        if args:
            kw.setdefault("cycles", int(args[0]))
        return self._session_run(**kw)

    def __getattr__(self, name: str):
        # Anything the facade does not define delegates to the engine
        # (group_state, gather_group, classes, place, step, graph, ...).
        if name.startswith("__") or name == "engine":
            raise AttributeError(name)
        return getattr(self.engine, name)

    def __repr__(self):
        st = "reset" if self._state is not None else "unreset"
        return (f"Simulation(engine={type(self.engine).__name__}, "
                f"kind={self.kind!r}, {st})")
