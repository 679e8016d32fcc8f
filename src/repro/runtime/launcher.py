"""Multiprocess launcher — ``Network.build(engine="procs")`` (paper §III,
DESIGN.md §Runtime).

``ProcsEngine`` is the fifth engine: it realizes the paper's deployment
model *literally* — one free-running OS process per granule, connected at
runtime by shared-memory SPSC queues — behind the same ``Simulation``
facade as the in-process engines.  The division of labor:

  * ``graph.lower_partition`` assigns every channel its granule-local
    queue (the same lowering the shard_map engines consume, so the
    granule state layouts are bit-identical);
  * the launcher creates one slab ring + one credit ring per boundary
    channel and one packet ring per external port
    (``runtime.shmem.ShmRing``), spawns one worker per granule
    (``runtime.worker``), and speaks the session protocol to them over
    command pipes: ``init`` / ``run`` / ``probe`` / ``stats`` /
    checkpoint ``gather``/``scatter``;
  * host Tx/Rx ports read and write the external rings directly — host
    I/O never interrupts a running worker, it lands at the worker's next
    epoch boundary exactly like the in-process engines' host tier.

**Prebuilt-simulator cache**: before spawning anything, the launcher
AOT-compiles one granule simulator per *distinct granule signature*
(``jit(...).lower().compile()`` into the shared JAX persistent
compilation cache).  Workers then compile against a warm cache, so build
time grows with unique granule shapes — O(#block kinds), not
O(#instances) — the paper's flat-build-time property.

**Failure surface** (``runtime.fault_tolerance``): every reply wait polls
worker exitcodes (ANY exit while replies are pending, clean or not) and
per-epoch heartbeats; a dead or silent worker raises ``WorkerDiedError``
with that worker's captured log tail, and the remaining workers are torn
down — never a hang on a half-dead fleet.  When the WHOLE fleet goes
quiet, the per-worker "blocked on ring X" status words in the heartbeat
shm are decoded into the credit wait-for graph: a cycle raises
``FleetStallError`` naming the deadlock, an acyclic graph names the root
worker.  Checked rings surface slab corruption as
``RingCorruptionError`` (``runtime.shmem``).

**Self-healing** (``runtime.recovery``, ISSUE 8): with
``on_fault="recover"`` (env ``REPRO_ON_FAULT``) the engine takes
coordinated snapshots every ``snapshot_every`` epochs at command
boundaries (the fleet is quiesced there, so ``gather_state`` is a
consistent cut) and, on any recoverable fault, tears down the remnant
fleet, respawns workers from the warm prebuilt-simulator cache,
scatters the last snapshot, and replays the lost epochs — final state
and host Rx traffic bit-identical to a fault-free run.  Deterministic
drills via ``runtime.faultinject`` (``REPRO_FAULT_PLAN``).
"""
from __future__ import annotations

import atexit
import dataclasses
import os
import pickle
import secrets
import socket
import tempfile
import time
import weakref
from multiprocessing import get_context, shared_memory
from typing import Any, Callable, Sequence

import numpy as np

from ..core import queue as qmod
from ..core.compile_cache import enable_compile_cache
from ..kernels import granule_step
from ..obs import telemetry as _telem
from ..obs import trace as _trace
from ..obs.registry import REGISTRY
from ..obs.telemetry import telemetry_ring_name
from ..core.graph import (
    ChannelGraph, PartitionLowering, PartitionTree, Tier, lower_partition,
    normalize_partition, normalize_tiers,
)
from . import fleet as _fleet
from .bridge import BridgeChannel, BridgeSpec, bridge_entry
from .fault_tolerance import (
    FleetStallError, LinkDownError, ProcessMonitor, WorkerDiedError,
    find_stall_cycle, read_log_tail, stall_wait_edges,
)
from .faultinject import actions_for, resolve_fault_plan, split_plan
from .recovery import RecoveryController, resolve_on_fault
from .shmem import RingCorruptionError, RingTimeout, ShmRing, slab_slot_bytes
from .worker import (
    HB_RECORD_BYTES, HB_RECORD_F64, BatchSpec, BatchedGranuleSim, GranuleSim,
    GranuleSpec, GroupSpec, TierSpec,
    credit_ring_name, data_ring_name, ext_ring_name, worker_entry,
)

PyTree = Any


def _worker_mp_context():
    """Multiprocessing context for worker processes.

    Default is a ``forkserver`` preloaded with ``repro.runtime.worker``:
    the server pays the jax/repro import ONCE, then every worker — and
    critically every recovery *respawn* — is a cheap fork of it.  Safe
    because importing the worker module initializes no XLA backend and
    starts no threads (each fork creates its own client); the server
    starts inside the ``_child_env`` window, so its frozen environment is
    the canonical single-CPU-device worker env.  ``REPRO_WORKER_SPAWN=
    spawn`` restores plain spawn (each worker re-imports jax, several
    seconds apiece)."""
    method = os.environ.get("REPRO_WORKER_SPAWN", "forkserver")
    if method not in ("forkserver", "spawn"):
        raise ValueError(
            f"REPRO_WORKER_SPAWN={method!r}: expected 'forkserver' or "
            "'spawn'"
        )
    if method == "forkserver":
        try:
            ctx = get_context("forkserver")
            ctx.set_forkserver_preload(["repro.runtime.worker"])
            return ctx
        except (ValueError, OSError):  # platform without forkserver
            pass
    return get_context("spawn")

# Engines are tracked weakly: a garbage-collected engine tears itself down
# via __del__, and whatever is still alive at interpreter exit is closed
# here — worker processes and shm segments never outlive the launcher.
_live_engines: "weakref.WeakSet[ProcsEngine]" = weakref.WeakSet()


def _close_all_engines() -> None:  # pragma: no cover - interpreter exit
    for eng in list(_live_engines):
        try:
            eng.close()
        except Exception:
            pass


atexit.register(_close_all_engines)


@dataclasses.dataclass
class ProcsState:
    """The session's handle on a running fleet — a *reference*, not the
    state itself: granule state lives in the workers (that is the point).
    The handle carries the boundary-synchronized counters plus a
    generation stamp so a stale handle (pre-reset) fails loudly."""

    cycle: np.ndarray  # () int32 — identical on every worker at a boundary
    epoch: np.ndarray  # () int32
    generation: int

    def replace(self, **kw) -> "ProcsState":
        return dataclasses.replace(self, **kw)


class ProcsEngine:
    """Free-running multiprocess engine over a partitioned ChannelGraph.

    graph:      the channel-graph IR.
    partition:  ``PartitionTree`` (tiered), or any flat instance->granule
                map ``normalize_partition`` accepts (with ``n_workers``/
                ``tiers``); granule ids are worker indices.
    n_workers:  worker count for flat partitions (default: max granule+1).
    K:          innermost sync rate (cycles between boundary exchanges).
    tiers:      optional ``(axes, K)`` spec with ``axis_sizes`` supplied by
                a PartitionTree — procs needs no mesh, so pass tiered
                layouts via PartitionTree.
    ring_depth: slab records a boundary ring buffers (>= 2; staleness
                slack for the slab data — the credit chain already bounds
                epoch drift at one exchange period per channel).
    timeout:    seconds a worker waits on a ring / the launcher waits on a
                silent worker before declaring it dead.
    prebuild:   AOT-compile each distinct granule signature in-launcher
                (warming the persistent cache) before any worker spawns.
    cache_dir:  JAX persistent compilation cache directory (shared);
                None follows ``core.compile_cache``'s rule.
    batch_signatures:
                group same-signature granules (``lowering.batch_plan``)
                into ONE worker process each, stepping the whole group as
                a leading-axis batch with a single vmapped dispatch per
                program op — fewer processes and fewer dispatches for
                replicated designs, bit-identical traffic (the batch is a
                legal lockstep refinement of the free-running schedule).
    overlap:    split every tier exchange into issue (drain + push) and
                commit (pop + fill) phases — at a boundary all outgoing
                slabs are pushed before the worker blocks on any incoming
                one (send-early/receive-late), so peer latencies overlap
                instead of adding.  Bit-identical traffic (the credit
                protocol per channel is unchanged).  "auto"/bool with
                ``REPRO_OVERLAP`` env override; auto = off.
    on_fault:   "raise" (default) propagates the first fleet fault;
                "recover" auto-heals: snapshot periodically, and on a
                dead/hung/corrupted/deadlocked fleet respawn + restore +
                replay (``runtime.recovery``).  "auto"/str with
                ``REPRO_ON_FAULT`` env override; auto = raise.
    snapshot_every:
                coordinated-snapshot cadence in epochs (recover mode; the
                snapshot is taken at the first command boundary on each
                multiple, where the fleet is quiesced).  The default
                trades the steady-state gather tax (benchmarked at
                ~1.2x a raise-mode run on the smoke wafer, budget 1.5x)
                against the replay bound of one cadence of epochs.
    max_restarts:
                recovery attempts before giving up (the original fault is
                re-raised, chained).
    backoff_s:  base of the exponential respawn backoff (doubles per
                consecutive restart).
    fault_plan: deterministic fault injection for drills — a plan string
                (see ``runtime.faultinject``) or a sequence of
                ``FaultAction``; default: env ``REPRO_FAULT_PLAN``.
                Link-fault kinds (``linkkill``/``linkslow``/``linkcorrupt``)
                target bridged links and are executed launcher-side at
                epoch boundaries.
    hosts:      multi-host fleet placement (ISSUE 9): a host count, comma
                list of names, ``{host: [granule, ...]}`` dict, or a
                ``runtime.fleet.HostPlan``; default env ``REPRO_HOSTS``,
                else single-host.  The partition's granules are sharded
                across that many launcher processes, connected ONLY by TCP
                ring bridges (``runtime.bridge``) — traffic, state, and
                the per-tier staleness bound are bit-identical to the
                single-host engine.
    host:       which plan host THIS engine instance is (internal: set by
                ``fleet.follower_entry``; user code leaves it None and
                gets the leader).
    base_port:  deterministic bridge/control port base (link i listens on
                ``base_port + i``); default env ``REPRO_BRIDGE_PORT``,
                else ephemeral ports exchanged at rendezvous.
    """

    engine_kind = "procs"

    def __init__(
        self,
        graph: ChannelGraph,
        partition=None,
        *,
        n_workers: int | None = None,
        K: int = 1,
        tiers: Sequence | None = None,
        ring_depth: int = 2,
        timeout: float = 60.0,
        prebuild: bool = True,
        cache_dir: str | None = None,
        log_dir: str | None = None,
        batch_signatures: bool = False,
        overlap: Any = "auto",
        on_fault: str = "auto",
        snapshot_every: int = 16,
        max_restarts: int = 3,
        backoff_s: float = 0.25,
        fault_plan: Any = None,
        hosts: Any = None,
        host: str | None = None,
        base_port: int | None = None,
    ):
        self.graph = graph
        if isinstance(partition, PartitionTree):
            if tiers is not None:
                raise ValueError("pass tiers via the PartitionTree, not both")
            ptree = partition
        else:
            if tiers is not None:
                tspec = normalize_tiers(tiers)
                raise ValueError(
                    "procs has no mesh to size tier axes "
                    f"{[t.axes for t in tspec]} — pass a PartitionTree"
                )
            if n_workers is None:
                part0 = normalize_partition(graph, partition, 1 << 30)
                n_workers = int(part0.max()) + 1 if part0.size else 1
            part = normalize_partition(graph, partition, n_workers)
            ptree = PartitionTree(
                part, (Tier(axes=("w",), K=int(K)),), {"w": int(n_workers)}
            )
        self.ptree = ptree
        self.tiers = ptree.tiers
        self.K_tiers = ptree.K_tiers
        self.periods = ptree.periods()
        self.cycles_per_epoch = ptree.cycles_per_epoch
        self.K = self.K_tiers[-1]
        self.G = ptree.n_granules
        self.n_workers = self.G
        self.E_tiers = tuple(min(p, graph.capacity - 1) for p in self.periods)
        self.W = graph.payload_words
        self.payload_words = graph.payload_words
        self.capacity = graph.capacity
        self.dtype = np.dtype(graph.dtype if graph.dtype is not None
                              else np.float32)
        self.part = ptree.part
        # A boundary slab ring must hold one exchange window in flight PLUS
        # the next window the overlapped (send-early/receive-late) schedule
        # pushes before the previous one is consumed.  Shallower rings
        # deadlock the free-running fleet (historically surfacing only as
        # the CI watchdog timeout) — fail fast at build time instead.
        ring_depth = int(ring_depth)
        if ring_depth < 2:
            raise ValueError(
                f"ring_depth={ring_depth} is too shallow: boundary slab "
                f"rings must hold two exchange windows (>= 2 slab records "
                f"of E_t slots each; tier slab depths E_t={self.E_tiers}) "
                f"so the overlapped schedule can push window w+1 before "
                f"window w is consumed — a shallower ring deadlocks the "
                f"free-running fleet instead of failing fast"
            )
        self.ring_depth = ring_depth
        self.overlap = granule_step.resolve_overlap(overlap)
        self.timeout = float(timeout)
        self.cache_dir = cache_dir
        self.on_fault = resolve_on_fault(on_fault)
        self.fault_plan = resolve_fault_plan(fault_plan)
        self._incarnation = 0  # bumped on every recovery respawn

        low = lower_partition(graph, ptree)
        self.lowering = low
        self.n_local = low.n_local
        self._chan_owner = low.chan_owner
        self._tx_local, self._rx_local = low.tx_local, low.rx_local

        self._ring_prefix = f"sb{os.getpid() % 100000:x}{secrets.token_hex(3)}"
        self._log_dir = log_dir or tempfile.mkdtemp(prefix="repro_procs_")
        self._specs = [self._granule_spec(g) for g in range(self.G)]
        self.signatures = [s.signature for s in self._specs]

        # ---- signature-batch plan: one worker per granule, or (with
        # batch_signatures) one worker per signature group stepping the
        # whole group as a leading-axis batch
        self.batch_signatures = bool(batch_signatures)
        if self.batch_signatures:
            groups, where = low.batch_plan()
            self._worker_members = [tuple(ms) for ms in groups]
            self._worker_of = {g: b for g, (b, r) in where.items()}
            self._row_of = {g: r for g, (b, r) in where.items()}
        else:
            self._worker_members = [(g,) for g in range(self.G)]
            self._worker_of = {g: g for g in range(self.G)}
            self._row_of = {g: 0 for g in range(self.G)}
        self._wspecs: list[Any] = [
            self._specs[ms[0]] if len(ms) == 1
            else BatchSpec(members=ms, specs=[self._specs[g] for g in ms])
            for ms in self._worker_members
        ]
        self._is_batch = [isinstance(s, BatchSpec) for s in self._wspecs]
        self.NW = len(self._wspecs)
        # channel id -> (producer worker, consumer worker) of its slab
        # direction: the topology the stall diagnoser decodes status
        # words against
        self._chan_workers = {
            c: (self._worker_of[s], self._worker_of[d])
            for (t, s, d), chans in self.lowering.routes.items()
            for c in chans
        }
        self._chan_tier = {c: t
                           for (t, _s, _d), chans in self.lowering.routes.items()
                           for c in chans}

        # ---- multi-host fleet placement (ISSUE 9; ``runtime.fleet``):
        # shard the worker set over named hosts, one launcher process per
        # host, cross-host channels carried by TCP ring bridges
        self.host_plan = _fleet.resolve_host_plan(hosts, self.G)
        if host is not None and self.host_plan is None:
            raise ValueError(
                "host= names a fleet member but no multi-host plan was "
                "given (pass hosts=)")
        self.host = (host if host is not None
                     else (self.host_plan.leader if self.host_plan else None))
        self.is_leader = (self.host_plan is None
                          or self.host == self.host_plan.leader)
        if self.host_plan is not None:
            if self.host not in self.host_plan.hosts:
                raise ValueError(f"host {self.host!r} is not in the plan "
                                 f"{self.host_plan.hosts}")
            for w, ms in enumerate(self._worker_members):
                hs = sorted({self.host_plan.host_of(g) for g in ms})
                if len(hs) > 1:
                    raise ValueError(
                        f"signature-batch worker {w} spans hosts {hs} "
                        f"(granules {list(ms)}); a batched worker must stay "
                        "on one host — adjust the host plan or disable "
                        "batch_signatures")
            self._host_of_w = {w: self.host_plan.host_of(ms[0])
                               for w, ms in enumerate(self._worker_members)}
            self._local_ws = tuple(w for w in range(self.NW)
                                   if self._host_of_w[w] == self.host)
            self._chan_hosts = {c: (self._host_of_w[sw], self._host_of_w[dw])
                                for c, (sw, dw) in self._chan_workers.items()}
            self._links = _fleet.build_links(self.host_plan, self._chan_hosts)
            self._local_links = tuple(lk for lk in self._links
                                      if self.host in (lk.accept, lk.dial))
            self.NB = len(self._local_links)
            self._bridge_ids = {lk.link: self.NW + i
                                for i, lk in enumerate(self._local_links)}
            self._link_of_chan = {}
            for lk in self._links:
                for c, _sh in lk.chans:
                    self._link_of_chan[c] = lk.link
            # host-local stall topology: a cross-host channel's remote end
            # is its LOCAL bridge proxy's monitor id, so the stall graph
            # blames the bridge, never an innocent remote worker
            self._chan_peers = {}
            for c, (sw, dw) in self._chan_workers.items():
                sh, dh = self._chan_hosts[c]
                if self.host not in (sh, dh):
                    continue
                if sh == dh:
                    self._chan_peers[c] = (sw, dw)
                    continue
                b = self._bridge_ids[self._link_of_chan[c]]
                self._chan_peers[c] = (sw if sh == self.host else b,
                                       dw if dh == self.host else b)
        else:
            self._host_of_w = {w: None for w in range(self.NW)}
            self._local_ws = tuple(range(self.NW))
            self._chan_hosts = {}
            self._links = ()
            self._local_links = ()
            self.NB = 0
            self._bridge_ids = {}
            self._link_of_chan = {}
            self._chan_peers = self._chan_workers
        self._base_port = (_fleet.resolve_base_port(base_port)
                           if self.host_plan is not None else 0)
        self._fleet_token = secrets.token_hex(8)

        self._worker_faults, self._link_faults = split_plan(self.fault_plan)
        bad = [a for a in self._worker_faults if a.worker >= self.NW]
        if bad:
            raise ValueError(
                f"fault plan targets worker(s) {[a.worker for a in bad]} "
                f"but the fleet has {self.NW} worker(s)"
            )
        if self._link_faults:
            if self.host_plan is None:
                raise ValueError(
                    "fault plan has link fault(s) "
                    f"{[a.kind for a in self._link_faults]} but the engine "
                    "has no bridged links (pass hosts=)")
            badl = [a for a in self._link_faults
                    if a.worker >= len(self._links)]
            if badl:
                raise ValueError(
                    f"fault plan targets link(s) "
                    f"{[a.worker for a in badl]} but the fleet has "
                    f"{len(self._links)} bridged link(s)")
        self._fired_links: set = set()

        # ---- the prebuilt-simulator cache: one compile per DISTINCT shape
        self.build_stats: dict[str, Any] = {
            "n_workers": self.NW,
            "n_signatures": len(set(self.signatures)),
            "compiled": {},
            "prebuild_seconds": 0.0,
        }
        if prebuild:
            enable_compile_cache(self.cache_dir)
            t0 = time.perf_counter()
            done: set[tuple[str, int]] = set()
            for wspec in self._wspecs:
                nb = len(wspec.specs) if isinstance(wspec, BatchSpec) else 1
                key = (wspec.signature, nb)
                if key in done:
                    continue
                done.add(key)
                sim = (BatchedGranuleSim(wspec) if isinstance(wspec, BatchSpec)
                       else GranuleSim(wspec))
                stats = sim.prebuild()
                name = (wspec.signature if nb == 1
                        else f"{wspec.signature}x{nb}")
                self.build_stats["compiled"][name] = stats
            self.build_stats["prebuild_seconds"] = time.perf_counter() - t0

        # forkserver preloaded with the worker module: respawns fork the
        # already-imported server instead of re-importing jax (recovery
        # MTTR); starts lazily inside the launch() _child_env window
        self._ctx = _worker_mp_context()
        self._procs: dict[int, Any] = {}
        self._conns: dict[int, Any] = {}
        self._bridge_procs: dict[int, Any] = {}
        self._bridge_conns: dict[int, Any] = {}
        self._bridge_labels: dict[int, str] = {}
        self._bridge_logs: dict[int, str] = {}
        self._accept_ports: dict[int, int] = {}
        self._follower_procs: dict[str, Any] = {}
        self._follower_ctls: dict[str, Any] = {}
        self._follower_mid: dict[str, int] = {}
        self._ctl_listener: socket.socket | None = None
        self._rings: dict[str, ShmRing] = {}
        self._hb_shm: shared_memory.SharedMemory | None = None
        self._hb: np.ndarray | None = None
        self._generation = 0
        self._launched = False
        self._closed = False
        self._monitor: ProcessMonitor | None = None
        # packets per rx port the host already received before a recovery
        # rewind: the replay regenerates them, the host-facing pop drops
        # them (exactly-once delivery; owned by the RecoveryController)
        self._ext_discard: dict[str, int] = {}
        # flight recorder (repro.obs): per-worker telemetry ring names,
        # tracing toggle, and the (pid, tid) tracks already named
        self._telem_on = False
        self._telem_names: dict[int, str] = {}
        self._telem_tracked: set[tuple[int, int]] = set()
        self._recovery = RecoveryController(
            self, snapshot_every=snapshot_every, max_restarts=max_restarts,
            backoff_s=backoff_s,
        )
        _live_engines.add(self)

    # ------------------------------------------------------------- lowering
    def _granule_spec(self, g: int) -> GranuleSpec:
        low, graph = self.lowering, self.graph
        groups = []
        for gi, grp in enumerate(graph.groups):
            mo = low.member_of[gi][g]
            params_local = None
            if grp.params is not None:
                params_local = _tree_np(grp.params, mo)
            groups.append(GroupSpec(
                block=grp.block,
                n_members=grp.n_members,
                n_slot=low.n_slot[gi],
                member_of=mo.copy(),
                active=low.act_tables[gi][g].copy(),
                rx_idx=low.rx_tables[gi][g].copy(),
                tx_idx=low.tx_tables[gi][g].copy(),
                params_local=params_local,
            ))
        tiers = []
        for t in range(self.ptree.n_tiers):
            eg, ing = low.tier_channels(t, g)
            tiers.append(TierSpec(
                K=self.K_tiers[t],
                E=self.E_tiers[t],
                egress_chans=tuple(eg),
                egress_lqids=low.tx_local[eg].astype(np.int32)
                if eg else np.zeros((0,), np.int32),
                ingress_chans=tuple(ing),
                ingress_lqids=low.rx_local[ing].astype(np.int32)
                if ing else np.zeros((0,), np.int32),
            ))
        ext = [
            (name, cid, int(max(low.tx_local[cid], low.rx_local[cid])), is_in)
            for name, cid, is_in in low.ext_channels(g)
        ]
        return GranuleSpec(
            granule=g,
            signature=low.granule_signature(g),
            payload_words=self.W,
            capacity=self.capacity,
            dtype=self.dtype.str,
            n_local=self.n_local,
            groups=groups,
            tiers=tiers,
            ext_ports=ext,
            ring_prefix=self._ring_prefix,
            ring_depth=self.ring_depth,
            timeout=self.timeout,
            overlap=self.overlap,
        )

    # ------------------------------------------------------------- lifecycle
    def launch(self) -> "ProcsEngine":
        """Create this host's rings and spawn its workers + bridges (and,
        on the fleet leader, the follower launchers) — idempotent."""
        if self._launched:
            return self
        if self._closed:
            raise RuntimeError("engine was closed")
        itemsize = self.dtype.itemsize
        for t, ts in enumerate(self.tiers):
            for (tt, s, d), chans in sorted(self.lowering.routes.items()):
                if tt != t:
                    continue
                for c in chans:
                    # a multi-host fleet materialises a channel's rings on
                    # every host that touches it: both endpoints of a
                    # cross-host channel get LOCAL rings under this
                    # launcher's own shm namespace, paired over TCP by the
                    # bridge — workers run completely unmodified
                    if (self.host_plan is not None
                            and self.host not in self._chan_hosts[c]):
                        continue
                    # slab + host-port rings are integrity-checked (per-
                    # record seq + crc32); 4-byte credit rings are not —
                    # their payload IS the protocol invariant
                    self._rings[data_ring_name(self._ring_prefix, c)] = (
                        ShmRing.create(
                            data_ring_name(self._ring_prefix, c),
                            self.ring_depth + 1,
                            slab_slot_bytes(self.E_tiers[t], self.W, itemsize),
                            checked=True, label=f"slab:c{c}",
                        )
                    )
                    self._rings[credit_ring_name(self._ring_prefix, c)] = (
                        ShmRing.create(
                            credit_ring_name(self._ring_prefix, c),
                            self.ring_depth + 2, 4,
                        )
                    )
        for name, (cid, is_in) in self.graph.ext_ports().items():
            if (self.host_plan is not None
                    and self._ext_home_host(cid) != self.host):
                continue
            self._rings[ext_ring_name(self._ring_prefix, cid)] = ShmRing.create(
                ext_ring_name(self._ring_prefix, cid),
                self.capacity, self.W * itemsize,
                checked=True, label=f"ext:{name}",
            )
        self._seed_credit_rings()

        hb_name = f"{self._ring_prefix}hb"
        nhb = self.NW + self.NB  # bridge proxies beat alongside the workers
        self._hb_shm = shared_memory.SharedMemory(
            name=hb_name, create=True, size=HB_RECORD_BYTES * nhb
        )
        self._hb_shm.buf[:] = bytes(HB_RECORD_BYTES * nhb)
        self._hb = np.frombuffer(self._hb_shm.buf, np.float64)

        env_save = _child_env()
        try:
            for g in self._local_ws:
                spec = self._wspecs[g]
                parent, child = self._ctx.Pipe()
                log_path = os.path.join(self._log_dir, f"worker{g}.log")
                faults = actions_for(self.fault_plan, g, self._incarnation)
                # flight-recorder ring: always created (a few hundred KB),
                # records only flow once tracing is switched on
                tname = telemetry_ring_name(self._ring_prefix, g)
                self._rings[tname] = ShmRing.create(
                    tname, _telem.TELEM_RING_RECORDS,
                    _telem.TELEM_RECORD_BYTES,
                )
                self._telem_names[g] = tname
                p = self._ctx.Process(
                    target=worker_entry,
                    args=(child, pickle.dumps(spec), g, log_path,
                          self.cache_dir, hb_name,
                          pickle.dumps(faults) if faults else None, tname),
                    daemon=True,
                    name=f"repro-granule-{g}",
                )
                p.start()
                child.close()
                self._procs[g] = p
                self._conns[g] = parent
            for i, lk in enumerate(self._local_links):
                self._spawn_bridge(i, lk, hb_name)
            if self.host_plan is not None and self.is_leader:
                self._spawn_followers()
        finally:
            _restore_env(env_save)

        # accept-side bridges report their bound listener ports first
        for i, lk in enumerate(self._local_links):
            mid = self.NW + i
            kind, payload = self._bridge_recv(mid, max(self.timeout, 120.0))
            if kind != "ready":
                raise self._bridge_dead(mid, f"failed to start: {payload}")
            if payload is not None:
                self._accept_ports[lk.link] = int(payload)

        procs: dict[int, Any] = dict(self._procs)
        procs.update(self._bridge_procs)
        logs = {g: os.path.join(self._log_dir, f"worker{g}.log")
                for g in self._local_ws}
        logs.update(self._bridge_logs)
        labels = dict(self._bridge_labels)
        for h, mid in self._follower_mid.items():
            procs[mid] = self._follower_procs[h]
            logs[mid] = os.path.join(self._log_dir, f"launcher-{h}.log")
            labels[mid] = f"launcher {h}"
        self._monitor = ProcessMonitor(
            procs,
            logs,
            heartbeat=lambda g: float(self._hb[g * HB_RECORD_F64])
            + float(self._hb[g * HB_RECORD_F64 + 1]),
            hang_timeout_s=self.timeout,
            diagnose=self._diagnose_stall,
            labels=labels,
            link_ids=frozenset(self._bridge_ids.values()),
        )
        self._launched = True
        self.launch_stats = {"ready_seconds": {}}
        for g in self._local_ws:
            t0 = time.perf_counter()
            # no heartbeats exist yet (first beat lands on the init
            # command), so the ready-wait polls exitcodes only under a
            # generous absolute deadline — a cold compilation cache must
            # not read as "hung"
            kind, payload = self._recv(g, timeout=max(self.timeout, 300.0),
                                       hang_check=False)
            if kind != "ready":
                raise WorkerDiedError(g, f"failed to start: {payload}",
                                      read_log_tail(self._monitor.log_paths[g]))
            self.launch_stats["ready_seconds"][g] = time.perf_counter() - t0
        if self.host_plan is not None and self.is_leader:
            self._rendezvous_fleet()
        REGISTRY.set("procs.workers", float(self.NW))
        REGISTRY.set("procs.incarnation", float(self._incarnation))
        if self.build_stats.get("prebuild_seconds"):
            REGISTRY.set("procs.prebuild.s",
                         float(self.build_stats["prebuild_seconds"]))
            REGISTRY.set("procs.compile.count",
                         float(len(self.build_stats.get("compiled", {}))))
        if self._telem_on:
            # a respawn (recovery _reopen) keeps tracing on across
            # incarnations; a pre-launch set_tracing lands here too
            self._apply_tracing()
        # a follower returns here with its bridges still un-dialed:
        # ``fleet.follower_entry`` sends the hello (with _accept_ports)
        # and calls _finish_rendezvous once the leader broadcasts the map
        return self

    # ------------------------------------------------ fleet wiring (leader)
    def _ext_home_host(self, cid: int):
        """The host owning an external port's granule (its ring lives
        there; the leader forwards host I/O to it over the control link)."""
        g = int(self._chan_owner[cid])
        return self._host_of_w[self._worker_of[g]]

    def _spawn_bridge(self, i: int, lk, hb_name: str) -> None:
        mid = self.NW + i
        channels = []
        itemsize = self.dtype.itemsize
        for c, src_host in lk.chans:
            t = self._chan_tier[c]
            channels.append(BridgeChannel(
                chan=c,
                side="tx" if src_host == self.host else "rx",
                data_name=data_ring_name(self._ring_prefix, c),
                data_capacity=self.ring_depth + 1,
                data_slot_bytes=slab_slot_bytes(self.E_tiers[t], self.W,
                                                itemsize),
                credit_name=credit_ring_name(self._ring_prefix, c),
                credit_capacity=self.ring_depth + 2,
            ))
        role = "accept" if lk.accept == self.host else "dial"
        spec = BridgeSpec(
            link=lk.link, label=lk.label, host=self.host,
            peer=lk.peer_of(self.host), role=role, token=self._fleet_token,
            port=(self._base_port + lk.link if self._base_port else 0),
            channels=tuple(channels), timeout=self.timeout,
            hb_name=hb_name, hb_index=mid,
        )
        parent, child = self._ctx.Pipe()
        log_path = os.path.join(self._log_dir, f"bridge{lk.link}.log")
        p = self._ctx.Process(
            target=bridge_entry,
            args=(child, pickle.dumps(spec), log_path),
            daemon=True,
            name=f"repro-bridge-{lk.link}",
        )
        p.start()
        child.close()
        self._bridge_procs[mid] = p
        self._bridge_conns[mid] = parent
        self._bridge_labels[mid] = f"bridge {lk.label}"
        self._bridge_logs[mid] = log_path

    def _spawn_followers(self) -> None:
        """Bind the fleet control listener and spawn one follower launcher
        per non-leader host (each a full ProcsEngine restricted to its
        granules — ``fleet.follower_entry``)."""
        plan = self.host_plan
        port = self._base_port + len(self._links) if self._base_port else 0
        lst = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        lst.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        lst.bind(("127.0.0.1", port))
        lst.listen(plan.n_hosts)
        self._ctl_listener = lst
        addr = ("127.0.0.1", lst.getsockname()[1])
        kwargs = dict(
            ring_depth=self.ring_depth, timeout=self.timeout,
            prebuild=False, cache_dir=self.cache_dir,
            batch_signatures=self.batch_signatures, overlap=self.overlap,
            on_fault="raise", fault_plan=self.fault_plan,
            hosts=plan, base_port=self._base_port,
        )
        build = pickle.dumps((self.graph, self.ptree, kwargs))
        followers = tuple(h for h in plan.hosts if h != self.host)
        for j, h in enumerate(followers):
            mid = self.NW + self.NB + j
            boot = _fleet.FollowerBoot(
                host=h, leader_addr=addr, token=self._fleet_token,
                build=build, timeout=self.timeout,
                incarnation=self._incarnation,
            )
            log_path = os.path.join(self._log_dir, f"launcher-{h}.log")
            # NOT daemonic: a follower spawns its own worker/bridge
            # children (daemons cannot).  Leader death still reaps it —
            # its control-link recv raises ConnectionError and it exits.
            p = self._ctx.Process(
                target=_fleet.follower_entry,
                args=(pickle.dumps(boot), log_path),
                daemon=False,
                name=f"repro-launcher-{h}",
            )
            p.start()
            self._follower_procs[h] = p
            self._follower_mid[h] = mid

    def _rendezvous_fleet(self) -> None:
        """Leader rendezvous: collect follower hellos (their accept-side
        bridge ports), broadcast the aggregated link -> address map, dial
        the local bridges, then wait for every member's all-links-up."""
        followers = tuple(h for h in self.host_plan.hosts if h != self.host)

        def _alive() -> None:
            for h, p in self._follower_procs.items():
                if p.exitcode is not None:
                    mid = self._follower_mid[h]
                    tail = read_log_tail(
                        os.path.join(self._log_dir, f"launcher-{h}.log"))
                    self.close()
                    raise WorkerDiedError(
                        mid, f"died with exitcode {p.exitcode} during "
                        "rendezvous", tail, label=f"launcher {h}")

        conns = _fleet.accept_followers(
            self._ctl_listener, followers, self._fleet_token,
            timeout=max(self.timeout, 300.0), on_wait=_alive)
        addr_map = {lk: ("127.0.0.1", prt)
                    for lk, prt in self._accept_ports.items()}
        for h, (ctl, ports) in conns.items():
            self._follower_ctls[h] = ctl
            for lk, prt in ports.items():
                addr_map[int(lk)] = ("127.0.0.1", int(prt))
        for h in followers:
            self._follower_ctls[h].send(("rendezvous", addr_map))
        self._finish_rendezvous(addr_map)
        for h in followers:
            self._ctl_wait(h, timeout=max(self.timeout, 300.0))

    def _finish_rendezvous(self, addr_map: dict) -> None:
        """Dial this host's dial-side bridges and wait for every local
        link to come up (HELLO handshake verified bridge-side)."""
        for i, lk in enumerate(self._local_links):
            mid = self.NW + i
            if lk.accept != self.host:
                if lk.link not in addr_map:
                    raise self._bridge_dead(
                        mid, f"rendezvous map lacks {lk.label}")
                self._bridge_conns[mid].send(("dial",
                                              tuple(addr_map[lk.link])))
        for i, lk in enumerate(self._local_links):
            mid = self.NW + i
            kind, payload = self._bridge_recv(mid, max(self.timeout, 300.0))
            if kind != "up":
                raise self._bridge_dead(
                    mid, f"link never came up: got {kind!r} {payload!r}")

    def _seed_credit_rings(self) -> None:
        """Every boundary channel's sender starts with capacity-1 credit —
        the engines' initial-credit convention, as one pre-seeded record.
        On a bridged fleet only the SENDER's host seeds a cross-host
        channel (the receiver host's credit ring starts empty: the bridge
        drains the receiver's post-fill credits into it and forwards them
        over the wire — seeding both sides would double the credit)."""
        for (t, s, d), chans in self.lowering.routes.items():
            for c in chans:
                name = credit_ring_name(self._ring_prefix, c)
                if name not in self._rings:
                    continue  # channel not materialised on this host
                ring = self._rings[name]
                ring.reset()
                if (self.host_plan is None
                        or self._chan_hosts[c][0] == self.host):
                    ring.push_u32(self.capacity - 1, timeout=1.0)

    def close(self) -> None:
        """Tear down workers, bridges, and follower launchers, and unlink
        every shared-memory segment."""
        if self._closed:
            return
        self._closed = True
        if self._telem_on:
            try:  # last drain before the rings unlink (best-effort)
                self._drain_telemetry_once()
            except Exception:
                pass
        # exits go out to everyone first (followers tear their own fleets
        # down concurrently with our local joins)
        for ctl in list(self._follower_ctls.values()):
            try:
                ctl.send(("exit",))
            except Exception:
                pass
        for conn in list(self._bridge_conns.values()):
            try:
                conn.send(("exit",))
            except Exception:
                pass
        for g, conn in list(self._conns.items()):
            try:
                conn.send(("exit",))
            except Exception:
                pass
        for g, p in list(self._procs.items()):
            try:
                p.join(timeout=2.0)
                if p.is_alive():
                    p.terminate()
                    p.join(timeout=2.0)
            except Exception:
                pass
        for mid, p in list(self._bridge_procs.items()):
            try:
                p.join(timeout=2.0)
                if p.is_alive():
                    p.terminate()
                    p.join(timeout=2.0)
            except Exception:
                pass
        for h, p in list(self._follower_procs.items()):
            try:
                p.join(timeout=5.0)
                if p.is_alive():
                    p.terminate()
                    p.join(timeout=2.0)
            except Exception:
                pass
        for conn in (list(self._conns.values())
                     + list(self._bridge_conns.values())):
            try:
                conn.close()
            except Exception:
                pass
        for ctl in list(self._follower_ctls.values()):
            ctl.close()
        if self._ctl_listener is not None:
            try:
                self._ctl_listener.close()
            except Exception:
                pass
            self._ctl_listener = None
        for ring in self._rings.values():
            ring.close()
        self._rings.clear()
        if self._hb_shm is not None:
            self._hb = None
            try:
                self._hb_shm.close()
                self._hb_shm.unlink()
            except Exception:
                pass
        _live_engines.discard(self)

    def _reopen(self) -> None:
        """Respawn the fleet after a fault (the recovery path): fresh ring
        namespace, fresh worker processes, the SAME lowering — and a warm
        persistent compilation cache, so the respawn skips every compile
        the first launch paid for.  The restart count gates incarnation-
        scoped fault-plan actions (``:r<N>``), so a fired drill fault does
        not re-fire during its own replay."""
        if not self._closed:
            self.close()
        self._incarnation += 1
        self._closed = False
        self._launched = False
        self._procs = {}
        self._conns = {}
        self._bridge_procs = {}
        self._bridge_conns = {}
        self._bridge_labels = {}
        self._bridge_logs = {}
        self._accept_ports = {}
        self._follower_procs = {}
        self._follower_ctls = {}
        self._follower_mid = {}
        self._ctl_listener = None
        self._rings = {}
        self._telem_names = {}
        self._hb_shm = None
        self._hb = None
        self._monitor = None
        self._fired_links = set()
        # fresh incarnation token: a bridge or follower surviving from the
        # previous incarnation can never splice into the new rendezvous
        self._fleet_token = secrets.token_hex(8)
        self._ring_prefix = f"sb{os.getpid() % 100000:x}{secrets.token_hex(3)}"
        # specs embed the ring prefix — rebuild them for the new namespace
        self._specs = [self._granule_spec(g) for g in range(self.G)]
        self._wspecs = [
            self._specs[ms[0]] if len(ms) == 1
            else BatchSpec(members=ms, specs=[self._specs[g] for g in ms])
            for ms in self._worker_members
        ]
        self._np_tables_cache = {}
        _live_engines.add(self)
        self.launch()

    def __del__(self):  # best-effort; atexit covers the normal path
        try:
            self.close()
        except Exception:
            pass

    # --------------------------------------------------------------- comms
    def _check_workers(self, waiting_on=None) -> None:
        # Early follower faults FIRST: a remote worker fault lands as a
        # typed ("fault", ...) control frame, usually accompanied by
        # collateral bridge deaths (the follower tears its fleet down
        # before reporting) — prefer the root-cause frame over blaming
        # the first dead bridge the monitor happens to see.  The frame
        # can still lose the race to the monitor (TCP latency), so
        # consumers must treat LinkDownError/typed fault as equivalent
        # triggers; recovery does (both are RECOVERABLE).
        self._poll_follower_faults()
        if self._monitor is not None:
            try:
                self._monitor.check(waiting_on)
            except (WorkerDiedError, FleetStallError):
                # a dead or deadlocked granule poisons the whole fleet (its
                # peers would hang on its rings) — tear everything down
                # before raising
                self.close()
                raise

    def _poll_follower_faults(self) -> None:
        for h, ctl in list(self._follower_ctls.items()):
            try:
                msg = ctl.peek()
            except ConnectionError:
                mid = self._follower_mid.get(h, self.NW + self.NB)
                tail = read_log_tail(
                    os.path.join(self._log_dir, f"launcher-{h}.log"))
                self.close()
                raise WorkerDiedError(
                    mid, "control link closed unexpectedly", tail,
                    label=f"launcher {h}")
            if msg is not None and msg[0] in ("fault", "err"):
                ctl.take()
                self.close()
                if msg[0] == "fault":
                    raise _fleet.decode_fault(msg[1], h)
                raise RuntimeError(f"follower {h} command failed:\n{msg[1]}")

    def _diagnose_stall(self, waiting_on: tuple[int, ...]):
        """Fleet-wide no-heartbeat diagnosis (monitor callback): decode
        every member's "blocked on ring X" status word into the credit
        wait-for graph.  A cycle is a true deadlock → ``FleetStallError``
        naming it; an acyclic graph blames its root member — a bridge
        proxy root raises ``LinkDownError`` (the link, not an innocent
        worker, is the fault); no usable information returns None (the
        monitor falls back to the plain hung-worker error)."""
        if self._hb is None:
            return None
        blocked = {w: int(self._hb[w * HB_RECORD_F64 + 2])
                   for w in self._local_ws}
        for mid in self._bridge_ids.values():
            blocked[mid] = int(self._hb[mid * HB_RECORD_F64 + 2])
        edges, details = stall_wait_edges(blocked, self._chan_peers)
        cycle = find_stall_cycle(edges)
        if cycle is not None:
            return FleetStallError(cycle, [details[w] for w in cycle])
        roots = set(edges.values()) - set(edges)
        if edges and roots:
            w = min(roots)
            cls = LinkDownError if w >= self.NW else WorkerDiedError
            label = (self._monitor.labels.get(w)
                     if self._monitor is not None else None)
            return cls(
                w,
                f"is the root of a fleet-wide stall: {len(edges)} member(s) "
                f"transitively blocked on it while it made no progress for "
                f"{self.timeout:.0f}s",
                read_log_tail(self._monitor.log_paths.get(w)
                              if self._monitor else None),
                label=label,
            )
        return None

    # ------------------------------------------------------- bridge command
    def _bridge_dead(self, mid: int, reason: str) -> LinkDownError:
        label = self._bridge_labels.get(mid, f"bridge {mid}")
        tail = read_log_tail(self._bridge_logs.get(mid))
        self.close()
        return LinkDownError(mid, reason, tail, label=label)

    def _bridge_recv(self, mid: int, timeout: float):
        conn = self._bridge_conns[mid]
        deadline = time.monotonic() + timeout
        while not conn.poll(0.05):
            p = self._bridge_procs.get(mid)
            if p is not None and p.exitcode is not None:
                raise self._bridge_dead(mid,
                                        f"died with exitcode {p.exitcode}")
            if time.monotonic() > deadline:
                raise self._bridge_dead(mid,
                                        f"no reply within {timeout:.0f}s")
        try:
            return conn.recv()
        except (EOFError, OSError):
            raise self._bridge_dead(mid, "command pipe closed")

    def _bridge_cmd(self, mid: int, cmd: tuple,
                    timeout: float | None = None):
        try:
            self._bridge_conns[mid].send(cmd)
        except (BrokenPipeError, OSError):
            raise self._bridge_dead(
                mid, f"died (command pipe closed on {cmd[0]!r})")
        kind, payload = self._bridge_recv(
            mid, timeout if timeout is not None else max(self.timeout, 60.0))
        if kind != "ok":
            raise self._bridge_dead(
                mid, f"command {cmd[0]!r} failed: {kind} {payload}")
        return payload

    # ------------------------------------------------------ follower command
    def _ctl_wait(self, host: str, timeout: float | None = None,
                  progress: bool = False):
        """Await one control reply from a follower; typed fault replies
        re-raise here with the fleet torn down (recovery catches them one
        frame up, exactly like a local worker fault)."""
        ctl = self._follower_ctls[host]
        deadline = (None if progress
                    else time.monotonic() + (timeout or self.timeout))
        while True:
            try:
                if ctl.poll(0.02):
                    break
            except ConnectionError:
                mid = self._follower_mid.get(host, self.NW + self.NB)
                tail = read_log_tail(
                    os.path.join(self._log_dir, f"launcher-{host}.log"))
                self.close()
                raise WorkerDiedError(mid, "control link closed", tail,
                                      label=f"launcher {host}")
            self._check_workers()
            if deadline is not None and time.monotonic() > deadline:
                mid = self._follower_mid.get(host, self.NW + self.NB)
                tail = read_log_tail(
                    os.path.join(self._log_dir, f"launcher-{host}.log"))
                self.close()
                raise WorkerDiedError(
                    mid, f"no control reply within "
                    f"{timeout or self.timeout:.0f}s", tail,
                    label=f"launcher {host}")
        kind, payload = ctl.take()
        if kind == "fault":
            self.close()
            raise _fleet.decode_fault(payload, host)
        if kind == "err":
            self.close()
            raise RuntimeError(f"follower {host} command failed:\n{payload}")
        return payload

    def _ctl_cmd(self, host: str, op: str, *args,
                 timeout: float | None = None, progress: bool = False):
        try:
            self._follower_ctls[host].send((op, *args))
        except (ConnectionError, OSError):
            mid = self._follower_mid.get(host, self.NW + self.NB)
            tail = read_log_tail(
                os.path.join(self._log_dir, f"launcher-{host}.log"))
            self.close()
            raise WorkerDiedError(
                mid, f"control link closed (sending {op!r})", tail,
                label=f"launcher {host}")
        return self._ctl_wait(host, timeout=timeout, progress=progress)

    @property
    def _follower_hosts(self) -> tuple:
        return tuple(h for h in (self.host_plan.hosts if self.host_plan
                                 else ()) if h != self.host)

    def _send(self, g: int, cmd: tuple) -> None:
        """Send one command; a closed pipe means the worker is gone —
        surface WorkerDiedError (with the log tail) instead of
        BrokenPipeError, and tear the fleet down."""
        if self._closed:
            raise RuntimeError(
                "engine is closed (a worker died or close() was called); "
                "build a fresh engine"
            )
        try:
            self._conns[g].send(cmd)
        except (BrokenPipeError, OSError):
            p = self._procs.get(g)
            if p is not None:
                p.join(timeout=1.0)
            rc = p.exitcode if p is not None else None
            tail = read_log_tail(
                self._monitor.log_paths[g] if self._monitor else None
            )
            self.close()
            raise WorkerDiedError(
                g, f"died with exitcode {rc} (command pipe closed)", tail
            )

    def _recv_raw(self, g: int):
        """recv() one reply from a worker whose pipe is ready — EOF-
        hardened (a worker can die between poll() and recv(); poll returns
        True at EOF), and typed ``("fault", ...)`` replies (worker-side
        ring corruption / ring timeout) are rebuilt into their original
        exception with the fleet torn down — the recovery controller
        catches them one frame up."""
        try:
            kind, payload = self._conns[g].recv()
        except (EOFError, OSError):
            p = self._procs.get(g)
            if p is not None:
                p.join(timeout=1.0)
            rc = p.exitcode if p is not None else None
            tail = read_log_tail(
                self._monitor.log_paths[g] if self._monitor else None
            )
            self.close()
            how = (f"died with exitcode {rc}" if rc
                   else "exited cleanly (exitcode 0) while replies were "
                        "still pending")
            raise WorkerDiedError(g, f"{how} (reply pipe closed)", tail)
        if kind == "fault":
            self.close()
            raise _rebuild_fault(g, payload)
        return kind, payload

    def _recv(self, g: int, timeout: float | None = None,
              progress: bool = False, hang_check: bool = True):
        """Await one reply.  ``progress=True`` (run commands): no absolute
        deadline — the ProcessMonitor's heartbeat watchdog converts a
        worker that stops making *epoch progress* for ``timeout`` seconds
        (dead, hung, or deadlocked on a ring) into a WorkerDiedError.
        ``hang_check=False`` (startup): workers emit no heartbeats before
        their first command, so only exitcodes are polled and the
        absolute deadline governs."""
        conn = self._conns[g]
        deadline = (None if progress
                    else time.monotonic() + (timeout or self.timeout))
        while not conn.poll(0.02):
            self._check_workers(waiting_on=(g,) if hang_check else None)
            if deadline is not None and time.monotonic() > deadline:
                tail = read_log_tail(self._monitor.log_paths[g])
                self.close()
                raise WorkerDiedError(
                    g, f"no reply within {timeout or self.timeout:.0f}s", tail
                )
        return self._recv_raw(g)

    def _command(self, g: int, cmd: tuple, timeout: float | None = None):
        self._send(g, cmd)
        kind, payload = self._recv(g, timeout)
        if kind == "err":
            self.close()
            raise RuntimeError(f"worker {g} command {cmd[0]!r} failed:\n{payload}")
        return payload

    def _broadcast(self, cmd: tuple, progress: bool = False) -> dict:
        """Send to every worker ON THIS HOST, then collect every reply —
        the workers run the command concurrently (free-running; no barrier
        inside).  Returns ``{worker: payload}`` keyed by global worker id
        (the leader merges follower dicts on top for fleet-wide ops).

        Replies are consumed READY-FIRST, not in worker order: a typed
        fault reply (ring corruption, worker-side timeout) surfaces the
        moment it lands even while earlier-numbered workers are wedged by
        that same fault — detection latency is one poll interval, and the
        monitor's fleet-wide stall diagnosis reasons over exactly the
        still-pending set."""
        for g in self._local_ws:
            self._send(g, cmd)
        out: dict = {}
        pending = set(self._local_ws)
        deadline = (None if progress
                    else time.monotonic() + self.timeout)
        while pending:
            ready = [g for g in sorted(pending) if self._conns[g].poll(0)]
            for g in ready:
                kind, payload = self._recv_raw(g)
                if kind == "err":
                    self.close()
                    raise RuntimeError(
                        f"worker {g} command {cmd[0]!r} failed:\n{payload}"
                    )
                out[g] = payload
                pending.discard(g)
            if not pending:
                break
            if ready:
                if deadline is not None:  # any reply rearms the deadline
                    deadline = time.monotonic() + self.timeout
                continue
            self._check_workers(waiting_on=tuple(sorted(pending)))
            if self._telem_on:
                # free-running coverage: keep the telemetry rings drained
                # while the fleet runs, so a bounded ring never forces the
                # workers to drop records on long epochs-per-command runs
                self._drain_telemetry_once()
            if deadline is not None and time.monotonic() > deadline:
                g = min(pending)
                tail = read_log_tail(self._monitor.log_paths[g])
                self.close()
                raise WorkerDiedError(
                    g, f"no reply within {self.timeout:.0f}s", tail
                )
            time.sleep(0.02)
        return out

    # ------------------------------------------------------ engine protocol
    def init(self, key, group_params: dict[int, PyTree] | None = None) -> ProcsState:
        import jax

        self.launch()
        self._generation += 1
        self._recovery.note_reset()
        # On a bridged fleet a RE-init can catch the previous run's final
        # credit still inside a TCP pipe — fence every bridge (drain +
        # pause) before reseeding, or that credit would land after the
        # reseed and double-credit its channel.
        self._fence_fleet()
        for ring in self._rings.values():
            ring.reset()
        self._seed_credit_rings()
        import jax.numpy as jnp

        key = jnp.asarray(key)
        if not jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
            key = jax.random.wrap_key_data(key)  # legacy raw uint32 keys
        key_data = np.asarray(jax.device_get(jax.random.key_data(key)))
        per_granule_params: list[list | None] = [None] * self.G
        if group_params is not None:
            for g in range(self.G):
                sliced: list = [None] * len(self.graph.groups)
                for gi, p in group_params.items():
                    mo = self.lowering.member_of[gi][g]
                    sliced[gi] = _tree_np(p, mo)
                per_granule_params[g] = sliced
        payloads: dict[int, Any] = {}
        for w, members in enumerate(self._worker_members):
            if group_params is None:
                payloads[w] = None
            elif self._is_batch[w]:
                payloads[w] = [per_granule_params[g] for g in members]
            else:
                payloads[w] = per_granule_params[members[0]]
        for h in self._follower_hosts:
            remote = {w: payloads[w] for w in range(self.NW)
                      if self._host_of_w[w] == h}
            self._follower_ctls[h].send(("init", key_data, remote))
        for w in self._local_ws:
            self._send(w, ("init", key_data, payloads[w]))
        for g in self._local_ws:
            kind, payload = self._recv(g)
            if kind == "err":
                self.close()
                raise RuntimeError(f"worker {g} init failed:\n{payload}")
        for h in self._follower_hosts:
            self._ctl_wait(h, timeout=max(self.timeout, 300.0))
        self._resume_fleet()
        return ProcsState(
            cycle=np.zeros((), np.int32), epoch=np.zeros((), np.int32),
            generation=self._generation,
        )

    def _fence_fleet(self) -> None:
        """Quiesce every bridge in the fleet.  Each proxy pauses its pump,
        sends a FENCE marker, and discards inbound frames until its peer's
        marker arrives — after which BOTH TCP directions are provably
        empty.  Fence commands go out to every party (local bridges AND
        follower launchers) before any ack is collected: a proxy's fence
        completes only when its peer fences too, so acking serially would
        deadlock the handshake."""
        if self.host_plan is None or not self.is_leader or not self._launched:
            return
        gen = self._generation % 256
        for mid in sorted(self._bridge_conns):
            self._bridge_conns[mid].send(("fence", gen))
        for h in self._follower_hosts:
            self._follower_ctls[h].send(("fence", gen))
        for mid in sorted(self._bridge_conns):
            kind, payload = self._bridge_recv(mid, max(self.timeout, 60.0))
            if kind != "ok":
                raise self._bridge_dead(
                    mid, f"fence failed: {kind} {payload}")
        for h in self._follower_hosts:
            self._ctl_wait(h, timeout=max(self.timeout, 60.0))

    def _resume_fleet(self) -> None:
        """Un-pause every bridge after the fenced section (ring reseed /
        state restore) completes fleet-wide."""
        if self.host_plan is None or not self.is_leader or not self._launched:
            return
        for mid in sorted(self._bridge_conns):
            self._bridge_conns[mid].send(("resume",))
        for h in self._follower_hosts:
            self._follower_ctls[h].send(("resume",))
        for mid in sorted(self._bridge_conns):
            kind, payload = self._bridge_recv(mid, max(self.timeout, 60.0))
            if kind != "ok":
                raise self._bridge_dead(
                    mid, f"resume failed: {kind} {payload}")
        for h in self._follower_hosts:
            self._ctl_wait(h, timeout=max(self.timeout, 60.0))

    def _require(self, state: ProcsState) -> ProcsState:
        if not isinstance(state, ProcsState):
            raise TypeError(f"expected ProcsState, got {type(state).__name__}")
        if state.generation != self._generation:
            raise RuntimeError(
                "stale ProcsState: the engine was re-initialized (reset) "
                "after this handle was issued"
            )
        return state

    def run_epochs(self, state: ProcsState, n_epochs: int, *,
                   donate: bool = True) -> ProcsState:
        """Free-run ``n_epochs`` on every worker.  Returns when the slowest
        worker reaches the target epoch — the only global synchronization
        is this *observation* at the command boundary; during the run each
        worker is gated solely by its own channels' credits.

        With ``on_fault="recover"`` the run goes through the recovery
        controller: coordinated snapshots on the ``snapshot_every`` epoch
        grid, and any recoverable fleet fault (dead / hung / corrupted /
        deadlocked) is healed by respawn + restore + replay instead of
        raised."""
        state = self._require(state)
        if n_epochs <= 0:
            return state
        if self.on_fault == "recover":
            return self._recovery.run_epochs(state, int(n_epochs))
        return self._run_epochs_raw(state, int(n_epochs))

    def _run_epochs_raw(self, state: ProcsState, n_epochs: int) -> ProcsState:
        if self._link_faults and self.is_leader:
            # Link faults are launcher-executed at epoch boundaries (the
            # bridge pump has no epoch counter): split the run at every
            # armed fault epoch, run up to it, fire, continue.  The fault
            # then surfaces from inside the NEXT segment — a killed link
            # stalls its consumers, the monitor's stall diagnosis roots
            # the wait-for graph at the bridge, and LinkDownError goes to
            # the recovery controller like any worker death.
            done = int(state.epoch)
            end = done + int(n_epochs)
            while done < end:
                pending = sorted(a.epoch for a in self._armed_link_faults()
                                 if done <= a.epoch < end)
                cut = pending[0] if pending else end
                if cut > done:
                    state = self._run_all(state, cut - done)
                    done = cut
                for a in self._armed_link_faults():
                    if a.epoch <= done:
                        self._fire_link_fault(a)
            return state
        return self._run_all(state, int(n_epochs))

    def _run_all(self, state: ProcsState, n_epochs: int) -> ProcsState:
        for h in self._follower_hosts:
            self._follower_ctls[h].send(("run", int(n_epochs)))
        epochs = self._broadcast(("run", int(n_epochs)), progress=True)
        for h in self._follower_hosts:
            epochs.update(self._ctl_wait(h, progress=True))
        if self._telem_on:
            self._drain_telemetry_once()
            self._drain_followers()
        done = next(iter(epochs.values()))
        assert all(e == done for e in epochs.values()), epochs
        return state.replace(
            cycle=np.int32(done * self.cycles_per_epoch),
            epoch=np.int32(done),
        )

    def _armed_link_faults(self):
        return tuple(a for a in self._link_faults
                     if a.restart == self._incarnation
                     and (a.kind, a.worker, a.epoch, a.restart)
                     not in self._fired_links)

    def _fire_link_fault(self, a) -> None:
        """Execute one armed link fault.  ``a.worker`` is a bridge LINK
        index; the fault routes to a host incident to that link — local
        side preferred, else over the control link to the accept host (for
        ``linkcorrupt``, to a side that actually SENDS slabs, since the
        corruption flips a byte in the next outbound slab frame)."""
        self._fired_links.add((a.kind, a.worker, a.epoch, a.restart))
        REGISTRY.inc("faults.injected")
        _trace.instant("fault_injected", cat="fault",
                       args={"kind": a.kind, "link": int(a.worker),
                             "incarnation": int(self._incarnation)})
        lk = self._links[int(a.worker)]
        mid = self._bridge_ids.get(lk.link)
        local = mid is not None and mid in self._bridge_conns
        if a.kind == "linkkill":
            if local:
                self._bridge_procs[mid].kill()
            else:
                self._ctl_cmd(lk.accept, "linkfault", "linkkill",
                              lk.link, None)
        elif a.kind == "linkslow":
            secs = float(a.arg) if a.arg is not None else 0.05
            if local:
                self._bridge_cmd(mid, ("slow", secs))
            else:
                self._ctl_cmd(lk.accept, "linkfault", "linkslow",
                              lk.link, secs)
        elif a.kind == "linkcorrupt":
            tx_hosts = sorted({sh for (_c, sh) in lk.chans})
            if local and self.host in tx_hosts:
                self._bridge_cmd(mid, ("corrupt",))
            else:
                self._ctl_cmd(tx_hosts[0], "linkfault", "linkcorrupt",
                              lk.link, None)

    def run_cycles(self, state: ProcsState, n_cycles: int) -> ProcsState:
        return self.run_epochs(
            state, -(-int(n_cycles) // self.cycles_per_epoch)
        )

    def _done_view(self, view):
        return view

    def _np_tables(self, g: int):
        """This granule's GraphTables as numpy (the launcher-side copy the
        lightweight ``view`` replies are rejoined with — tables are
        constant, so they never ride the per-epoch pickle)."""
        if not hasattr(self, "_np_tables_cache"):
            self._np_tables_cache: dict[int, Any] = {}
        if g not in self._np_tables_cache:
            from ..core.distributed import GraphTables

            spec = self._specs[g]
            self._np_tables_cache[g] = GraphTables(
                rx_idx=tuple(gs.rx_idx for gs in spec.groups),
                tx_idx=tuple(gs.tx_idx for gs in spec.groups),
                active=tuple(gs.active for gs in spec.groups),
                send_idx=tuple(t.egress_lqids for t in spec.tiers),
                send_mask=tuple(np.ones(len(t.egress_chans), bool)
                                for t in spec.tiers),
                recv_idx=tuple(t.ingress_lqids for t in spec.tiers),
                recv_mask=tuple(np.ones(len(t.ingress_chans), bool)
                                for t in spec.tiers),
            )
        return self._np_tables_cache[g]

    def _views(self) -> list:
        """Per-GRANULE state views in granule order (batched workers reply
        with the stacked batch; each member's row is sliced back out).
        Remote granules come back over the control links, numpy-leaved."""
        import jax

        for h in self._follower_hosts:
            self._follower_ctls[h].send(("views",))
        out: list = [None] * self.G
        for w, v in self._broadcast(("view",)).items():
            for r, g in enumerate(self._worker_members[w]):
                vv = (jax.tree.map(lambda x: x[r], v) if self._is_batch[w]
                      else v)
                out[g] = vv.replace(tables=self._np_tables(g))
        for h in self._follower_hosts:
            for g, vv in self._ctl_wait(
                    h, timeout=max(self.timeout, 60.0)).items():
                out[g] = vv
        return out

    def eval_done(self, state: ProcsState, done_fn: Callable) -> bool:
        """Evaluate a granule-local predicate on every worker's state view
        (host-side — predicates are arbitrary closures, which do not cross
        process boundaries)."""
        self._require(state)
        return all(bool(np.asarray(done_fn(self._done_view(v))).all())
                   for v in self._views())

    def run_until(self, state: ProcsState, done_fn: Callable,
                  max_epochs: int, *, cache_key: Any = None,
                  donate: bool = True) -> ProcsState:
        """Run until ``done_fn`` holds on every granule (checked at epoch
        boundaries, the engines' cadence), at most ``max_epochs`` more."""
        state = self._require(state)
        ran = 0
        while ran < max_epochs and not self.eval_done(state, done_fn):
            state = self.run_epochs(state, 1)
            ran += 1
        return state

    def run_until_done(self, state: ProcsState, max_epochs: int, **kw) -> ProcsState:
        return self.run_until(
            state, lambda v: np.asarray(True), max_epochs, **kw
        )

    # ------------------------------------------------------------- probing
    def group_state(self, state: ProcsState, inst) -> PyTree:
        """One instance's (unstacked) live state — mirrors the in-process
        engines' ``group_state``."""
        self._require(state)
        inst_id = inst if isinstance(inst, int) else inst.inst_id
        gi, slot_g = self.graph.locate(inst_id)
        g = int(self.lowering.member_granule[gi][slot_g])
        slot = int(self.lowering.member_slot[gi][slot_g])
        w = self._worker_of[g]
        row = self._row_of[g] if self._is_batch[w] else None
        h = self._host_of_w[w]
        if self.host_plan is not None and h != self.host:
            return self._ctl_cmd(h, "probe", w, gi, slot, row)
        if row is not None:
            return self._command(w, ("probe", gi, slot, row))
        return self._command(w, ("probe", gi, slot))

    def gather_group(self, state: ProcsState, gi: int) -> PyTree:
        """Group ``gi``'s member states in global instantiation order."""
        self._require(state)
        views = self._views()
        low = self.lowering
        import jax

        def pick(*leaves):
            stacked = np.stack(
                [leaves[g][low.member_slot[gi][m]]
                 for m, g in enumerate(low.member_granule[gi])]
            ) if len(low.member_granule[gi]) else np.zeros((0,))
            return stacked

        per_worker = [v.block_states[gi] for v in views]
        return jax.tree.map(pick, *per_worker)

    def worker_stats(self, state: ProcsState | None = None) -> list[dict]:
        """One record per GRANULE (batched workers reply with a list, one
        per batch row — flattened here so the schema is engine-invariant)."""
        if state is not None:
            self._require(state)
        for h in self._follower_hosts:
            self._follower_ctls[h].send(("wstats",))
        merged = dict(self._broadcast(("stats",)))
        for h in self._follower_hosts:
            merged.update(self._ctl_wait(h, timeout=max(self.timeout, 60.0)))
        out: list[dict] = []
        for w in sorted(merged):
            payload = merged[w]
            if isinstance(payload, list):
                out.extend(payload)
            else:
                out.append(payload)
        if self._telem_on:
            self._drain_telemetry_once()
        return out

    # ------------------------------------------------------ flight recorder
    def set_tracing(self, on: bool) -> bool:
        """Toggle per-worker phase telemetry fleet-wide (``repro.obs``).
        Pre-launch calls are remembered and applied by ``launch()``; a
        recovery respawn re-applies the setting to the new incarnation."""
        self._telem_on = bool(on)
        if self._launched:
            self._apply_tracing()
            if not self._telem_on:
                self._drain_telemetry_once(force=True)
        return self._telem_on

    def _apply_tracing(self) -> None:
        on = self._telem_on
        for h in self._follower_hosts:
            try:
                self._ctl_cmd(h, "telemetry", on)
            except WorkerDiedError:
                raise
            except Exception:
                pass
        self._broadcast(("telemetry", on))

    def _is_telem_sink(self) -> bool:
        """Only the leader (or a single-host engine) folds records into
        the process-global recorder/registry — a follower ships its raw
        records to the leader via the ``obs_drain`` control op instead."""
        return self.host_plan is None or self.is_leader

    def _drain_telemetry_once(self, force: bool = False) -> None:
        """Pop every pending local telemetry record into the trace
        recorder and metrics registry (cheap no-op when nothing pends)."""
        if not (self._is_telem_sink() or force):
            return
        for g, name in sorted(self._telem_names.items()):
            ring = self._rings.get(name)
            if ring is None:
                continue
            self._fold_records(g, _telem.drain(ring), pid=0,
                               host=self.host or "local")

    def _fold_records(self, g: int, records, *, pid: int,
                      host: str) -> None:
        if records.shape[0] == 0:
            return
        rec = _trace.recorder()
        key = (int(pid), int(g))
        if key not in self._telem_tracked:
            self._telem_tracked.add(key)
            rec.set_process(pid, f"procs:{host}")
            rec.set_track(pid, int(g), f"worker {g}")
        _telem.records_to_events(records, worker=int(g), pid=pid,
                                 recorder=rec, registry=REGISTRY)

    def _drain_followers(self) -> None:
        """Pull follower hosts' raw telemetry records over the control
        links and fold them in under their host's trace pid."""
        if self.host_plan is None or not self.is_leader:
            return
        for i, h in enumerate(self._follower_hosts):
            try:
                got = self._ctl_cmd(h, "obs_drain")
            except Exception:
                continue
            for g in sorted(got):
                rows = np.asarray(got[g], np.float64).reshape(
                    -1, _telem.TELEM_RECORD_F64)
                self._fold_records(g, rows, pid=1 + i, host=h)

    def flush_telemetry(self) -> None:
        """Drain every host's telemetry rings into the recorder/registry —
        the trace-export path (``Simulation.trace`` exit, ``REPRO_TRACE``
        atexit).  Also folds bridge counters in as one track per proxy."""
        if not self._launched or self._closed:
            return
        self._drain_telemetry_once()
        self._drain_followers()
        rec = _trace.recorder()
        for i, row in enumerate(self.bridge_stats()):
            link = int(row.get("link", i))
            REGISTRY.set(f"bridge.l{link}.{row.get('role', 'x')}.bytes_tx",
                         float(row.get("bytes_tx", 0)))
            REGISTRY.set(f"bridge.l{link}.{row.get('role', 'x')}.bytes_rx",
                         float(row.get("bytes_rx", 0)))
            if rec.enabled:
                tid = self.NW + i
                rec.set_track(0, tid,
                              f"bridge {link} ({row.get('host', '?')})")
                rec.instant("bridge_counters", pid=0, tid=tid, cat="bridge",
                            args={k: v for k, v in row.items()
                                  if isinstance(v, (int, float, str))})

    def port_stats(self, state: ProcsState) -> dict[str, dict]:
        """Per external port: shm-ring occupancy (packets the host can pop /
        has parked) plus the owning worker's device-queue occupancy — the
        uniform ``Simulation.stats()["ports"]`` schema, nested by
        direction so a name serving BOTH directions reports each
        channel's own ring/queue."""
        self._require(state)
        remote_ext: dict[str, tuple] = {}
        for h in self._follower_hosts:
            remote_ext.update(self._ctl_cmd(h, "ext_state"))
        wstats = {s["granule"]: s for s in self.worker_stats()}

        def rec(cid, name, is_in):
            rname = ext_ring_name(self._ring_prefix, cid)
            if rname in self._rings:
                size, free = self._rings[rname].size(), self._rings[rname].free()
            else:  # port homed on a follower host
                size, free = remote_ext[name]
            g = int(self._chan_owner[cid])
            dev = wstats[g]["ports"].get(name, {})
            return {
                "occupancy": size + int(dev.get("occupancy", 0)),
                "credit": (self.capacity - 1 - int(dev.get("occupancy", 0)))
                if is_in else free,
                "ring": size,
                "home": g,
            }

        return {
            "tx": {n: rec(c, n, True) for n, c in self.graph.ext_in.items()},
            "rx": {n: rec(c, n, False) for n, c in self.graph.ext_out.items()},
        }

    # ---------------------- host-side external ports (PySbTx/PySbRx surface)
    def _ext_ring(self, table: dict, name: str) -> ShmRing:
        if name not in table:
            raise KeyError(name)
        return self._rings[ext_ring_name(self._ring_prefix, table[name])]

    def _ext_remote(self, table: dict, name: str):
        """The follower host owning this external port's ring, or None if
        the port is local (the leader forwards host I/O over the control
        link so PySbTx/PySbRx keep working on a sharded fleet)."""
        if name not in table:
            raise KeyError(name)
        if self.host_plan is None:
            return None
        h = self._ext_home_host(table[name])
        return None if h == self.host else h

    def _ext_push_raw(self, name: str, arr) -> int:
        """Push packets into an external ingress ring, local or follower-
        homed — no recovery bookkeeping (the controller's replay path
        uses this directly)."""
        h = self._ext_remote(self.graph.ext_in, name)
        if h is not None:
            return int(self._ctl_cmd(h, "ext_push", name, arr))
        return int(self._ext_ring(self.graph.ext_in, name).push_packets(arr))

    def _ext_pop_raw(self, name: str, max_n: int):
        h = self._ext_remote(self.graph.ext_out, name)
        if h is not None:
            return self._ctl_cmd(h, "ext_pop", name, max_n)
        return self._ext_ring(self.graph.ext_out, name).pop_packets(
            max_n, self.dtype, self.W
        )

    def _ext_pop_host(self, state: ProcsState, name: str, max_n: int):
        """Host-facing pop: raw ring pops are journaled for recovery, and
        packets a replay regenerated that the host already received
        before the rewind are silently dropped (exactly-once delivery)."""
        skip = int(self._ext_discard.get(name, 0))
        got = self._ext_pop_raw(name, int(max_n) + skip)
        if len(got):
            self._recovery.note_ext_pop(state, name, len(got))
        if skip:
            dropped = min(skip, len(got))
            self._ext_discard[name] = skip - dropped
            got = got[dropped:]
        return got

    # recovery hooks: exactly-once host delivery across a rewind
    def _replay_ext_push(self, name: str, batch) -> None:
        arr = np.asarray(batch, self.dtype).reshape(-1, self.W)
        self._ext_push_raw(name, arr)

    def _set_ext_discard(self, discards: dict) -> None:
        self._ext_discard = {k: int(v) for k, v in discards.items() if v}

    def _ext_discard_state(self) -> dict:
        return {k: v for k, v in self._ext_discard.items() if v}

    def host_push(self, state: ProcsState, name: str, payload):
        state = self._require(state)
        arr = np.asarray(payload, self.dtype).reshape(1, self.W)
        n = self._ext_push_raw(name, arr)
        if n:
            self._recovery.note_ext_push(state, name, arr[:n])
        return state, np.bool_(n == 1)

    def host_pop(self, state: ProcsState, name: str):
        state = self._require(state)
        got = self._ext_pop_host(state, name, 1)
        if len(got):
            return state, got[0], np.bool_(True)
        return state, np.zeros((self.W,), self.dtype), np.bool_(False)

    def host_push_many(self, state: ProcsState, name: str, payloads):
        state = self._require(state)
        arr = np.asarray(payloads, self.dtype).reshape(-1, self.W)
        arr = arr[: self.capacity - 1]
        n = self._ext_push_raw(name, arr)
        if n:
            self._recovery.note_ext_push(state, name, arr[:n])
        return state, np.int32(n)

    def host_pop_many(self, state: ProcsState, name: str, max_n: int):
        state = self._require(state)
        got = self._ext_pop_host(state, name, max_n)
        out = np.zeros((max_n, self.W), self.dtype)
        out[: len(got)] = got
        return state, out, np.int32(len(got))

    # ------------------------------------------------- checkpoint (gather)
    def gather_state(self, state: ProcsState) -> PyTree:
        """Full-fleet state as one pytree: every worker's granule state,
        every boundary channel's in-flight credit record, every external
        ring's resident packets (fixed-size buffers + counts, so the
        checkpoint template is shape-stable)."""
        state = self._require(state)
        for h in self._follower_hosts:
            self._follower_ctls[h].send(("gather",))
        tree = self._gather_local()
        for h in self._follower_hosts:
            remote = self._ctl_wait(h, timeout=max(self.timeout, 60.0))
            tree["workers"].update(remote["workers"])
            tree["credits"].update(remote["credits"])
            tree["ext"].update(remote["ext"])
        if self.host_plan is not None:
            missing = [g for g in range(self.G)
                       if f"g{g}" not in tree["workers"]]
            assert not missing, f"gather missing granules {missing}"
        return {
            "cycle": np.asarray(state.cycle),
            "epoch": np.asarray(state.epoch),
            "workers": tree["workers"],
            "credits": tree["credits"],
            "ext": tree["ext"],
        }

    def _gather_local(self) -> dict:
        """This host's contribution to the fleet checkpoint: its workers'
        granule states, the resting credit of every channel whose SENDER
        lives here (the credit's home at quiesce), and its external
        rings."""
        import jax

        gathered = self._broadcast(("gather",))
        workers: dict[str, Any] = {}
        for w, tree_w in gathered.items():
            for r, g in enumerate(self._worker_members[w]):
                workers[f"g{g}"] = (jax.tree.map(lambda x: x[r], tree_w)
                                    if self._is_batch[w] else tree_w)
        credits = {}
        for (t, s, d), chans in sorted(self.lowering.routes.items()):
            for c in chans:
                name = credit_ring_name(self._ring_prefix, c)
                if name not in self._rings:
                    continue  # channel not materialised on this host
                if (self.host_plan is not None
                        and self._chan_hosts[c][0] != self.host):
                    continue  # rx side of a cross-host channel: the tx
                    #           host accounts its resting credit
                ring = self._rings[name]
                if (self.host_plan is not None
                        and self._chan_hosts[c][0] != self._chan_hosts[c][1]):
                    self._await_credit(c, ring)
                snap = ring.snapshot()
                # at a command boundary exactly one credit is in flight
                assert len(snap) == 1, (c, len(snap))
                credits[f"c{c}"] = snap[0].copy()
        return {"workers": workers, "credits": credits,
                "ext": self._gather_ext_local()}

    def _await_credit(self, c: int, ring: ShmRing) -> None:
        """A cross-host channel's resting credit can still be in TCP
        flight at the command boundary (the receiver pushed it; the bridge
        pair is forwarding it home).  Poll the tx-side credit ring until
        it lands — a link that never delivers it raises RingTimeout, a
        RECOVERABLE fault (the recovery controller restores from the last
        coordinated snapshot)."""
        deadline = time.monotonic() + max(self.timeout, 10.0)
        while ring.size() != 1:
            self._check_workers()
            if time.monotonic() > deadline:
                self.close()
                raise RingTimeout(
                    f"cross-host credit for channel {c} never arrived "
                    f"within {max(self.timeout, 10.0):.0f}s — link down "
                    "or bridge wedged")
            time.sleep(0.002)

    def _gather_ext(self) -> dict:
        """FLEET-WIDE external-ring snapshot — the recovery controller's
        ext-dirty refresh hook.  Follower-homed ports must ride along
        (over the control links), or a refreshed snapshot would be
        missing their entries and a later cross-host scatter would have
        nothing to restore into the follower's rings."""
        ext = {}
        if self.host_plan is not None and self.is_leader:
            for h in self._follower_hosts:
                ext.update(self._ctl_cmd(h, "ext_gather"))
        ext.update(self._gather_ext_local())
        return ext

    def _gather_ext_local(self) -> dict:
        """THIS host's external rings' resident packets + seq counters.
        Checked rings snapshot WITH their headers, and the (producer,
        consumer) sequence counters ride along so a restore into a FRESH
        segment resumes the exact seq timeline — the bit-identical-
        recovery requirement."""
        ext = {}
        for name, (cid, is_in) in self.graph.ext_ports().items():
            rname = ext_ring_name(self._ring_prefix, cid)
            if rname not in self._rings:
                continue  # port homed on another host
            ring = self._rings[rname]
            snap = ring.snapshot()
            buf = np.zeros((self.capacity - 1, ring.stride), np.uint8)
            buf[: len(snap)] = snap
            ext[name] = {"buf": buf, "count": np.int32(len(snap)),
                         "seq": np.asarray(ring.seq_state(), np.int64)}
        return ext

    def scatter_state(self, state: ProcsState, tree: PyTree) -> ProcsState:
        """Restore a ``gather_state`` tree into the running fleet.  On a
        bridged fleet the restore runs inside a fence: restoring rings
        while a bridge pumps — or with a stale credit still in TCP
        flight — would corrupt the credit protocol."""
        import jax

        state = self._require(state)
        self._recovery.note_scatter()
        tree = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), tree)
        self._fence_fleet()
        for h in self._follower_hosts:
            self._follower_ctls[h].send(("scatter", tree))
        self._scatter_local(tree)
        for h in self._follower_hosts:
            self._ctl_wait(h, timeout=max(self.timeout, 300.0))
        self._resume_fleet()
        epoch = int(np.asarray(tree["epoch"]).ravel()[0])
        return state.replace(
            cycle=np.int32(np.asarray(tree["cycle"]).ravel()[0]),
            epoch=np.int32(epoch),
        )

    def _scatter_local(self, tree: PyTree) -> None:
        """This host's share of a fleet-wide restore: credits land on each
        channel's tx host (the rx side of a cross-host channel resets to
        empty — its resting credit lives at the sender), every local data
        ring resets, local external rings restore, local workers scatter."""
        import jax

        for (t, s, d), chans in sorted(self.lowering.routes.items()):
            for c in chans:
                name = credit_ring_name(self._ring_prefix, c)
                if name not in self._rings:
                    continue
                ring = self._rings[name]
                if (self.host_plan is None
                        or self._chan_hosts[c][0] == self.host):
                    ring.restore(np.asarray(tree["credits"][f"c{c}"])[None])
                else:
                    ring.reset()
        for (t, s, d), chans in sorted(self.lowering.routes.items()):
            for c in chans:
                name = data_ring_name(self._ring_prefix, c)
                if name in self._rings:
                    self._rings[name].reset()
        for name, (cid, is_in) in self.graph.ext_ports().items():
            rname = ext_ring_name(self._ring_prefix, cid)
            if rname not in self._rings:
                continue
            ring = self._rings[rname]
            rec = tree["ext"][name]
            seq = (tuple(int(x) for x in np.asarray(rec["seq"]).ravel())
                   if "seq" in rec else None)
            ring.restore(np.asarray(rec["buf"])[: int(rec["count"])], seq=seq)
        epoch = int(np.asarray(tree["epoch"]).ravel()[0])
        for w in self._local_ws:
            members = self._worker_members[w]
            if self._is_batch[w]:
                rows = [tree["workers"][f"g{g}"] for g in members]
                payload = jax.tree.map(lambda *xs: np.stack(xs), *rows)
            else:
                payload = tree["workers"][f"g{members[0]}"]
            self._send(w, ("scatter", payload, epoch))
        for g in self._local_ws:
            kind, payload = self._recv(g)
            if kind == "err":
                self.close()
                raise RuntimeError(f"worker {g} scatter failed:\n{payload}")

    # ------------------------------------------------------- bridge surface
    def bridge_stats(self) -> list[dict]:
        """One counter row per live bridge proxy, fleet-wide (leader) —
        ``Simulation.stats()["bridges"]``.  Empty on a single-host engine.
        Dead proxies and unreachable followers are skipped, not raised:
        stats must stay callable mid-fault."""
        if self.host_plan is None or not self._launched:
            return []
        rows = self._local_bridge_stats()
        if self.is_leader:
            for h in self._follower_hosts:
                ctl = self._follower_ctls.get(h)
                p = self._follower_procs.get(h)
                if ctl is None or (p is not None and p.exitcode is not None):
                    continue
                try:
                    ctl.send(("bridge_stats",))
                    deadline = time.monotonic() + 10.0
                    msg = None
                    while msg is None:
                        ctl.poll(0.02)
                        msg = ctl.peek()
                        if msg is None and time.monotonic() > deadline:
                            break
                    # a pending typed fault stays queued for _check_workers
                    if msg is not None and msg[0] == "ok":
                        ctl.take()
                        rows.extend(msg[1])
                except Exception:
                    continue
        rows.sort(key=lambda r: (r["link"], r["host"]))
        return rows

    def _local_bridge_stats(self) -> list[dict]:
        rows = []
        for mid in sorted(self._bridge_conns):
            p = self._bridge_procs.get(mid)
            if p is None or p.exitcode is not None:
                continue
            conn = self._bridge_conns[mid]
            try:
                conn.send(("stats",))
                deadline = time.monotonic() + 5.0
                while not conn.poll(0.02):
                    if (time.monotonic() > deadline
                            or p.exitcode is not None):
                        raise TimeoutError
                kind, payload = conn.recv()
            except (TimeoutError, EOFError, OSError, BrokenPipeError):
                continue
            if kind == "ok" and payload is not None:
                rows.append(payload)
        return rows

    # ------------------------------------------- follower control dispatch
    def _fleet_dispatch(self, op: str, args: tuple):
        """Serve one leader control command on a FOLLOWER launcher (called
        from ``fleet.follower_entry``).  Faults raised here are encoded and
        shipped back typed — the leader re-raises them as if local."""
        if op == "run":
            (n,) = args
            return self._broadcast(("run", int(n)), progress=True)
        if op == "init":
            key_data, payloads = args
            self._generation += 1
            self._recovery.note_reset()
            for ring in self._rings.values():
                ring.reset()
            self._seed_credit_rings()
            for w in self._local_ws:
                self._send(w, ("init", key_data, payloads.get(w)))
            for g in self._local_ws:
                kind, payload = self._recv(g, timeout=max(self.timeout, 300.0))
                if kind == "err":
                    raise RuntimeError(f"worker {g} init failed:\n{payload}")
            return True
        if op == "fence":
            (gen,) = args
            for mid in sorted(self._bridge_conns):
                self._bridge_conns[mid].send(("fence", int(gen)))
            for mid in sorted(self._bridge_conns):
                kind, payload = self._bridge_recv(mid,
                                                  max(self.timeout, 60.0))
                if kind != "ok":
                    raise self._bridge_dead(
                        mid, f"fence failed: {kind} {payload}")
            return True
        if op == "resume":
            for mid in sorted(self._bridge_conns):
                self._bridge_conns[mid].send(("resume",))
            for mid in sorted(self._bridge_conns):
                kind, payload = self._bridge_recv(mid,
                                                  max(self.timeout, 60.0))
                if kind != "ok":
                    raise self._bridge_dead(
                        mid, f"resume failed: {kind} {payload}")
            return True
        if op == "gather":
            return self._gather_local()
        if op == "scatter":
            (tree,) = args
            self._scatter_local(tree)
            return True
        if op == "views":
            import jax

            out: dict[int, Any] = {}
            for w, v in self._broadcast(("view",)).items():
                for r, g in enumerate(self._worker_members[w]):
                    vv = (jax.tree.map(lambda x: x[r], v)
                          if self._is_batch[w] else v)
                    vv = vv.replace(tables=self._np_tables(g))
                    out[g] = jax.tree.map(lambda x: np.asarray(x), vv)
            return out
        if op == "probe":
            import jax

            w, gi, slot, row = args
            if row is not None:
                got = self._command(w, ("probe", gi, slot, row))
            else:
                got = self._command(w, ("probe", gi, slot))
            return jax.tree.map(lambda x: np.asarray(x), got)
        if op == "wstats":
            return dict(self._broadcast(("stats",)))
        if op == "ext_state":
            out = {}
            for name, (cid, is_in) in self.graph.ext_ports().items():
                rname = ext_ring_name(self._ring_prefix, cid)
                if rname in self._rings:
                    r = self._rings[rname]
                    out[name] = (r.size(), r.free())
            return out
        if op == "ext_gather":
            return self._gather_ext_local()
        if op == "ext_push":
            name, arr = args
            return int(self._ext_ring(self.graph.ext_in, name)
                       .push_packets(np.asarray(arr)))
        if op == "ext_pop":
            name, n = args
            return self._ext_ring(self.graph.ext_out, name).pop_packets(
                int(n), self.dtype, self.W)
        if op == "bridge_stats":
            return self._local_bridge_stats()
        if op == "telemetry":
            (on,) = args
            self._telem_on = bool(on)
            self._broadcast(("telemetry", bool(on)))
            return True
        if op == "obs_drain":
            # ship raw per-worker records to the leader (the only sink)
            out = {}
            for g, name in sorted(self._telem_names.items()):
                ring = self._rings.get(name)
                if ring is None:
                    continue
                rows = _telem.drain(ring)
                if rows.shape[0]:
                    out[g] = rows
            return out
        if op == "linkfault":
            kind, link, arg = args
            mid = self._bridge_ids[int(link)]
            if kind == "linkkill":
                self._bridge_procs[mid].kill()
            elif kind == "linkslow":
                self._bridge_cmd(mid, ("slow", float(arg)))
            elif kind == "linkcorrupt":
                self._bridge_cmd(mid, ("corrupt",))
            else:
                raise RuntimeError(f"unknown link fault {kind!r}")
            return True
        raise RuntimeError(f"unknown fleet control op {op!r}")

    # -------------------------------------------------------- fault surface
    def fault_stats(self) -> dict:
        """Recovery/fault counters — ``Simulation.stats()["faults"]``."""
        return self._recovery.stats()

    def _handle_at(self, epoch: int) -> ProcsState:
        """A fresh state handle pinned at ``epoch`` — the recovery restore
        path's replacement for the handle that rode into the fault."""
        return ProcsState(
            cycle=np.int32(int(epoch) * self.cycles_per_epoch),
            epoch=np.int32(int(epoch)),
            generation=self._generation,
        )


def _rebuild_fault(worker: int, payload: dict) -> Exception:
    """Rebuild a worker's typed ``("fault", ...)`` reply into its original
    exception (ring corruption / ring timeout) so the recovery controller
    sees the same type it would from a launcher-side detection."""
    if payload.get("error") == "RingCorruptionError":
        return RingCorruptionError(**payload["args"])
    return RingTimeout(
        f"worker {worker}: {payload.get('message', 'ring timeout')}"
    )


def _tree_np(tree: PyTree, idx: np.ndarray) -> PyTree:
    import jax

    return jax.tree.map(lambda x: np.asarray(x)[np.asarray(idx)], tree)


def _child_env() -> dict[str, str | None]:
    """Point spawned workers at a single CPU device: strip the parent's
    fake-device XLA flag and force the CPU platform.  Returns the saved
    parent values for ``_restore_env``."""
    saved = {k: os.environ.get(k) for k in ("XLA_FLAGS", "JAX_PLATFORMS")}
    flags = [
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    if flags:
        os.environ["XLA_FLAGS"] = " ".join(flags)
    else:
        os.environ.pop("XLA_FLAGS", None)
    os.environ["JAX_PLATFORMS"] = "cpu"
    return saved


def _restore_env(saved: dict[str, str | None]) -> None:
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
