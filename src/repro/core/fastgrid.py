"""Register-channel grid engine — the hand-specialized systolic preset of
the fused-backend family (§Perf).

The general fused fast path is ``core.fused.FusedEngine``: it lowers ANY
partitioned channel graph to depth-1 register channels + a fused K-cycle
epoch, and subsumes this engine.  What this preset keeps
is the hand-written Pallas kernel that fuses the MAC *block semantics*
(not just the channel plumbing) for TPU.  Use ``engine="fused"`` for
arbitrary topologies; ``engine="register"`` remains the systolic-grid
Pallas-kernel reference.

The queue engine (``distributed.GridEngine``) is paper-faithful: 62-slot
SPSC queues updated cycle by cycle with ~10 XLA ops per cycle.  This engine
is the beyond-paper optimized backend for the manycore app:

  * intra-tile channels are **depth-1 elastic registers** (a valid/value
    pair per hop) — a legal latency-insensitive implementation, so the final
    result is unchanged (property-tested vs the queue engine);
  * the whole K-cycle epoch of a granule runs inside ONE Pallas kernel
    (``kernels/systolic_step``) with the tile state resident in VMEM —
    HBM sees the state once per epoch instead of ~10 times per cycle;
  * tile boundaries remain epoch slabs exchanged with ``ppermute`` and
    credit flow control — identical distribution semantics to the paper
    engine, so granule counts/partitioning stay invariant.

This is the paper's own Table-I move (same behaviour, faster backend behind
the same interface) applied to its own flagship experiment.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels import ops as kops
from ..obs.registry import REGISTRY
from .compat import shard_map
from .graph import ChannelGraph
from .struct import pytree_dataclass

PyTree = Any


@pytree_dataclass
class RegGridState:
    """All leaves carry leading (Dr, Dc) device dims."""

    cell: dict          # b, a_reg, a_v, p_reg, p_v, a_idx, y_idx, a_buf, y_buf, flags
    west_slab: jax.Array   # (Dr, Dc, Tr, 2K) ingress (east-bound data)
    west_cnt: jax.Array    # (Dr, Dc, Tr)
    north_slab: jax.Array  # (Dr, Dc, Tc, 2K)
    north_cnt: jax.Array   # (Dr, Dc, Tc)
    credit_e: jax.Array    # (Dr, Dc, Tr) packets we may send east next epoch
    credit_s: jax.Array    # (Dr, Dc, Tc)
    cycle: jax.Array       # (Dr, Dc)
    epoch: jax.Array       # (Dr, Dc)


def _sq(tree):
    return jax.tree.map(lambda x: x.reshape(x.shape[2:]), tree)


def _unsq(tree):
    return jax.tree.map(lambda x: x.reshape((1, 1) + x.shape), tree)


def _compact(slab, cnt, consumed, arrived, arrived_cnt):
    """Drop ``consumed`` leading packets, append ``arrived``; per row.

    slab: (R, W); arrived: (R, A). Returns (slab', cnt').
    """
    R, W = slab.shape
    A = arrived.shape[1]
    idx = jnp.arange(W)[None, :] + consumed[:, None]  # shift left
    shifted = jnp.take_along_axis(
        jnp.concatenate([slab, jnp.zeros_like(slab)], axis=1), idx, axis=1
    )
    left = cnt - consumed  # leftovers
    # insert arrived at position `left` per row
    pos = jnp.arange(W)[None, :] - left[:, None]  # index into arrived
    can = (pos >= 0) & (pos < A) & (pos < arrived_cnt[:, None])
    from_arrived = jnp.take_along_axis(
        arrived, jnp.clip(pos, 0, A - 1), axis=1
    )
    new_slab = jnp.where(can, from_arrived, shifted)
    return new_slab, left + jnp.minimum(arrived_cnt, W - left)


class RegisterGridEngine:
    """Drop-in alternative to GridEngine for the systolic app."""

    engine_kind = "register"

    def __init__(self, R: int, C: int, mesh: Mesh, K: int, m_stream: int,
                 axis_r: str = "gr", axis_c: str = "gc"):
        self.R, self.C = R, C
        self.mesh = mesh
        self.axis_r, self.axis_c = axis_r, axis_c
        self.Dr = mesh.shape[axis_r]
        self.Dc = mesh.shape[axis_c]
        if R % self.Dr or C % self.Dc:
            raise ValueError("grid not divisible by device grid")
        self.Tr, self.Tc = R // self.Dr, C // self.Dc
        self.K = K
        self.W = 2 * K  # ingress slab capacity (credit-bounded)
        self.M = m_stream
        self._spec = P(axis_r, axis_c)
        self._cache: dict = {}
        self._graph_ab: tuple[np.ndarray, np.ndarray] | None = None

    # ------------------------------------------------------- IR entry point
    @classmethod
    def from_graph(
        cls,
        graph: ChannelGraph,
        mesh: Mesh,
        K: int,
        axis_r: str = "gr",
        axis_c: str = "gc",
    ) -> "RegisterGridEngine":
        """Build the register engine from the channel-graph IR.

        This backend is specialized: the kernel fuses the systolic-matmul
        cell semantics, so the IR must describe exactly the §IV-B topology —
        one group of ``SystolicCell`` instances wired as a row-major R×C
        east/south grid with stacked ``SystolicParams``.  The shape is
        *verified* against a freshly generated reference grid IR; anything
        else raises, steering the caller to engine="graph".
        """
        from ..hw.systolic import SystolicCell, SystolicParams

        if len(graph.groups) != 1 or not isinstance(graph.groups[0].block, SystolicCell):
            raise ValueError(
                "engine='register' requires a single-group SystolicCell "
                f"network, got {graph.summary()}"
            )
        grp = graph.groups[0]
        if not isinstance(grp.params, SystolicParams):
            raise ValueError("engine='register' requires stacked SystolicParams")
        is_north = np.asarray(grp.params.is_north).astype(bool)
        C = int(is_north.sum())
        if C == 0 or grp.n_members % C:
            raise ValueError("IR is not a rectangular systolic grid")
        R = grp.n_members // C
        ref = ChannelGraph.grid(
            grp.block, R, C,
            payload_words=graph.payload_words, dtype=graph.dtype,
            capacity=graph.capacity,
        )
        # Compare channel structure up to channel *renumbering*: every
        # channel is identified by its (src instance, dst instance) pair,
        # which is unique in a grid.
        def endpoint_map(g):
            return {
                (int(s), int(d)): cid
                for cid, (s, d) in enumerate(zip(g.chan_src, g.chan_dst))
                if cid >= 2
            }

        ref_map, act_map = endpoint_map(ref), endpoint_map(graph)
        same = (
            not graph.ext_in and not graph.ext_out
            and graph.n_channels == ref.n_channels
            and set(ref_map) == set(act_map)
        )
        if same:
            renum = np.arange(ref.n_channels, dtype=np.int64)
            for pair, rc in ref_map.items():
                renum[rc] = act_map[pair]
            same = np.array_equal(renum[ref.rx_idx[0]], graph.rx_idx[0]) and (
                np.array_equal(renum[ref.tx_idx[0]], graph.tx_idx[0])
            )
        if not same:
            raise ValueError(
                "IR channel table is not the row-major east/south grid the "
                "register backend is specialized for; use engine='graph'"
            )
        a_buf = np.asarray(grp.params.a_buf)  # (R*C, M)
        M = a_buf.shape[-1]
        A = a_buf.reshape(R, C, M)[:, 0, :].T  # west cells stream A[:, r]
        B = np.asarray(grp.params.b).reshape(R, C)
        eng = cls(R, C, mesh, K=K, m_stream=M, axis_r=axis_r, axis_c=axis_c)
        eng._graph_ab = (A, B)
        return eng

    # ------------------------------------------------------------------ init
    def init(self, A: np.ndarray | None = None, B: np.ndarray | None = None) -> RegGridState:
        if A is None and B is None and self._graph_ab is not None:
            A, B = self._graph_ab  # engine came from the IR; operands stacked there
        R, C, M = self.R, self.C, self.M
        Dr, Dc, Tr, Tc = self.Dr, self.Dc, self.Tr, self.Tc
        rr, cc = np.meshgrid(np.arange(R), np.arange(C), indexing="ij")
        a_buf = np.zeros((R, C, M), np.float32)
        a_buf[:, 0, :] = np.asarray(A, np.float32).T

        def tile(x):
            x = jnp.asarray(x)
            return x.reshape((Dr, Tr, Dc, Tc) + x.shape[2:]).transpose(
                (0, 2, 1, 3) + tuple(range(4, x.ndim + 2))
            )

        z = jnp.zeros
        cell = dict(
            b=tile(jnp.asarray(B, jnp.float32)),
            a_reg=z((Dr, Dc, Tr, Tc)), a_v=z((Dr, Dc, Tr, Tc), bool),
            p_reg=z((Dr, Dc, Tr, Tc)), p_v=z((Dr, Dc, Tr, Tc), bool),
            a_idx=z((Dr, Dc, Tr, Tc), jnp.int32),
            y_idx=z((Dr, Dc, Tr, Tc), jnp.int32),
            a_buf=tile(a_buf), y_buf=z((Dr, Dc, Tr, Tc, M)),
            is_west=tile(jnp.asarray(cc == 0)),
            is_north=tile(jnp.asarray(rr == 0)),
            is_south=tile(jnp.asarray(rr == R - 1)),
            is_east=tile(jnp.asarray(cc == C - 1)),
        )
        return RegGridState(
            cell=cell,
            west_slab=z((Dr, Dc, Tr, self.W)), west_cnt=z((Dr, Dc, Tr), jnp.int32),
            north_slab=z((Dr, Dc, Tc, self.W)), north_cnt=z((Dr, Dc, Tc), jnp.int32),
            credit_e=jnp.full((Dr, Dc, Tr), self.W, jnp.int32),
            credit_s=jnp.full((Dr, Dc, Tc), self.W, jnp.int32),
            cycle=z((Dr, Dc), jnp.int32), epoch=z((Dr, Dc), jnp.int32),
        )

    def place(self, state: RegGridState) -> RegGridState:
        sh = NamedSharding(self.mesh, self._spec)
        return jax.tree.map(lambda x: jax.device_put(x, sh), state)

    @property
    def cycles_per_epoch(self) -> int:
        return self.K

    # ----------------------------------------------------------------- epoch
    def _epoch(self, st: RegGridState) -> RegGridState:
        Tr, Tc, K = self.Tr, self.Tc, self.K
        kstate = dict(
            st.cell,
            west_slab=st.west_slab, west_cnt=st.west_cnt,
            north_slab=st.north_slab, north_cnt=st.north_cnt,
            widx=jnp.zeros((Tr,), jnp.int32), nidx=jnp.zeros((Tc,), jnp.int32),
            east_slab=jnp.zeros((Tr, K)), east_cnt=jnp.zeros((Tr,), jnp.int32),
            south_slab=jnp.zeros((Tc, K)), south_cnt=jnp.zeros((Tc,), jnp.int32),
            east_limit=jnp.minimum(st.credit_e, K),
            south_limit=jnp.minimum(st.credit_s, K),
        )
        out = kops.systolic_step(kstate, K)

        Dr, Dc = self.Dr, self.Dc
        perm_e = [(j, j + 1) for j in range(Dc - 1)]
        perm_w = [(j + 1, j) for j in range(Dc - 1)]
        perm_s = [(i, i + 1) for i in range(Dr - 1)]
        perm_n = [(i + 1, i) for i in range(Dr - 1)]

        def pshift(x, axis_name, perm):
            if not perm:
                return jnp.zeros_like(x)
            return jax.lax.ppermute(x, axis_name, perm)

        # emission was credit-bounded inside the kernel; send everything.
        e_cnt = out["east_cnt"]
        s_cnt = out["south_cnt"]
        slab_e_in = pshift(out["east_slab"], self.axis_c, perm_e)
        cnt_e_in = pshift(e_cnt, self.axis_c, perm_e)
        slab_s_in = pshift(out["south_slab"], self.axis_r, perm_s)
        cnt_s_in = pshift(s_cnt, self.axis_r, perm_s)

        west_slab, west_cnt = _compact(
            out["west_slab"], out["west_cnt"], out["widx"], slab_e_in, cnt_e_in
        )
        north_slab, north_cnt = _compact(
            out["north_slab"], out["north_cnt"], out["nidx"], slab_s_in, cnt_s_in
        )
        credit_e = pshift(self.W - west_cnt, self.axis_c, perm_w)
        credit_s = pshift(self.W - north_cnt, self.axis_r, perm_n)

        cell = {k: out[k] for k in st.cell}
        return st.replace(
            cell=cell,
            west_slab=west_slab, west_cnt=west_cnt,
            north_slab=north_slab, north_cnt=north_cnt,
            credit_e=credit_e, credit_s=credit_s,
            cycle=st.cycle + K, epoch=st.epoch + 1,
        )

    # ------------------------------------------------------------------- run
    def epoch_fn(self):
        def run(state):
            return _unsq(self._epoch(_sq(state)))

        return shard_map(run, mesh=self.mesh, in_specs=self._spec,
                         out_specs=self._spec)

    def run_epochs(
        self, state: RegGridState, n_epochs: int, *, donate: bool = True
    ) -> RegGridState:
        """Advance ``n_epochs`` epochs (K cycles each) — the uniform engine
        entry point the ``Simulation`` session drives.

        ``donate=True`` (default) donates the state into the compiled loop
        (no per-call state copy); the input must not be reused after.
        """
        key = ("epochs", n_epochs, donate)
        if key not in self._cache:
            REGISTRY.inc("register.compile.count")

            def run(state):
                local = _sq(state)
                out = jax.lax.scan(
                    lambda s, _: (self._epoch(s), None), local, None,
                    length=n_epochs,
                )[0]
                return _unsq(out)

            self._cache[key] = jax.jit(
                shard_map(run, mesh=self.mesh, in_specs=self._spec,
                          out_specs=self._spec),
                donate_argnums=(0,) if donate else (),
            )
        if donate:
            from .distributed import _dealias_for_donation

            state = _dealias_for_donation(state)
        REGISTRY.inc("register.dispatch.count")
        REGISTRY.inc("register.epochs", float(n_epochs))
        return self._cache[key](state)

    def run_until(
        self,
        state: RegGridState,
        done_fn,
        max_epochs: int,
        *,
        cache_key=None,
        donate: bool = True,
    ) -> RegGridState:
        """Run epochs until ``done_fn(cell)`` holds on every granule (the
        predicate sees the granule-local cell dict, leaves (Tr, Tc, ...)),
        or at most ``max_epochs`` MORE epochs from the input state — the
        same relative-budget contract as ``GraphEngine.run_until``.  An
        already-done state runs zero epochs, so chunked (session) callers
        can re-enter."""
        anchor = cache_key if cache_key is not None else done_fn
        key = ("until", id(anchor), max_epochs, donate)
        if key not in self._cache:

            def run(state):
                local = _sq(state)
                e0 = local.epoch

                def pending_of(s):
                    not_done = 1 - done_fn(s.cell).astype(jnp.int32)
                    return jax.lax.psum(
                        jax.lax.psum(not_done, self.axis_r), self.axis_c
                    )

                def cond(carry):
                    s, pending = carry
                    return (pending > 0) & (s.epoch - e0 < max_epochs)

                def body(carry):
                    s, _ = carry
                    s = self._epoch(s)
                    return s, pending_of(s)

                out, _ = jax.lax.while_loop(cond, body, (local, pending_of(local)))
                return _unsq(out)

            self._cache[key] = (
                anchor,  # strong ref: keeps the keyed id alive
                jax.jit(
                    shard_map(run, mesh=self.mesh, in_specs=self._spec,
                              out_specs=self._spec),
                    donate_argnums=(0,) if donate else (),
                ),
            )
        if donate:
            from .distributed import _dealias_for_donation

            state = _dealias_for_donation(state)
        return self._cache[key][1](state)

    def run_until_done(
        self, state: RegGridState, max_epochs: int, *, donate: bool = True
    ) -> RegGridState:
        """Run epochs until every south cell collected all M outputs."""
        M = self.M
        return self.run_until(
            state,
            lambda cell: ((~cell["is_south"]) | (cell["y_idx"] >= M)).all(),
            max_epochs,
            cache_key="y_done",
            donate=donate,
        )

    # -------------------------------------------------------- host utilities
    def group_state(self, state: RegGridState, inst) -> dict:
        """One cell's (unstacked) state leaves — the uniform probe surface
        (``Simulation.probe``).  ``inst`` is the row-major instance id of
        the cell (or an ``Instance``), matching the IR numbering every
        other engine uses for the same grid."""
        inst_id = inst if isinstance(inst, int) else inst.inst_id
        r, c = divmod(int(inst_id), self.C)
        didx = (r // self.Tr, c // self.Tc)
        lr, lc = r % self.Tr, c % self.Tc
        cell = jax.device_get(state.cell)
        return {
            k: v[didx + (lr, lc)]
            for k, v in cell.items()
            if np.ndim(v) >= 4  # per-cell leaves carry (Dr, Dc, Tr, Tc, ...)
        }

    def result(self, state: RegGridState) -> np.ndarray:
        """Gather Y (M, C) from south-edge cells."""
        Dr, Dc, Tr, Tc = self.Dr, self.Dc, self.Tr, self.Tc
        y = np.asarray(jax.device_get(state.cell["y_buf"]))
        y = y.transpose(0, 2, 1, 3, 4).reshape(self.R, self.C, self.M)
        return y[self.R - 1].transpose(1, 0)  # (M, C)
