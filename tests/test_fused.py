"""Fused-epoch engine property tests (ISSUE 3 acceptance; DESIGN.md §Perf).

The engine contract: ``FusedEngine`` lowers ANY partitioned channel graph
to depth-1 register channels + a fused K-cycle epoch body, and its
handshaked results are **bit-exact** vs ``GraphEngine`` and the
single-netlist ``NetworkSim`` for random topologies, random hierarchical
partitions and any (K_inner, K_outer).  With ``capacity=2`` the register
refinement is cycle-*identical* to the SPSC queues, so at K=(1,1) the
fused engine is additionally cycle-accurate — including the hetero SoC's
latency-sensitive free-running analog path.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ChannelGraph, FusedEngine, Network, NetworkSim, fold_mesh,
    tiered_grid_partition,
)
from repro.core.compat import make_mesh
from repro.core.distributed import GridEngine
from repro.hw.manycore import (
    ManycoreCell, allreduce_done, expected_total, make_core_params,
)
from repro.kernels import granule_step

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
EXAMPLES = os.path.join(os.path.dirname(__file__), "..", "examples")


def _run_subprocess(code: str, devices: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=600,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def build_chain(n=3, capacity=8):
    from test_graph import build_chain as _bc

    return _bc(n, capacity)


# ------------------------------------------------------------- lowering units
def test_fused_lowering_registers_vs_queues():
    """Intra-granule channels become registers; boundary + external channels
    stay queues (row 0 reserved as the padding scratch row)."""
    R, C = 3, 4
    g = ChannelGraph.torus(
        ManycoreCell(R, C), R, C,
        params=make_core_params(np.ones((R, C), np.float32)), capacity=4,
    )
    # single granule: every channel is intra -> registers only + scratch row
    eng1 = FusedEngine(g, None, make_mesh((1,), ("gx",)), K=2)
    assert eng1.n_reg == 2 + 2 * R * C
    assert eng1.n_q == 1
    # a multi-granule split needs real devices -> subprocess
    code = textwrap.dedent("""
        import numpy as np
        from repro.core import ChannelGraph, FusedEngine
        from repro.core.compat import make_mesh
        from repro.hw.manycore import ManycoreCell, make_core_params

        R, C = 3, 4
        g = ChannelGraph.torus(
            ManycoreCell(R, C), R, C,
            params=make_core_params(np.ones((R, C), np.float32)), capacity=4)
        part = (np.arange(R * C) % C >= C // 2).astype(np.int32)
        eng2 = FusedEngine(g, part, make_mesh((2,), ('gx',)), K=2)
        # boundary channels move to the queue array
        assert eng2.n_q > 1
        assert eng2.n_reg - 2 < 2 * R * C
        # exchange tables address queue rows, never the scratch row
        for si, sm in zip(eng2._send_idx_f, eng2._send_mask):
            assert (si[sm] > 0).all()
        for ri, rm in zip(eng2._recv_idx_f, eng2._recv_mask):
            assert (ri[rm] > 0).all()
        print('FUSED-LOWERING-OK')
    """)
    assert "FUSED-LOWERING-OK" in _run_subprocess(code, devices=2)


def test_epoch_loop_contract():
    carry = (jnp.zeros((4,)), jnp.zeros((), jnp.int32))
    out = granule_step.epoch_loop(
        lambda c: (c[0] + 1.0, c[1] + 1), carry, 5
    )
    assert int(out[1]) == 5 and float(out[0][0]) == 5.0
    # k=0 is the identity
    out0 = granule_step.epoch_loop(lambda c: c, carry, 0)
    assert out0 is carry
    # a body that changes shapes is rejected with a clear error
    with pytest.raises(TypeError, match="preserve"):
        granule_step.epoch_loop(
            lambda c: (jnp.zeros((5,)), c[1]), carry, 3
        )


# A toy carry (x, cycles, per-tier sums): a cycle adds 1 to x, tier t's
# exchange adds (t + 1) * sum(x) to its sum.  The split halves hand that
# value from issue to commit as the pending pytree.
def _toy_cycle(c, k):
    x, n, acc = c
    return x + k, n + 1, acc


def _toy_exchange(c, t, k):
    x, n, acc = c
    return x, n, acc.at[t].add((t + 1) * jnp.sum(x))


def _toy_issue(c, t, k):
    return c, (t + 1) * jnp.sum(c[0])


def _toy_commit(c, t, pending, k):
    x, n, acc = c
    return x, n, acc.at[t].add(pending)


@pytest.mark.parametrize("program", [
    (("C", 3), ("X", 1), ("C", 3), ("X", 1), ("X", 0)),
    (("C", 1), ("X", 0), ("C", 4), ("X", 1), ("X", 0), ("C", 1)),
], ids=["loops", "single-cycle-blocks"])
def test_epoch_program_split_matches_serial(program):
    """``epoch_program`` walks a serial op list and its ``overlap_program``
    rewrite (issue/commit halves) to the same carry, the one a plain
    Python walk of the same ops reaches."""
    carry = (jnp.zeros((4,), jnp.float32), jnp.zeros((), jnp.int32),
             jnp.zeros((2,), jnp.float32))
    consts = jnp.float32(1.0)
    fns = dict(exchange_fn=_toy_exchange, issue_fn=_toy_issue,
               commit_fn=_toy_commit, consts=consts)
    split = granule_step.overlap_program(program)
    assert any(op == "XI" for op, _ in split)
    serial = granule_step.epoch_program(_toy_cycle, carry, program, **fns)
    overlapped = granule_step.epoch_program(_toy_cycle, carry, split, **fns)

    x, acc = 0.0, [0.0, 0.0]
    for op, arg in program:
        if op == "C":
            x += arg
        else:
            acc[arg] += (arg + 1) * 4 * x
    assert int(serial[1]) == sum(a for op, a in program if op == "C")
    np.testing.assert_array_equal(np.asarray(serial[0]), np.full(4, x))
    np.testing.assert_array_equal(np.asarray(serial[2]), np.array(acc))
    for a, b in zip(serial, overlapped):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def assert_allreduce_matches_netlist(graph_fn, eng) -> None:
    """Run the torus allreduce on ``eng`` to done and compare every core's
    final state with the single netlist on ``graph_fn()``: the ring
    handshakes fix the final state by the values alone, for any partition
    and any sync period."""
    sim = NetworkSim(graph_fn())
    want = sim.run_until(
        sim.init(jax.random.key(0)),
        lambda s: allreduce_done(s.block_states[0]), 10_000,
    ).block_states[0]
    done = lambda s: allreduce_done(s.block_states[0], s.tables.active[0])  # noqa: E731
    st = eng.run_until(eng.place(eng.init(jax.random.key(0))), done, 10_000,
                       cache_key="done")
    got = eng.gather_group(st, 0)
    assert (np.asarray(want.phase) == 2).all()
    for f in want.__dataclass_fields__:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, f)), np.asarray(getattr(want, f)),
            err_msg=f)


# (rows, cols, queue capacity) of each epoch-program layout's torus; the
# folded wafer runs wafer_64k's queue depth and sync periods on 16x16
_LAYOUT_SIZES = {"unbatched": (3, 4, 4), "batch-rows": (8, 4, 4),
                 "wafer-folded": (16, 16, 62)}


def _epoch_layout(layout):
    """A torus builder and the fused engine for one epoch-program layout."""
    R, C, cap = _LAYOUT_SIZES[layout]
    vals = (np.arange(R * C) % 11 + 1).astype(np.float32).reshape(R, C)

    def graph():
        return ChannelGraph.torus(
            ManycoreCell(R, C), R, C, params=make_core_params(vals),
            capacity=cap)

    if layout == "unbatched":
        eng = FusedEngine(graph(), None, make_mesh((1,), ("gx",)), K=4)
    elif layout == "batch-rows":
        eng = FusedEngine(
            graph(), np.arange(R * C) % 2, make_mesh((1,), ("gx",)),
            tiers=[(("gx",), 4)], batch_axes={"gx": 2})
    else:
        # wafer_64k's two tiers, all eight granules folded onto one device
        mesh, batch = fold_mesh({"pod": 2, "gr": 2, "gc": 2},
                                jax.devices()[:1])
        eng = FusedEngine(
            graph(), tiered_grid_partition(R, C, [(2, 1), (2, 2)]), mesh,
            tiers=[(("pod",), 4), (("gr", "gc"), 16)], batch_axes=batch)
    return graph, eng


@pytest.mark.parametrize("layout", list(_LAYOUT_SIZES))
def test_fused_epoch_layouts_match_netlist(layout):
    """The fused epoch body — ``epoch_loop`` per granule, the per-row walk
    of a pure-batch engine, and the folded two-tier wafer layout — ends
    the allreduce where the single netlist does, core for core."""
    graph, eng = _epoch_layout(layout)
    if layout == "wafer-folded":
        assert eng._batched and not eng.real_axes
    assert_allreduce_matches_netlist(graph, eng)


@pytest.mark.parametrize("kwarg", ["fuse", "pallas_interpret"])
def test_fused_build_rejects_retired_epoch_knobs(kwarg):
    """The fused engine has one epoch body: the old strategy knobs are
    unknown build kwargs, not silently ignored."""
    with pytest.raises(TypeError, match=kwarg):
        build_chain(3).build(engine="fused", mesh=make_mesh((1,), ("gx",)),
                             **{kwarg: "xla"})


# ------------------------------------------ single-granule vs single netlist
@pytest.mark.parametrize("k_epoch", [1, 3, 16])
def test_fused_matches_netlist_chain(k_epoch):
    """build(engine='fused') == build() through external ports, any K."""
    ref = build_chain(3).build()
    eng = build_chain(3).build(
        engine="fused", mesh=make_mesh((1,), ("gx",)), K=k_epoch
    )
    rs = ref.init(jax.random.key(0))
    es = eng.init(jax.random.key(0))
    for v in (10.0, 20.0, 30.0):
        rs, ok1 = ref.push_external(rs, "tx", jnp.array([v, v]))
        es, ok2 = eng.push_external(es, "tx", jnp.array([v, v]))
        assert bool(ok1) and bool(ok2)
    rs = ref.run(rs, 48)
    es = eng.run_epochs(es, -(-48 // k_epoch))
    for _ in range(3):
        rs, p1, v1 = ref.pop_external(rs, "rx")
        es, p2, v2 = eng.pop_external(es, "rx")
        assert bool(v1) and bool(v2)
        np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
    for i in range(3):
        assert int(ref.group_state(rs, i).count) == int(eng.group_state(es, i).count) == 3


@pytest.mark.parametrize("k_epoch", [1, 16])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32, jnp.bfloat16],
                         ids=["float32", "int32", "bfloat16"])
def test_fused_matches_netlist_chain_payload_dtype(dtype, k_epoch):
    """Valid/ready flags cross the port gathers as columns of the payload
    rows (32-bit payloads) or as a separate int32 array (any other): both
    forms keep the chain bit-exact vs the netlist, registers and queues."""
    from test_graph import Increment

    def chain():
        net = Network(payload_words=2, capacity=8, dtype=dtype)
        insts = [net.instantiate(Increment(), name=f"b{i}") for i in range(3)]
        net.external_in(insts[0]["to_rtl"], "tx")
        for a, b in zip(insts, insts[1:]):
            net.connect(a["from_rtl"], b["to_rtl"])
        net.external_out(insts[-1]["from_rtl"], "rx")
        return net

    ref = chain().build()
    eng = chain().build(
        engine="fused", mesh=make_mesh((1,), ("gx",)), K=k_epoch
    )
    rs = ref.init(jax.random.key(0))
    es = eng.init(jax.random.key(0))
    sent = [[-7, 5], [0, -1], [96, 3]]
    for v in sent:
        rs, ok1 = ref.push_external(rs, "tx", jnp.array(v, dtype))
        es, ok2 = eng.push_external(es, "tx", jnp.array(v, dtype))
        assert bool(ok1) and bool(ok2)
    rs = ref.run(rs, 48)
    es = eng.run_epochs(es, -(-48 // k_epoch))
    for v in sent:
        rs, p1, v1 = ref.pop_external(rs, "rx")
        es, p2, v2 = eng.pop_external(es, "rx")
        assert bool(v1) and bool(v2)
        assert p1.dtype == p2.dtype == jnp.dtype(dtype)
        np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
        np.testing.assert_array_equal(
            np.asarray(p2, np.float32), [v[0] + 3, v[1]])
    es, _, v3 = eng.pop_external(es, "rx")
    assert not bool(v3)
    for i in range(3):
        assert int(ref.group_state(rs, i).count) == int(eng.group_state(es, i).count) == 3


@pytest.mark.parametrize("k_epoch", [1, 4])
def test_fused_matches_netlist_hetero_analog(k_epoch):
    """The hetero SoC (RTL + SW + rate-controlled analog blocks): K=1 is
    cycle-accurate — bit-identical even on the latency-*sensitive*
    free-running analog path — and K>1 keeps handshaked results exact with
    bounded analog drift (the Fig. 15 property, on the fused engine)."""
    sys.path.insert(0, EXAMPLES)
    try:
        import heterogeneous_soc as soc
    finally:
        sys.path.pop(0)

    cycles = 120 if k_epoch == 1 else 160
    truth = soc.run_single(cycles)
    net, cpu = soc.build_soc()
    eng = net.build(engine="fused", mesh=make_mesh((1,), ("gx",)), K=k_epoch)
    st = eng.run_epochs(eng.init(jax.random.key(0)), -(-cycles // k_epoch))
    got = eng.group_state(st, cpu)
    assert int(got.n_done) == soc.N_REQ
    if k_epoch == 1:
        np.testing.assert_array_equal(
            np.asarray(got.results), np.asarray(truth.results)
        )
    else:
        base = np.arange(soc.N_REQ) * 10.0
        drift = np.asarray(got.results) - base
        assert (drift >= 0).all() and (drift < 1.0).all()


def test_fused_cycle_accurate_at_capacity_2():
    """With capacity=2 a depth-1 register IS the SPSC queue (holds one
    packet, same pre-cycle snapshot), so the fused engine tracks the
    single netlist cycle by cycle — the strongest accuracy claim."""
    R, C = 3, 5
    rng = np.random.RandomState(1)
    vals = rng.randint(1, 50, size=(R, C)).astype(np.float32)
    g = ChannelGraph.torus(
        ManycoreCell(R, C), R, C, params=make_core_params(vals), capacity=2
    )
    sim = NetworkSim(g)
    eng = FusedEngine(g, None, make_mesh((1,), ("gx",)), K=1)
    ss = sim.init(jax.random.key(0))
    fs = eng.init(jax.random.key(0))
    for t in range(60):
        ss = sim.step(ss)
        fs = eng.run_epochs(fs, 1, donate=False)
        ref = jax.tree.leaves(ss.block_states[0])
        got = jax.tree.leaves(eng.gather_group(fs, 0))
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=f"cycle {t}")


def test_fused_grid_preset_matches_grid_engine():
    """FusedEngine.grid == GridEngine on the systolic app (the GridEngine
    preset of the fused family)."""
    from repro.hw.systolic import SystolicCell, make_cell_params

    rng = np.random.RandomState(3)
    M, R, C = 6, 4, 4
    A = rng.randn(M, R).astype(np.float32)
    B = rng.randn(R, C).astype(np.float32)
    mesh = make_mesh((1, 1), ("gr", "gc"))
    done_cells = lambda cells: ((~cells.is_south) | (cells.y_idx >= M)).all()  # noqa: E731
    qeng = GridEngine(SystolicCell(m_stream=M), R, C, mesh, K=4)
    qs = qeng.init(jax.random.key(0), make_cell_params(A, B))
    qs = qeng.run_until(qs, done_cells, 10_000, cache_key="done")
    feng = FusedEngine.grid(SystolicCell(m_stream=M), R, C, mesh, K=4)
    fs = feng.init(
        jax.random.key(0),
        group_params={0: jax.tree.map(
            lambda x: jnp.reshape(jnp.asarray(x), (R * C,) + jnp.shape(x)[2:]),
            make_cell_params(A, B),
        )},
    )
    fs = feng.run_until(
        fs, lambda s: done_cells(s.block_states[0]), 10_000, cache_key="done"
    )
    Yq = np.asarray(qeng.gather_cells(qs).y_buf)
    Yf = np.asarray(feng.gather_group(fs, 0).y_buf).reshape(R, C, M)
    np.testing.assert_array_equal(Yq[-1], Yf[-1])  # south row: the results
    np.testing.assert_allclose(Yf[-1].transpose(1, 0), A @ B, rtol=1e-5)


# ----------------------------------------------- multi-granule (subprocess)
def test_fused_bit_exact_random_hier_partitions_multidevice():
    """THE acceptance property: for random topology partitions and any
    (K_inner, K_outer), the fused engine's handshaked results are bit-exact
    vs the tiered GraphEngine and vs NetworkSim."""
    code = textwrap.dedent("""
        import numpy as np, jax
        from repro.core import ChannelGraph, NetworkSim, FusedEngine
        from repro.core.compat import make_mesh
        from repro.core.distributed import GraphEngine
        from repro.hw.manycore import (
            ManycoreCell, allreduce_done, expected_total, make_core_params)

        R, C = 4, 6
        rng = np.random.RandomState(11)
        vals = rng.randint(1, 30, size=(R, C)).astype(np.float32)

        def torus():
            return ChannelGraph.torus(
                ManycoreCell(R, C), R, C,
                params=make_core_params(vals), capacity=4)

        sim = NetworkSim(torus())
        st = sim.init(jax.random.key(0))
        st = sim.run(st, 400)
        truth = np.asarray(st.block_states[0].total)
        assert (truth == expected_total(vals)).all()

        mesh = make_mesh((2, 2), ('pod', 'gx'))
        done = lambda s: allreduce_done(s.block_states[0], s.tables.active[0])
        for seed in (0, 1, 2):
            part = np.random.RandomState(seed).randint(0, 4, size=R * C)
            for (ko, ki) in ((1, 1), (2, 3), (4, 4)):
                tiers = [(('pod',), ko), (('gx',), ki)]
                feng = FusedEngine(torus(), part, mesh, tiers=tiers)
                s = feng.place(feng.init(jax.random.key(0)))
                s = feng.run_until(s, done, 100000, cache_key='done')
                got = np.asarray(feng.gather_group(s, 0).total)
                np.testing.assert_array_equal(got, truth)
                # the queue engine agrees under the identical schedule
                geng = GraphEngine(torus(), part, mesh, tiers=tiers)
                s2 = geng.place(geng.init(jax.random.key(0)))
                s2 = geng.run_until(s2, done, 100000, cache_key='done')
                np.testing.assert_array_equal(
                    np.asarray(geng.gather_group(s2, 0).total), truth)
        print('FUSED-BIT-EXACT-OK')
    """)
    assert "FUSED-BIT-EXACT-OK" in _run_subprocess(code)


def test_fused_k11_cycle_accurate_multidevice_capacity2():
    """K=(1,1) + capacity 2: the fused engine is cycle-accurate vs the
    single netlist across a real 2x2 (pod, gx) mesh split."""
    code = textwrap.dedent("""
        import numpy as np, jax
        from repro.core import ChannelGraph, NetworkSim, FusedEngine
        from repro.core.compat import make_mesh
        from repro.hw.manycore import ManycoreCell, make_core_params

        R, C = 4, 4
        rng = np.random.RandomState(5)
        vals = rng.randint(1, 20, size=(R, C)).astype(np.float32)

        def torus():
            return ChannelGraph.torus(
                ManycoreCell(R, C), R, C,
                params=make_core_params(vals), capacity=2)

        sim = NetworkSim(torus())
        ss = sim.init(jax.random.key(0))
        mesh = make_mesh((2, 2), ('pod', 'gx'))
        part = np.random.RandomState(0).randint(0, 4, size=R * C)
        eng = FusedEngine(torus(), part, mesh,
                          tiers=[(('pod',), 1), (('gx',), 1)])
        fs = eng.place(eng.init(jax.random.key(0)))
        for t in range(50):
            ss = sim.step(ss)
            fs = eng.run_epochs(fs, 1, donate=False)
            ref = np.asarray(ss.block_states[0].acc)
            got = np.asarray(eng.gather_group(fs, 0).acc)
            assert np.array_equal(ref, got), (t, ref, got)
        print('FUSED-K11-CYCLE-OK')
    """)
    assert "FUSED-K11-CYCLE-OK" in _run_subprocess(code)


def test_fused_wafer_allreduce_multidevice():
    """Wafer-style end-to-end: tiered 2-pod mesh, fused engine, global-sum
    invariant across every granule and tier boundary."""
    code = textwrap.dedent("""
        import numpy as np, jax
        from repro.core import ChannelGraph, FusedEngine, tiered_grid_partition
        from repro.core.compat import make_mesh
        from repro.hw.manycore import (
            ManycoreCell, allreduce_done, expected_total, make_core_params)

        N = 16
        values = (np.arange(N * N) % 23 + 1).astype(np.float32)
        graph = ChannelGraph.torus(
            ManycoreCell(N, N), N, N,
            params=make_core_params(values.reshape(N, N)), capacity=8)
        mesh = make_mesh((2, 2), ('pod', 'gx'))
        part = tiered_grid_partition(N, N, [(2, 1), (1, 2)])
        eng = FusedEngine(graph, part, mesh,
                          tiers=[(('pod',), 4), (('gx',), 8)])
        done = lambda s: allreduce_done(s.block_states[0], s.tables.active[0])
        st = eng.place(eng.init(jax.random.key(0)))
        st = eng.run_until(st, done, 100000, cache_key='done')
        totals = np.asarray(eng.gather_group(st, 0).total)
        assert np.array_equal(
            totals, np.full_like(totals, expected_total(values)))
        print('FUSED-WAFER-OK')
    """)
    assert "FUSED-WAFER-OK" in _run_subprocess(code)
