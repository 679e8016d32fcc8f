"""Channel-graph IR + GraphEngine property tests (DESIGN.md §1-§3).

The engine contract: for any partition map, the distributed epoch-batched
GraphEngine produces results identical to the single-netlist NetworkSim —
bit-exact final dataflow for handshaked networks at every epoch length K,
and additionally bit-exact *cycle timing* at K=1 (where the boundary
exchange runs every cycle, including for latency-sensitive links like the
hetero SoC's free-running analog sampler).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Block, ChannelGraph, Network, normalize_partition
from repro.core.compat import make_mesh
from repro.core.struct import pytree_dataclass

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
EXAMPLES = os.path.join(os.path.dirname(__file__), "..", "examples")


# ---------------------------------------------------------------- helpers
@pytree_dataclass
class IncState:
    count: jax.Array


class Increment(Block):
    in_ports = ("to_rtl",)
    out_ports = ("from_rtl",)
    payload_words = 2

    def init_state(self, key):
        return IncState(count=jnp.zeros((), jnp.int32))

    def step(self, state, rx, tx_ready):
        (pay, valid) = rx["to_rtl"]
        fire = valid & tx_ready["from_rtl"]
        return (
            state.replace(count=state.count + fire.astype(jnp.int32)),
            {"to_rtl": fire},
            {"from_rtl": (pay.at[0].add(1), fire)},
        )


def build_chain(n=3, capacity=8):
    net = Network(payload_words=2, capacity=capacity)
    blk = Increment()
    insts = [net.instantiate(blk, name=f"b{i}") for i in range(n)]
    net.external_in(insts[0]["to_rtl"], "tx")
    for a, b in zip(insts, insts[1:]):
        net.connect(a["from_rtl"], b["to_rtl"])
    net.external_out(insts[-1]["from_rtl"], "rx")
    return net


# ----------------------------------------------------------------- IR unit
def test_ir_channel_table_layout():
    net = build_chain(3)
    g = net.graph()
    # 2 sentinels + 2 internal + 1 ext_in + 1 ext_out
    assert g.n_channels == 6
    assert len(g.groups) == 1 and g.groups[0].n_members == 3
    assert g.ext_in == {"tx": 4} and g.ext_out == {"rx": 5}
    # b0 reads the external-in channel, unwired ports hit the sentinels
    assert g.rx_idx[0][0, 0] == 4
    assert g.tx_idx[0][2, 0] == 5
    np.testing.assert_array_equal(g.chan_src[[2, 3]], [0, 1])
    np.testing.assert_array_equal(g.chan_dst[[2, 3]], [1, 2])
    assert g.locate(1) == (0, 1)


def test_ir_rejects_double_connection():
    net = Network()
    blk = Increment()
    a = net.instantiate(blk, name="a")
    b = net.instantiate(blk, name="b")
    c = net.instantiate(blk, name="c")
    net.connect(a["from_rtl"], b["to_rtl"])
    net.connect(a["from_rtl"], c["to_rtl"])  # same tx port twice
    with pytest.raises(ValueError, match="SPSC"):
        net.graph()


def test_grid_builder_matches_network_builder():
    """Vectorized ChannelGraph.grid == per-instance Network wiring (up to
    channel renumbering, compared via endpoint pairs)."""
    from repro.hw.systolic import SystolicCell, make_cell_params, make_systolic_network

    rng = np.random.RandomState(0)
    A = rng.randn(4, 3).astype(np.float32)
    B = rng.randn(3, 5).astype(np.float32)
    net, _ = make_systolic_network(A, B)
    g_net = net.graph()
    g_grid = ChannelGraph.grid(g_net.groups[0].block, 3, 5)

    def pairs(g):
        return {
            (int(s), int(d))
            for cid, (s, d) in enumerate(zip(g.chan_src, g.chan_dst))
            if cid >= 2
        }

    assert g_net.n_channels == g_grid.n_channels
    assert pairs(g_net) == pairs(g_grid)


def test_normalize_partition_validation():
    net = build_chain(3)
    g = net.graph()
    np.testing.assert_array_equal(normalize_partition(g, None, 4), [0, 0, 0])
    np.testing.assert_array_equal(normalize_partition(g, {"b1": 2}, 4), [0, 2, 0])
    with pytest.raises(KeyError):
        normalize_partition(g, {"nope": 1}, 4)
    with pytest.raises(ValueError):
        normalize_partition(g, [0, 1, 9], 4)
    with pytest.raises(ValueError):
        normalize_partition(g, [0, 1], 4)


# -------------------------------------------- single-granule bit-exactness
@pytest.mark.parametrize("k_epoch", [1, 3, 16])
def test_graph_engine_matches_netlist_chain(k_epoch):
    """build(engine='graph') == build() through external ports, any K."""
    ref = build_chain(3).build()
    eng = build_chain(3).build(
        engine="graph", mesh=make_mesh((1,), ("gx",)), K=k_epoch
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
    # per-instance state access agrees too
    for i in range(3):
        assert int(ref.group_state(rs, i).count) == int(eng.group_state(es, i).count) == 3


@pytest.mark.parametrize("k_epoch", [1, 3, 16])
def test_graph_engine_matches_netlist_hetero(k_epoch):
    """The heterogeneous SoC (RTL + SW + rate-controlled analog blocks) on
    GraphEngine: K=1 is cycle-accurate, hence bit-exact even on the
    latency-*sensitive* analog path; K>1 keeps the handshaked (latency-
    insensitive) results exact and the analog drift bounded — the paper's
    Fig. 15 accuracy-vs-sync-rate trade, reproduced as a property."""
    sys.path.insert(0, EXAMPLES)
    try:
        import heterogeneous_soc as soc
    finally:
        sys.path.pop(0)

    cycles = 120 if k_epoch == 1 else 160
    truth = soc.run_single(cycles)
    net, cpu = soc.build_soc()
    eng = net.build(engine="graph", mesh=make_mesh((1,), ("gx",)), K=k_epoch)
    st = eng.init(jax.random.key(0))
    st = eng.run_epochs(st, -(-cycles // k_epoch))
    got = eng.group_state(st, cpu)
    assert int(got.n_done) == soc.N_REQ
    if k_epoch == 1:
        np.testing.assert_array_equal(np.asarray(got.results), np.asarray(truth.results))
    else:
        base = np.arange(soc.N_REQ) * 10.0
        drift = np.asarray(got.results) - base
        assert (drift >= 0).all() and (drift < 1.0).all()


@pytest.mark.parametrize("k_epoch", [1, 3, 16])
def test_graph_engine_matches_netlist_systolic(k_epoch):
    """Fully handshaked dataflow: results bit-exact for every K."""
    from repro.hw.systolic import (
        collect_result, cycles_needed, make_systolic_network,
    )

    rng = np.random.RandomState(2)
    M, K, N = 5, 4, 3
    A = rng.randn(M, K).astype(np.float32)
    B = rng.randn(K, N).astype(np.float32)
    net, grid = make_systolic_network(A, B)
    sim = net.build()
    s1 = sim.init(jax.random.key(0))
    s1 = sim.run(s1, cycles_needed(M, K, N))
    Y1 = collect_result(sim, s1, grid)

    net2, _ = make_systolic_network(A, B)
    eng = net2.build(engine="graph", mesh=make_mesh((1,), ("gx",)), K=k_epoch)
    st = eng.init(jax.random.key(0))
    st = eng.run_until(
        st,
        lambda s: ((~s.block_states[0].is_south) | (s.block_states[0].y_idx >= M)).all(),
        max_epochs=100_000,
    )
    flat = eng.gather_group(st, 0)
    Y2 = np.stack([flat.y_buf[(K - 1) * N + c] for c in range(N)], axis=1)
    np.testing.assert_allclose(Y1, Y2, atol=0)


def test_register_engine_from_ir():
    """build(engine='register'): the kernel-fused backend consumes the same
    IR and reconstructs the systolic operands from the stacked params."""
    from repro.hw.systolic import make_systolic_network

    rng = np.random.RandomState(3)
    M, R, C = 6, 4, 4
    A = rng.randn(M, R).astype(np.float32)
    B = rng.randn(R, C).astype(np.float32)
    net, _ = make_systolic_network(A, B)
    eng = net.build(engine="register", mesh=make_mesh((1, 1), ("gr", "gc")), K=4)
    st = eng.run_until_done(eng.init(), max_epochs=100_000)
    np.testing.assert_allclose(eng.result(st), A @ B, rtol=1e-5)
    # non-systolic IRs are rejected with a pointer to the general engine
    with pytest.raises(ValueError, match="SystolicCell"):
        build_chain(2).build(
            engine="register", mesh=make_mesh((1, 1), ("gr", "gc")), K=4
        )


# ----------------------------------------------- multi-granule (subprocess)
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


def test_random_partitions_bit_exact_multidevice():
    """ANY partition map over 4 real granules reproduces the single-netlist
    result exactly — the tentpole property of the channel-graph IR."""
    code = textwrap.dedent("""
        import numpy as np, jax
        from repro.core.compat import make_mesh
        from repro.hw.systolic import (
            collect_result, cycles_needed, make_systolic_network)

        rng = np.random.RandomState(5)
        M, K, N = 6, 5, 4
        A = rng.randn(M, K).astype(np.float32)
        B = rng.randn(K, N).astype(np.float32)
        net, grid = make_systolic_network(A, B)
        sim = net.build()
        s1 = sim.init(jax.random.key(0))
        s1 = sim.run(s1, cycles_needed(M, K, N))
        Y1 = collect_result(sim, s1, grid)

        mesh = make_mesh((4,), ('gx',))
        for seed in (0, 1):
            part = np.random.RandomState(seed).randint(0, 4, size=K * N)
            net2, _ = make_systolic_network(A, B)
            eng = net2.build(engine='graph', mesh=mesh, K=3, partition=part)
            st = eng.place(eng.init(jax.random.key(0)))
            st = eng.run_until(
                st,
                lambda s: ((~s.block_states[0].is_south)
                           | (s.block_states[0].y_idx >= M)).all(),
                100000)
            flat = eng.gather_group(st, 0)
            Y2 = np.stack([flat.y_buf[(K - 1) * N + c] for c in range(N)], axis=1)
            np.testing.assert_allclose(Y1, Y2, atol=0)
        print('RANDOM-PARTITION-OK')
    """)
    assert "RANDOM-PARTITION-OK" in _run_subprocess(code)


def test_hetero_soc_distributed_bit_exact_multidevice():
    """examples/heterogeneous_soc.py across a real multi-device mesh: the
    distributed K=1 run is bit-identical to the single netlist (the PR's
    acceptance scenario, exercised end to end)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, "heterogeneous_soc.py")],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "4 device(s)" in out.stdout
    assert "bit-identical to the single netlist" in out.stdout
