"""Signature-batched granule stepping (ISSUE 6 acceptance; DESIGN.md §Perf).

The batching contract: with ``batch_axes`` naming an innermost suffix of
the granule axes, same-signature granules stack on ONE leading batch axis
and step with a single dispatch per epoch window — per-row blocked on CPU
(each row's registers/queues are private buffers, see ``FusedEngine``) —
and the tier exchange becomes a local slab gather instead of a collective.
Batching is an *execution strategy*, not a semantics change: every result
below must be bit-exact vs the unbatched engines and the single-netlist
``NetworkSim``, including the latency-sensitive SoC analog path at
K=1/capacity 2 where the engines are cycle-accurate.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import ChannelGraph, FusedEngine, NetworkSim
from repro.core.compat import make_mesh
from repro.core.distributed import GraphEngine
from repro.core import perfmodel
from repro.hw.manycore import ManycoreCell, make_core_params
from test_fused import assert_allreduce_matches_netlist

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


def _torus(R, C, vals, capacity):
    return ChannelGraph.torus(
        ManycoreCell(R, C), R, C, params=make_core_params(vals),
        capacity=capacity,
    )


def _lower(graph, part, n_gran):
    from repro.core.graph import (
        PartitionTree, Tier, lower_partition, normalize_partition,
    )

    ptree = PartitionTree(
        normalize_partition(graph, part, n_gran),
        (Tier(axes=("g",), K=1),), {"g": n_gran},
    )
    return lower_partition(graph, ptree)


# ----------------------------------------------------------- lowering tables
def test_batch_plan_groups_same_signature():
    """Uniform fabric -> ONE signature group covering every granule, and
    the ``where`` inverse locates each granule's batch row."""
    R, C = 4, 4
    g = _torus(R, C, np.ones((R, C), np.float32), 4)

    part = np.arange(R * C) % 4
    batches, where = _lower(g, part, 4).batch_plan()
    assert [sorted(b) for b in batches] == [[0, 1, 2, 3]]
    for b, members in enumerate(batches):
        for r, gran in enumerate(members):
            assert where[gran] == (b, r)


def test_batch_plan_splits_differing_signatures():
    """Granules with different compiled shapes land in different groups
    (they cannot share a traced stepper).  A uniform torus can never
    split — slots are max-padded and a balanced digraph has eg==in per
    granule — so the discriminator is a heterogeneous netlist: the SoC's
    cpu granule and dram+adc granule trace to different steppers."""
    sys.path.insert(0, EXAMPLES)
    try:
        import heterogeneous_soc as soc
    finally:
        sys.path.remove(EXAMPLES)
    net, _cpu = soc.build_soc(capacity=2)
    g = ChannelGraph.from_network(net)

    part = np.array([0, 1, 1])  # cpu | dram+adc
    low = _lower(g, part, 2)
    assert low.granule_signature(0) != low.granule_signature(1)
    batches, where = low.batch_plan()
    assert len(batches) == 2 and all(len(b) == 1 for b in batches)
    assert where[0] != where[1]


# ------------------------------------------------- bit-exactness vs unbatched
def test_batched_bit_exact_random_hier_partitions_multidevice():
    """THE acceptance property: on random hierarchical partitions and both
    K=(1,1) and K=(2,4), the signature-batched GraphEngine AND FusedEngine
    converge to the same handshaked results as the single netlist."""
    code = textwrap.dedent("""
        import numpy as np, jax
        from repro.core import ChannelGraph, NetworkSim, FusedEngine
        from repro.core.compat import make_mesh
        from repro.core.distributed import GraphEngine
        from repro.hw.manycore import (
            ManycoreCell, allreduce_done, expected_total, make_core_params)

        R, C = 4, 6
        rng = np.random.RandomState(7)
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
        for seed in (0, 2):
            part = np.random.RandomState(seed).randint(0, 4, size=R * C)
            for (ko, ki) in ((1, 1), (2, 4)):
                tiers = [(('pod',), ko), (('gx',), ki)]
                for cls in (FusedEngine, GraphEngine):
                    eng = cls(torus(), part, mesh, tiers=tiers,
                              batch_axes=('pod', 'gx'))
                    s = eng.place(eng.init(jax.random.key(0)))
                    s = eng.run_until(s, done, 100000, cache_key='done')
                    got = np.asarray(eng.gather_group(s, 0).total)
                    np.testing.assert_array_equal(got, truth)
        print('BATCHED-BIT-EXACT-OK')
    """)
    assert "BATCHED-BIT-EXACT-OK" in _run_subprocess(code)


def test_batched_state_bit_exact_vs_unbatched_epochs():
    """Stronger than converged results: after every epoch the batched
    engine's GLOBAL state equals the unbatched engine's, leaf for leaf
    (the per-row blocked walk is a pure reordering of the same cycles).
    The unbatched reference shards its granules on a 4-device mesh; the
    batched engine folds that whole mesh onto the batch axis."""
    code = textwrap.dedent("""
        import numpy as np, jax
        from repro.core import ChannelGraph, FusedEngine
        from repro.core.compat import make_mesh
        from repro.hw.manycore import ManycoreCell, make_core_params

        R, C = 8, 8
        vals = (np.arange(R * C) % 13 + 1).astype(np.float32).reshape(R, C)
        g = lambda: ChannelGraph.torus(
            ManycoreCell(R, C), R, C, params=make_core_params(vals),
            capacity=8)
        mesh = make_mesh((4,), ("gx",))
        part = np.arange(R * C) // (R * C // 4)
        tiers = [(("gx",), 4)]
        b = FusedEngine(g(), part, mesh, tiers=tiers, batch_axes=("gx",))
        u = FusedEngine(g(), part, mesh, tiers=tiers)
        sb = b.place(b.init(jax.random.key(0)))
        su = u.place(u.init(jax.random.key(0)))
        for ep in range(5):
            sb = b.run_epochs(sb, 1, donate=False)
            su = u.run_epochs(su, 1, donate=False)
            # dynamic leaves only: the static lowering tables legitimately
            # differ (the batched lowering reorders port maps into rows)
            da = jax.device_get(sb).replace(tables=None)
            dc = jax.device_get(su).replace(tables=None)
            for a, c in zip(jax.tree.leaves(da), jax.tree.leaves(dc)):
                assert np.array_equal(np.asarray(a), np.asarray(c)), ep
        print('BATCHED-EPOCH-STATE-OK')
    """)
    assert "BATCHED-EPOCH-STATE-OK" in _run_subprocess(code, devices=4)


def test_batched_k11_cycle_accurate_capacity2():
    """K=(1,1) + capacity 2: the batched fused engine tracks the single
    netlist cycle by cycle — batching must not even reorder observable
    timing."""
    R, C = 4, 4
    vals = np.random.RandomState(3).randint(
        1, 20, size=(R, C)).astype(np.float32)
    sim = NetworkSim(_torus(R, C, vals, 2))
    eng = FusedEngine(
        _torus(R, C, vals, 2), np.arange(R * C) % 4, make_mesh((1,), ("gx",)),
        tiers=[(("gx",), 1)], batch_axes={"gx": 4},
    )
    ss = sim.init(jax.random.key(0))
    fs = eng.place(eng.init(jax.random.key(0)))
    for t in range(40):
        ss = sim.step(ss)
        fs = eng.run_epochs(fs, 1, donate=False)
        ref = np.asarray(ss.block_states[0].acc)
        got = np.asarray(eng.gather_group(fs, 0).acc)
        assert np.array_equal(ref, got), (t, ref, got)


def test_batched_soc_analog_k1_capacity2():
    """The hetero SoC's free-running analog path at K=1, capacity 2: the
    batched engine (heterogeneous signatures padded into one stack) stays
    cycle-accurate — results bit-identical to the single netlist."""
    sys.path.insert(0, EXAMPLES)
    try:
        import heterogeneous_soc as soc
    finally:
        sys.path.pop(0)

    cycles = 140
    truth = soc.run_single(cycles)
    net, cpu = soc.build_soc(capacity=2)
    eng = net.build(
        engine="fused", session=False, mesh=make_mesh((1,), ("host",)),
        partition=np.array([0, 1, 1]), tiers=[(("g",), 1)],
        batch_axes={"g": 2},
    )
    st = eng.place(eng.init(jax.random.key(0)))
    st = eng.run_epochs(st, cycles, donate=False)
    got = eng.group_state(st, cpu)
    assert int(got.n_done) == soc.N_REQ
    np.testing.assert_array_equal(
        np.asarray(got.results), np.asarray(truth.results))


# ------------------------------------------ resident body vs single netlist
def test_batched_resident_body_pallas_vs_xla_bit_identical():
    """The per-row resident body (pure-batch rows, serial exchange) ends
    the allreduce where the single netlist does, core for core."""
    R, C = 8, 4
    vals = (np.arange(R * C) % 11 + 1).astype(np.float32).reshape(R, C)
    mesh = make_mesh((1,), ("gx",))
    part = np.arange(R * C) % 2
    kw = dict(tiers=[(("gx",), 4)], batch_axes={"gx": 2})
    eng = FusedEngine(_torus(R, C, vals, 4), part, mesh, **kw)
    assert not eng.overlap
    assert_allreduce_matches_netlist(lambda: _torus(R, C, vals, 4), eng)


# ------------------------------------------------------ procs batched workers
def test_procs_batch_signatures_allreduce():
    """ProcsEngine(batch_signatures=True): one worker per signature group
    stepping its granules as a stack — the allreduce invariant witnesses
    every packet crossing every shared-memory boundary."""
    code = textwrap.dedent("""
        import numpy as np, jax
        from repro.core import Simulation
        from repro.core.graph import ChannelGraph, tiered_grid_partition
        from repro.runtime import ProcsEngine
        from repro.hw.manycore import (
            ManycoreCell, allreduce_done, expected_total, make_core_params)

        R = C = 4
        values = (np.arange(R * C, dtype=np.int64) % 7 + 1).astype(np.float32)
        graph = ChannelGraph.torus(
            ManycoreCell(R, C), R, C,
            params=make_core_params(values.reshape(R, C)), capacity=4)
        part = tiered_grid_partition(R, C, [(2, 2)])
        eng = ProcsEngine(graph, part, n_workers=4, K=2, timeout=120.0,
                          batch_signatures=True)
        sim = Simulation(eng)
        try:
            sim.reset(0)
            done = lambda s: allreduce_done(
                s.block_states[0], s.tables.active[0])
            sim.run(until=done, max_epochs=2000, cache_key='allreduce')
            totals = np.asarray(eng.gather_group(sim.state, 0).total)
            want = expected_total(values)
            assert np.array_equal(totals, np.full_like(totals, want)), (
                np.unique(totals), want)
        finally:
            sim.close()
        print('PROCS-BATCHED-OK')
    """)
    assert "PROCS-BATCHED-OK" in _run_subprocess(code, devices=1)


# ------------------------------------------------ dispatch-amortization model
def test_perfmodel_dispatch_amortization_limits():
    # batching one granule is free; overhead amortizes toward the pad limit
    assert perfmodel.dispatch_amortization(1, 2.0, 5.0) == pytest.approx(1.0)
    s_inf = perfmodel.dispatch_amortization(10_000, 2.0, 5.0)
    assert s_inf == pytest.approx((5.0 + 2.0) / 2.0, rel=1e-2)
    # padding waste can flip batching into a loss
    assert perfmodel.dispatch_amortization(8, 2.0, 0.1, pad_factor=3.0) < 1.0


def test_perfmodel_fit_roundtrips_model():
    t_step, t_disp = 3.0, 7.0
    B = 8
    tu = perfmodel.unbatched_epoch_time(B, t_step, t_disp)
    tb = perfmodel.batched_epoch_time(B, t_step, t_disp)
    fs, fd = perfmodel.fit_dispatch_overhead(tu, tb, B)
    assert fs == pytest.approx(t_step) and fd == pytest.approx(t_disp)
    # degenerate (batched slower) clamps instead of going negative
    fs2, fd2 = perfmodel.fit_dispatch_overhead(10.0, 90.0, 8)
    assert fd2 == 0.0 and fs2 >= 0.0
    with pytest.raises(ValueError):
        perfmodel.fit_dispatch_overhead(1.0, 1.0, 1)


def test_perfmodel_batching_crossover():
    # dispatch-dominated: batching wins from B ~ t_disp / gain upward
    b = perfmodel.batching_crossover(1.0, 9.0, pad_factor=1.0)
    assert 1.0 <= b <= 2.0
    # heavy padding: batching can never win
    assert perfmodel.batching_crossover(1.0, 0.5, pad_factor=4.0) == np.inf
    for B in (2, 4, 32):
        s = perfmodel.dispatch_amortization(B, 1.0, 9.0)
        assert (s > 1.0) == (B > b)
