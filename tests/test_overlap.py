"""Overlapped (split issue/commit) exchange tests — ISSUE 7 acceptance.

The split schedule rewrites every sync boundary's serial exchanges
``X_a, X_b`` into ``XI_a, XI_b, XC_a, XC_b`` with the next window's
compute between issue and commit, so in-flight slabs cross a loop
iteration and transfers hide under compute.  The contract under test:

  * the rewrite (``overlap_program``) and its pairing discipline
    (``validate_program``) are exactly as specified;
  * ``overlap`` resolves explicit-arg > ``REPRO_OVERLAP`` env > auto(off),
    and reaches every engine;
  * the overlapped engines are **bit-identical** to the serial ones —
    full state, every epoch — on random hierarchical partitions, any
    (K_inner, K_outer), all engine paths (GraphEngine, FusedEngine,
    signature-batched, the resident per-row body, and the free-running
    procs fleet), and cycle-accurate vs the single netlist at K=(1,1).
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
from repro.hw.manycore import (
    ManycoreCell, allreduce_done, expected_total, make_core_params,
)
from repro.kernels import granule_step
from test_fused import assert_allreduce_matches_netlist

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


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


def _torus(R, C, vals, cap):
    return ChannelGraph.torus(
        ManycoreCell(R, C), R, C, params=make_core_params(vals), capacity=cap)


def _state_leaves(state):
    return jax.tree.leaves(jax.device_get(state).replace(tables=None))


# ------------------------------------------------------ program rewrite units
def test_overlap_program_splits_boundary_runs():
    prog = (("C", 4), ("X", 1), ("C", 4), ("X", 1), ("X", 0))
    split = granule_step.overlap_program(prog)
    assert split == (
        ("C", 4), ("XI", 1), ("XC", 1), ("C", 4),
        ("XI", 1), ("XI", 0), ("XC", 1), ("XC", 0),
    )
    # the rewrite always satisfies the pairing discipline
    assert granule_step.validate_program(split) == split
    # no exchanges -> unchanged; already-split ops pass through untouched
    assert granule_step.overlap_program((("C", 2),)) == (("C", 2),)
    assert granule_step.overlap_program(split) == split


def test_validate_program_rejects_broken_pairings():
    with pytest.raises(ValueError, match="unknown program op"):
        granule_step.validate_program((("Q", 0),))
    with pytest.raises(ValueError, match="issued twice"):
        granule_step.validate_program((("XI", 0), ("XI", 0)))
    with pytest.raises(ValueError, match="no pending issue"):
        granule_step.validate_program((("XC", 1),))
    with pytest.raises(ValueError, match="serial exchange"):
        granule_step.validate_program((("XI", 0), ("X", 0)))
    with pytest.raises(ValueError, match="uncommitted"):
        granule_step.validate_program((("XI", 0), ("C", 1)))


# ------------------------------------------------------- knob resolution
def test_resolve_overlap_precedence(monkeypatch):
    monkeypatch.delenv("REPRO_OVERLAP", raising=False)
    assert granule_step.resolve_overlap("auto") is False
    assert granule_step.resolve_overlap(True) is True
    assert granule_step.resolve_overlap("on") is True
    assert granule_step.resolve_overlap("off") is False
    # env overrides a caller-passed "auto" ...
    monkeypatch.setenv("REPRO_OVERLAP", "1")
    assert granule_step.resolve_overlap("auto") is True
    # ... but an explicit argument always beats the env
    assert granule_step.resolve_overlap(False) is False
    monkeypatch.setenv("REPRO_OVERLAP", "bogus")
    with pytest.raises(ValueError, match="REPRO_OVERLAP"):
        granule_step.resolve_overlap("auto")


def test_overlap_env_reaches_engines(monkeypatch):
    R, C = 4, 4
    vals = np.ones((R, C), np.float32)
    mesh = make_mesh((1, 1), ("pod", "gx"))
    kw = dict(tiers=[(("pod",), 2), (("gx",), 2)])
    monkeypatch.setenv("REPRO_OVERLAP", "1")
    assert GraphEngine(_torus(R, C, vals, 4), None, mesh, **kw).overlap
    assert FusedEngine(_torus(R, C, vals, 4), None, mesh, **kw).overlap
    eng = GraphEngine(_torus(R, C, vals, 4), None, mesh, overlap=False, **kw)
    assert not eng.overlap
    monkeypatch.delenv("REPRO_OVERLAP")
    assert not GraphEngine(_torus(R, C, vals, 4), None, mesh, **kw).overlap


# ----------------------------------------- bit identity, epoch by epoch
@pytest.mark.parametrize("ko,ki", [(1, 1), (2, 3), (4, 4)])
@pytest.mark.parametrize("cls", [GraphEngine, FusedEngine])
def test_overlap_state_bit_identical_single_device(cls, ko, ki):
    """After EVERY epoch the overlapped engine's full dynamic state equals
    the serial engine's, leaf for leaf — the split schedule is a pure
    reordering of the same cycle/exchange work."""
    R, C = 6, 6
    vals = (np.arange(R * C) % 13 + 1).astype(np.float32).reshape(R, C)
    # no real devices: both mesh axes fold onto the batch dimension, so 4
    # granules exchange through batched tables on one host device
    mesh = make_mesh((1, 1), ("pod", "gx"))
    part = np.arange(R * C) % 4
    kw = dict(tiers=[(("pod",), ko), (("gx",), ki)],
              batch_axes={"pod": 2, "gx": 2})
    ser = cls(_torus(R, C, vals, 4), part, mesh, overlap=False, **kw)
    ovl = cls(_torus(R, C, vals, 4), part, mesh, overlap=True, **kw)
    ss = ser.place(ser.init(jax.random.key(0)))
    so = ovl.place(ovl.init(jax.random.key(0)))
    for ep in range(5):
        ss = ser.run_epochs(ss, 1, donate=False)
        so = ovl.run_epochs(so, 1, donate=False)
        for a, b in zip(_state_leaves(ss), _state_leaves(so)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (ep, ko, ki)


def test_overlap_bit_exact_random_hier_partitions_multidevice():
    """THE acceptance property: on random hierarchical partitions, sharded
    over 4 real devices, for K=(1,1) and K=(2,4), graph/fused/batched
    engines under ``overlap=True`` converge to the same handshaked totals
    as the single netlist AND match their serial twins epoch by epoch."""
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
        st = sim.run(sim.init(jax.random.key(0)), 400)
        truth = np.asarray(st.block_states[0].total)
        assert (truth == expected_total(vals)).all()

        mesh = make_mesh((2, 2), ('pod', 'gx'))
        done = lambda s: allreduce_done(s.block_states[0], s.tables.active[0])
        variants = [
            (GraphEngine, {}), (FusedEngine, {}),
            (FusedEngine, {'batch_axes': ('pod', 'gx')}),
        ]
        for seed in (0, 2):
            part = np.random.RandomState(seed).randint(0, 4, size=R * C)
            for (ko, ki) in ((1, 1), (2, 4)):
                tiers = [(('pod',), ko), (('gx',), ki)]
                for cls, kw in variants:
                    ser = cls(torus(), part, mesh, tiers=tiers,
                              overlap=False, **kw)
                    ovl = cls(torus(), part, mesh, tiers=tiers,
                              overlap=True, **kw)
                    ss = ser.place(ser.init(jax.random.key(0)))
                    so = ovl.place(ovl.init(jax.random.key(0)))
                    for ep in range(4):  # state equality, epoch by epoch
                        ss = ser.run_epochs(ss, 1, donate=False)
                        so = ovl.run_epochs(so, 1, donate=False)
                        da = jax.device_get(ss).replace(tables=None)
                        db = jax.device_get(so).replace(tables=None)
                        for a, b in zip(jax.tree.leaves(da),
                                        jax.tree.leaves(db)):
                            assert np.array_equal(
                                np.asarray(a), np.asarray(b)), (ep, ko, ki)
                    # and the overlapped engine still converges to truth
                    so = ovl.run_until(so, done, 100000, cache_key='done')
                    got = np.asarray(ovl.gather_group(so, 0).total)
                    np.testing.assert_array_equal(got, truth)
        print('OVERLAP-BIT-EXACT-OK')
    """)
    assert "OVERLAP-BIT-EXACT-OK" in _run_subprocess(code)


def test_overlap_k11_cycle_accurate_capacity2():
    """K=(1,1) + capacity 2 (the tightest handshake): the overlapped fused
    engine tracks the single netlist cycle by cycle — splitting the
    exchange must not even reorder observable timing."""
    R, C = 4, 4
    vals = np.random.RandomState(3).randint(
        1, 20, size=(R, C)).astype(np.float32)
    sim = NetworkSim(_torus(R, C, vals, 2))
    eng = FusedEngine(
        _torus(R, C, vals, 2), np.arange(R * C) % 4, make_mesh((1,), ("gx",)),
        tiers=[(("gx",), 1)], batch_axes={"gx": 4}, overlap=True,
    )
    ss = sim.init(jax.random.key(0))
    fs = eng.place(eng.init(jax.random.key(0)))
    for t in range(40):
        ss = sim.step(ss)
        fs = eng.run_epochs(fs, 1, donate=False)
        ref = np.asarray(ss.block_states[0].acc)
        got = np.asarray(eng.gather_group(fs, 0).acc)
        assert np.array_equal(ref, got), (t, ref, got)


# ------------------------------------------ resident body vs single netlist
def test_overlap_resident_pallas_vs_xla_bit_identical():
    """Under the split schedule the per-row resident body still ends the
    allreduce where the single netlist does, core for core."""
    R, C = 8, 4
    vals = (np.arange(R * C) % 11 + 1).astype(np.float32).reshape(R, C)
    mesh = make_mesh((1,), ("gx",))
    part = np.arange(R * C) % 2
    kw = dict(tiers=[(("gx",), 4)], batch_axes={"gx": 2}, overlap=True)
    eng = FusedEngine(_torus(R, C, vals, 4), part, mesh, **kw)
    assert eng.overlap
    assert_allreduce_matches_netlist(lambda: _torus(R, C, vals, 4), eng)


# ---------------------------------------------- free-running procs fleet
@pytest.mark.parametrize("batch", [False, True])
def test_procs_overlap_bit_identical(batch):
    """The receive-late worker fleet under ``overlap=True`` produces the
    SAME full gathered state as the strict serial fleet — send-early
    pushes and first-ready pops reorder ring traffic, never data."""
    from repro.core import Simulation
    from repro.core.graph import PartitionTree, Tier, tiered_grid_partition
    from repro.runtime import ProcsEngine

    R = C = 4
    values = (np.arange(R * C) % 7 + 1).astype(np.float32)
    states = []
    for overlap in (False, True):
        graph = _torus(R, C, values.reshape(R, C), 4)
        part = tiered_grid_partition(R, C, [(2, 1), (2, 1)])
        ptree = PartitionTree(
            part, (Tier(axes=("pod",), K=2), Tier(axes=("g",), K=2)),
            {"pod": 2, "g": 2})
        eng = ProcsEngine(graph, ptree, timeout=60.0, overlap=overlap,
                          batch_signatures=batch)
        try:
            sim = Simulation(eng)
            sim.reset(0)
            sim.run(epochs=6)
            states.append(jax.device_get(eng.gather_state(sim.state)))
            stats = eng.worker_stats(sim.state)
            assert all("wait_fraction" in w for w in stats)
        finally:
            eng.close()
    for a, b in zip(jax.tree.leaves(states[0]), jax.tree.leaves(states[1])):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_procs_ring_depth_guard():
    """A boundary ring too shallow for two in-flight exchange windows must
    fail at launch with a diagnosis — not deadlock the fleet at runtime."""
    from repro.core.graph import tiered_grid_partition
    from repro.runtime import ProcsEngine

    R = C = 4
    graph = _torus(R, C, np.ones((R, C), np.float32), 4)
    part = tiered_grid_partition(R, C, [(2, 2)])
    with pytest.raises(ValueError, match="ring_depth=1 is too shallow"):
        ProcsEngine(graph, part, K=2, ring_depth=1, timeout=60.0)
