"""The scope split (``chipbench/scopes.py``) on hand-made traces, on a
hand-built profile file and on a trace recorded on a TPU v5e; the
existing readers pinned on the two older recorded traces; and the
recorder's spans on the profiler's clock."""
import glob
import os

import numpy as np
import pytest

from chipbench import harness, scopes, tracing, xplane
from chipbench.metrics import reduce

HERE = os.path.dirname(os.path.abspath(__file__))


def _ops(names, paths, events):
    """A one-device trace, the window [0, 1000] and ops given as (name
    index, start, end), and its scope table."""
    ids, st, en = (np.array(x) for x in zip(*events))
    lines = [
        tracing.Line("/host:CPU", "python", [tracing.WINDOW],
                     np.zeros(1, np.int32), np.zeros(1), np.full(1, 1000.0)),
        tracing.Line("/device:TPU:0", reduce.OPS, names,
                     ids.astype(np.int32), st.astype(float),
                     en.astype(float)),
    ]
    return lines, {("/device:TPU:0", reduce.OPS): paths}


def test_scope_of_takes_the_last_sb_component():
    assert scopes.scope_of("jit(run)/while/body/sb.read/gather") == "sb.read"
    assert scopes.scope_of("sb.drain/sb.permute/take_along_axis") == \
        "sb.permute"
    assert scopes.scope_of("jit(run)/closed_call/sb.done") == "sb.done"
    assert scopes.scope_of("jit(run)/sb.readout/add") == "sb.readout"
    assert scopes.scope_of("jit(run)/while/body/add") == scopes.UNSCOPED
    assert scopes.scope_of("") == scopes.UNSCOPED


def test_scope_shares_by_self_time():
    """Worked by hand: a 500 ns fusion under sb.step holds a 100 ns copy
    under sb.write (self time 400 + 100); then sb.read 100, sb.drain 50,
    sb.permute 50, sb.fill 40, sb.rows_split 60, sb.done 40 and an
    unscoped 60; an op past the window (at 990, 20 long) counts 10."""
    names = ["%fusion.1", "%copy.2", "%gather.3", "%ds.4", "%perm.5",
             "%fill.6", "%slice.7", "%reduce.8", "%copy.9", "%late.10"]
    paths = ["jit(run)/while/body/sb.step/vmap(step)/add",
             "jit(run)/while/body/sb.write/concatenate",
             "jit(run)/while/body/sb.read/gather",
             "jit(run)/while/body/sb.drain/dynamic_slice",
             "jit(run)/while/body/sb.drain/sb.permute/take_along_axis",
             "jit(run)/while/body/sb.fill/scatter",
             "jit(run)/sb.rows_split/slice",
             "jit(run)/while/body/sb.done/reduce_and",
             "jit(run)/while/body/copy",
             "jit(run)/while/body/sb.step/mul"]
    lines = _ops(names, paths, [
        (0, 0, 500), (1, 100, 200), (2, 500, 600), (3, 600, 650),
        (4, 650, 700), (5, 700, 740), (6, 740, 800), (7, 800, 840),
        (8, 840, 900), (9, 990, 1010)])
    pct = scopes.scope_pct(*lines)
    total = 910.0
    want = {"sb.step": 410, "sb.write": 100, "sb.read": 100, "sb.drain": 50,
            "sb.permute": 50, "sb.fill": 40, "sb.rows_split": 60,
            "sb.done": 40, scopes.UNSCOPED: 60}
    assert pct == pytest.approx({k: 100 * v / total for k, v in want.items()})
    assert scopes.split(*lines) == pytest.approx({
        "cycle_body_pct": 100 * 610 / total,
        "block_step_pct": 100 * 410 / total,
        "exchange_pct": 100 * 140 / total,
        "epoch_overhead_pct": 100 * 100 / total,
        scopes.UNSCOPED: 100 * 60 / total})


def test_unscoped_program_reads_nothing():
    """Op paths with no ``sb.*`` component (a program without the scopes)
    read as no metric, not as 0%."""
    lines = _ops(["%fusion.1"], ["jit(run)/while/body/add"], [(0, 0, 10)])
    assert scopes.scope_pct(*lines) is None
    assert scopes.split(*lines) is None


def test_profile_file_scope_paths_and_start(tmp_path):
    """A hand-built ``.xplane.pb``: the ``tf_op`` stat as a string and as a
    reference to a stat metadata name, an op without it, and the
    profile's start time; the lines are ``xplane.read``'s own."""
    space = scopes._space_class()()
    host = space.planes.add(name="/host:CPU")
    host.event_metadata[1].name = tracing.WINDOW
    st = host.stat_metadata[1]
    st.name = scopes.START_STAT
    host.stats.add(metadata_id=1, uint64_value=7_000_000)
    ln = host.lines.add(name="python", timestamp_ns=100)
    ln.events.add(metadata_id=1, offset_ps=0, duration_ps=1_000_000)
    dev = space.planes.add(name="/device:TPU:0")
    dev.stat_metadata[1].name = scopes.SCOPE_STAT
    dev.stat_metadata[2].name = "jit(run)/while/body/sb.drain/slice:"
    for k, name in ((5, "fusion.1"), (6, "copy.2"), (7, "add.3")):
        dev.event_metadata[k].name = name
    dev.event_metadata[5].stats.add(
        metadata_id=1, str_value="jit(run)/while/body/sb.step/add:")
    dev.event_metadata[6].stats.add(metadata_id=1, ref_value=2)
    ln = dev.lines.add(name=reduce.OPS, timestamp_ns=100)
    for k, off in ((5, 0), (6, 300), (7, 600)):
        ln.events.add(metadata_id=k, offset_ps=off * 1000,
                      duration_ps=300_000)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())

    lines, paths = scopes.read(str(path))
    assert [(ln.plane, ln.name, ln.names) for ln in lines] == [
        (p, n, names) for p, n, names, *_ in xplane.read(str(path))]
    assert paths == {("/device:TPU:0", reduce.OPS): [
        "jit(run)/while/body/sb.step/add:",
        "jit(run)/while/body/sb.drain/slice:", ""]}
    assert scopes.scope_pct(lines, paths) == pytest.approx(
        {"sb.step": 100 / 3, "sb.drain": 100 / 3, scopes.UNSCOPED: 100 / 3})
    assert scopes.base_ns(str(path)) == 7_000_100


def test_recorded_v5e_trace_by_scope():
    """One 64-cycle chunk of an 8x8 torus with ``wafer_64k``'s layout (2
    pods x 2x2 granules folded as batch rows, capacity 62, K 16/4) on a
    TPU v5e: 84,606 op events, their scope paths from the profiler's
    ``tf_op`` stat (cut to the ``sb.*`` components and the op; op names
    without the leading ``%``; times rounded to the nanosecond at both
    ends)."""
    lines = scopes.load_json(os.path.join(HERE, "data",
                                          "wafer8x8_v5e_trace.json"))
    pct = scopes.scope_pct(*lines)
    assert set(pct) == {"sb.read", "sb.step", "sb.write", "sb.drain",
                        "sb.permute", "sb.fill", "sb.rows_split",
                        "sb.rows_join", "sb.done", scopes.UNSCOPED}
    got = scopes.split(*lines)
    assert got == pytest.approx({
        "cycle_body_pct": 91.66720932600526,
        "block_step_pct": 0.21260899559479016,
        "exchange_pct": 3.0312259996574342,
        "epoch_overhead_pct": 0.03198730137681555,
        scopes.UNSCOPED: 5.269577372960489}, rel=1e-9)
    # the step is a part of the cycle body; the other three are disjoint
    assert got["block_step_pct"] < got["cycle_body_pct"]
    assert (got["cycle_body_pct"] + got["exchange_pct"]
            + got["epoch_overhead_pct"]
            + got[scopes.UNSCOPED]) == pytest.approx(100.0, abs=1e-9)


@pytest.mark.parametrize("trace", ["synth_trace.json", "chain_v5e_trace.json"])
def test_older_traces_carry_no_scopes(trace):
    lines, paths = scopes.load_json(os.path.join(HERE, "data", trace))
    assert paths == {}
    assert scopes.split(lines, paths) is None


# What the older readers read on the two older traces before the scope
# split was added (TraceRun: 10 cycles, 2 chips, 1e9 B/s, 4 B a cycle,
# compile 1.5 s), kept exactly.
PINNED = {
    "chain_v5e_trace.json": {
        "cycle_roofline.wafer": 0.0009343620036085061,
        "idle_pct.wafer": 99.44652394398386,
        "compile_s": 1.5,
        "top_ops": [
            ["fusion", 0.000493048],
            ["dynamic-slice_select_fusion", 0.000223551],
            ["dynamic-update-slice", 0.00019954],
            ["and_reduce_fusion", 0.000175544],
            ["copy", 0.000156947],
            ["slice", 0.00011565],
            ["constant_dynamic-slice_fusion", 2.9998000000000003e-05],
            ["copy-done", 2.9024000000000002e-05],
            ["pad_add_fusion", 2.8441e-05],
            ["broadcast_select_fusion", 2.2637e-05],
        ],
        "idle_gaps": [
            ["poll", 0.346709329],
            ["send", 0.037890643],
        ],
    },
    "synth_trace.json": {
        "cycle_roofline.wafer": 4.166666666666666,
        "idle_pct.wafer": 51.500000000000014,
        "compile_s": 1.5,
        "top_ops": [
            ["fusion", 1.55e-07],
            ["loop_fusion", 1.5000000000000002e-07],
            ["dynamic-slice", 5.5e-08],
            ["copy", 4.5000000000000006e-08],
            ["collective-permute-start", 2.5000000000000002e-08],
            ["collective-permute-done", 2.5000000000000002e-08],
            ["late-op", 5e-09],
        ],
        "idle_gaps": [
            ["run_chunk", 3.65e-07],
            ["poll", 1.3e-07],
            ["read_cycle", 2e-08],
        ],
    },
}
GAP_LABELS = {"synth_trace.json": ("run_chunk", "read_cycle", "poll"),
              "chain_v5e_trace.json": ("send", "poll")}


@pytest.mark.parametrize("trace", sorted(PINNED))
def test_older_readers_read_as_before(trace):
    lines = tracing.load_json(os.path.join(HERE, "data", trace))
    run = harness.TraceRun(lines, "cycles", 10, 2, {"hbm_bytes_per_s": 1e9},
                           4, 1.5)
    got = {m: harness.load_module("metrics", m).read(run)
           for m in ("cycle_roofline.wafer", "idle_pct.wafer", "compile_s")}
    got["top_ops"] = reduce.top_ops(lines)
    got["idle_gaps"] = reduce.idle_gaps(lines, GAP_LABELS[trace])
    assert got == PINNED[trace]


def test_recorder_spans_and_profiler_annotations_share_one_clock(tmp_path):
    """A tiny session under the profiler with the recorder on: every
    recorder span and its ``TraceAnnotation`` twin in the profiler's host
    plane start within 50 us of each other (read from the ``.xplane.pb``
    at absolute nanoseconds)."""
    import jax

    from repro.hw.pipestage import make_chain
    from repro.obs import trace as otrace

    sim = make_chain(4, capacity=4).build()
    sim.reset(0)
    sim.tx("tx").send_many([[1.0, 0.0], [2.0, 0.0]])
    sim.run(cycles=8)  # compile outside the profile
    sim.rx("rx").drain()
    rec = otrace.recorder()
    prev, n0 = rec.enabled, len(rec.events)
    rec.enabled = True
    try:
        with jax.profiler.trace(str(tmp_path)):
            sim.reset(0)
            sim.tx("tx").send_many([[3.0, 0.0], [4.0, 0.0]])
            sim.run(cycles=8)
            sim.rx("rx").drain()
            assert sim.cycle == 8
        spans = [e for e in rec.events[n0:] if e["ph"] == "X"]
    finally:
        rec.enabled = prev
        del rec.events[n0:]
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    base = scopes.base_ns(path)
    twins: dict[str, list] = {}
    for ln in tracing.load_xplane(path):
        if ln.plane.startswith("/device"):
            continue
        for i, name in enumerate(ln.names):
            if name.startswith("session."):
                twins.setdefault(name, []).extend(
                    base + ln.start[ln.ids == i])
    names = {e["name"] for e in spans}
    assert {"session.reset", "session.tx_flush", "session.run",
            "session.dispatch", "session.rx_drain", "session.read"} <= names
    for name in names:
        ours = sorted(e["ts"] * 1e3 for e in spans if e["name"] == name)
        theirs = sorted(twins.get(name, []))
        assert len(ours) == len(theirs), name
        gap = np.abs(np.array(ours) - np.array(theirs))
        print(f"clock gap {name}: {len(gap)} spans, max {gap.max():.0f} ns")
        assert gap.max() < 50e3, (name, gap.max())
