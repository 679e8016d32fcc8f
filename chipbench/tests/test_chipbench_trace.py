"""The trace reductions on a synthesized two-chip trace whose every
number was worked out by hand (``data/synth_trace.json``), and on a trace
recorded on a TPU v5e (a host-driven 64-stage chain session); and the
profiler file read back to the picosecond."""
import os

import numpy as np
import pytest

from chipbench import harness, tracing
from chipbench.metrics import reduce

HERE = os.path.dirname(os.path.abspath(__file__))

NS = 1e-9


@pytest.fixture(scope="module")
def synth():
    return tracing.load_json(os.path.join(HERE, "data", "synth_trace.json"))


def _run(lines, unit, count, bytes_per_cycle=None):
    return harness.TraceRun(lines, unit, count, 2, {"hbm_bytes_per_s": 1e9},
                            bytes_per_cycle, 1.5)


def test_busy_idle_and_window(synth):
    # device 0 busy [10,290] [330,690] [720,760] [990,1000] = 690 ns (an op
    # running past the window is clipped); device 1 busy [10,290] = 280 ns
    assert reduce.window(synth) == (0.0, 1000.0)
    assert reduce.window_s(synth) == pytest.approx(1000 * NS)
    assert reduce.busy_s(synth) == pytest.approx((690 + 280) / 2 * NS)
    assert reduce.idle_pct(synth) == pytest.approx(
        100 * (1 - (690 + 280) / 2 / 1000))


def test_module_time(synth):
    assert reduce.module_seconds(synth) == pytest.approx((680 + 280) / 2 * NS)


def test_top_ops_by_self_time(synth):
    got = dict(reduce.top_ops(synth))
    # summed by kind: the instruction name less its number
    want = {"fusion": (120 + 190) / 2, "loop_fusion": 300 / 2,
            "dynamic-slice": (70 + 40) / 2, "copy": (50 + 40) / 2,
            "collective-permute-start": 50 / 2,
            "collective-permute-done": 50 / 2, "late-op": 10 / 2}
    assert set(got) == set(want)
    for k, v in want.items():
        assert got[k] == pytest.approx(v * NS), k
    assert reduce.top_ops(synth)[0][0] == "fusion"


def test_idle_gaps_go_to_the_host_span_that_covers_them_most(synth):
    got = dict(reduce.idle_gaps(synth, ("run_chunk", "read_cycle", "poll")))
    assert got == pytest.approx({"run_chunk": (10 + 720) / 2 * NS,
                                 "poll": 260 / 2 * NS,
                                 "read_cycle": 40 / 2 * NS})
    idle = reduce.window_s(synth) - reduce.busy_s(synth)
    assert sum(got.values()) == pytest.approx(idle)


def test_union_and_covered():
    s, e = reduce.union(np.array([5., 0., 2., 20.]),
                        np.array([8., 3., 4., 25.]))
    assert s.tolist() == [0., 5., 20.] and e.tolist() == [4., 8., 25.]
    cov = reduce.covered(s, e, np.array([0., 3., 9.]), np.array([30., 6., 21.]))
    assert cov.tolist() == [12., 2., 1.]


def test_per_layer_readers(synth):
    read = {n: harness.load_module("metrics", n).read for n in (
        "cycle_roofline.wafer", "idle_pct.wafer", "compile_s")}
    # least time 48 B / (2 chips * 1e9 B/s) = 24 ns; 480 ns over 10 cycles
    cyc = _run(synth, "cycles", 10, bytes_per_cycle=48)
    assert read["cycle_roofline.wafer"](cyc) == pytest.approx(50.0)
    assert read["cycle_roofline.wafer"](_run(synth, "txns", 4)) is None
    assert read["cycle_roofline.wafer"](_run(synth, "cycles", 10)) is None
    assert read["idle_pct.wafer"](cyc) == pytest.approx(51.5)
    assert read["compile_s"](cyc) == 1.5


def test_roofline_share_over_100_is_an_error(synth):
    read = harness.load_module("metrics", "cycle_roofline.wafer").read
    with pytest.raises(ValueError, match="exceeds 100%"):
        read(_run(synth, "cycles", 10, bytes_per_cycle=200))


def test_trace_without_devices_reads_nothing(synth):
    host = [ln for ln in synth if not ln.plane.startswith("/device")]
    assert reduce.busy_s(host) is None
    assert reduce.module_seconds(host) is None
    assert reduce.top_ops(host) == [] and reduce.idle_gaps(host, ()) == []


def test_recorded_v5e_trace():
    """A trace of one 4-packet transaction through a 64-stage chain
    between host ports on a TPU v5e (cut to that transaction; op names
    shortened)."""
    lines = tracing.load_json(os.path.join(HERE, "data",
                                           "chain_v5e_trace.json"))
    assert 90.0 < reduce.idle_pct(lines) < 100.0
    assert 0.0 < reduce.busy_s(lines) < reduce.window_s(lines)
    kinds = dict(reduce.top_ops(lines))
    assert {"fusion", "dynamic-update-slice", "copy"} <= set(kinds)
    gaps = dict(reduce.idle_gaps(lines, ("send", "poll")))
    assert set(gaps) <= {"poll", "send", "host, outside any span"}
    assert gaps["poll"] > gaps["send"] > 0


def test_xplane_keeps_sub_nanosecond_times(tmp_path):
    """Events on a line stamped at today's wall clock (~1.8e18 ns), a
    nanosecond apart and half a nanosecond long, keep their times: they
    are taken from the line's start, not as absolute float nanoseconds
    (whose step there is 256 ns)."""
    from chipbench import xplane

    space = xplane._space_class()()
    plane = space.planes.add(name="/device:TPU:0")
    plane.event_metadata[7].name = "fusion.1"
    plane.event_metadata[9].name = "copy.2"
    line = plane.lines.add(name="XLA Ops",
                           timestamp_ns=1_790_000_000_000_000_000)
    for i in range(4):
        line.events.add(metadata_id=(7, 9)[i % 2], offset_ps=1000 * i,
                        duration_ps=500)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    (ln,) = tracing.load_xplane(str(path))
    assert (ln.plane, ln.name) == ("/device:TPU:0", "XLA Ops")
    assert ln.start.tolist() == [0.0, 1.0, 2.0, 3.0]
    assert (ln.end - ln.start).tolist() == [0.5] * 4
    assert [ln.names[i] for i in ln.ids] == ["fusion.1", "copy.2"] * 2
