"""The four-chip wafer cell (``wafer256k.allreduce``) on four CPU devices
at a 32x32 torus in the cell's own layout, and its collective reader on
hand-built traces.

One subprocess (four devices must be forced before JAX starts) runs the
harness's window and traced runs on the 4-device mesh, runs the same jobs
through the cell's driver on the mesh and folded onto one device, and
reads the exchange counters after each build; the tests below check what
it reports."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from chipbench import harness, tracing
from chipbench.metrics import reduce

WORKLOAD = "wafer256k.allreduce"
TINY = {"grid_rows": 32, "grid_cols": 32}
SEED = 2**31 + 11


def _tiny_cell() -> harness.Cell:
    cell = harness.Cell.load(WORKLOAD)
    cell.cfg.update(TINY)
    return cell


def _jobs(cell, devices, chunks: int) -> list:
    """``chunks`` chunks of back-to-back jobs and the open one run to its
    end, through the cell's driver: every job's cycles and final cores."""
    design = harness.load_module("designs", cell.cfg["design"]).build(
        cell.cfg, devices)
    drv = harness.load_module("drivers", cell.traffic["driver"]).Driver(
        design, cell.traffic,
        harness.load_module("reference", cell.cfg["reference"]), SEED)
    drv.window(chunks=chunks)
    drv.finish()
    exch = {k: v for k, v in design.sim.stats()["metrics"].items()
            if k.startswith("exchange.")}
    drv.collect()
    return drv.jobs, exch


def _on_four_devices() -> dict:
    """What the tests check, run in a process with four CPU devices."""
    import jax

    harness.peak_for = lambda kind: {"hbm_bytes_per_s": 1e11}
    cell = _tiny_cell()
    devs = jax.devices()
    assert len(devs) == 4, devs
    out = {}
    for name, trace in (("window", False), ("traced", True)):
        line, _ = harness.run_cell(cell, SEED, 1.0, trace, devs,
                                   time.perf_counter())
        out[name] = line
    # a job is 2 (R + C) + 192 = 320 cycles: five 64-cycle chunks and a
    # sixth that finds it done.  14 chunks: two whole jobs, and the third
    # run to its end after the window
    spread, out["counters_mesh"] = _jobs(cell, devs, 14)
    folded, out["counters_folded"] = _jobs(cell, devs[:1], 14)
    out["job_cycles"] = [[j["cycles"] for j in jobs]
                         for jobs in (spread, folded)]
    out["differing_fields"] = sorted({
        f for a, b in zip(spread, folded) for f in a["cores"]
        if not np.array_equal(a["cores"][f], b["cores"][f])})
    return out


@pytest.fixture(scope="module")
def four():
    path = [harness.ROOT, os.path.join(harness.ROOT, "src")]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   path + [os.environ.get("PYTHONPATH", "")]))
    res = subprocess.run([sys.executable, __file__], capture_output=True,
                         text=True, env=env, timeout=600,
                         cwd=harness.ROOT)
    assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_window_run_on_four_devices(four):
    line = four["window"]
    cell = harness.Cell.load(WORKLOAD)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in line["checks"].values())
    assert sorted(line["metrics"]) == sorted(m["name"]
                                             for m in cell.end_to_end)
    assert line["device"]["count"] == 4


def test_traced_run_on_four_devices(four):
    line = four["traced"]
    cell = harness.Cell.load(WORKLOAD)
    assert line["correct"] is True
    assert all(c["value"] == 0 for c in line["checks"].values())
    # the CPU has no device planes: only the metrics that read no device
    # trace are there, and every one the cell names for the host clock is
    assert set(line["metrics"]) == {m["name"] for m in cell.per_layer
                                    if m["source"] != "device_trace"}
    assert "collective_pct.wafer" in {m["name"] for m in cell.per_layer}


def test_four_devices_match_the_fold_bit_for_bit(four):
    spread, folded = four["job_cycles"]
    assert spread == folded == [320] * 3
    assert four["differing_fields"] == []


def _channels(cfg: dict) -> tuple[list[int], list[int]]:
    """Per tier, the boundary channels (decided as ``boundary_channels``
    of the design decides them), all of them and those whose two ends lie
    on different devices of the cell's 4-device mesh: the device is the
    (pod, gr) granule pair, since ``gc`` folds onto the device as batch
    rows."""
    design = harness.load_module("designs", "torus_allreduce")
    R, C = int(cfg["grid_rows"]), int(cfg["grid_cols"])
    tiles = [tuple(t) for t in cfg["layout"]["tiles"]]
    ids = design._block_ids(R, C, tiles)
    device = ids[-1] // tiles[-1][1]
    rr, cc = np.divmod(np.arange(R * C), C)
    every, across = [0] * len(tiles), [0] * len(tiles)
    for dst in (rr * C + (cc + 1) % C, ((rr + 1) % R) * C + cc):
        decided = np.zeros(R * C, bool)
        for t, gid in enumerate(ids):
            cross = (gid != gid[dst]) & ~decided
            every[t] += int(cross.sum())
            across[t] += int((cross & (device != device[dst])).sum())
            decided |= cross
    return every, across


def test_exchange_counters_match_the_cross_device_channels(four):
    cfg = _tiny_cell().cfg
    every, chans = _channels(cfg)
    design = harness.load_module("designs", "torus_allreduce")
    assert every == design.boundary_channels(cfg)
    assert all(chans)  # both tiers cross chips on this layout
    k = (int(cfg["k_inner"]) * int(cfg["k_outer"]), int(cfg["k_inner"]))
    mesh, folded = four["counters_mesh"], four["counters_folded"]
    for t, (n, period) in enumerate(zip(chans, k)):
        depth = min(period, int(cfg["queue_capacity"]) - 1)
        slot = depth * int(cfg["payload_words"]) * 4 + 4 + 4
        assert mesh[f"exchange.ici_bytes.{t}"] == n * slot, t
        assert folded[f"exchange.ici_bytes.{t}"] == 0
        assert folded[f"exchange.ici_permutes.{t}"] == 0
    # the pod tier crosses on two shifts (down, and the torus wrap up),
    # the gr tier on one; three ppermutes each: slab, count, credit
    assert mesh["exchange.ici_permutes.0"] == 6
    assert mesh["exchange.ici_permutes.1"] == 3


# ---- the collective reader on hand-built traces (window [0, 1000] ns)

OPS = ["%collective-permute-start.3 = (f32[8]{0}) collective-permute-start"
       "(f32[8]{0} %p.3)",
       "%collective-permute-done.3 = f32[8]{0} collective-permute-done("
       "(f32[8]{0}) %s.3)",
       "%all-reduce.7 = s32[] all-reduce(s32[] %x), to_apply=%add",
       "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %collective-permute-done.9)",
       "%copy.2 = f32[8]{0} copy(f32[8]{0} %p.4)",
       # the done psum, as a TPU profile names it: kind "psum", opcode
       # all-reduce
       "%psum.18 = s32[]{:T(128)} all-reduce(s32[]{:T(128)S(6)} %sub.154), "
       "channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%region_1.0"]


def _trace(*devices):
    """A host window span [0, 1000] and one TPU plane per entry of
    ``devices``, each a list of (op index, start, end) ``XLA Ops``
    events."""
    lines = [tracing.Line("/host:CPU", "python", [tracing.WINDOW],
                          np.zeros(1, np.int32), np.zeros(1),
                          np.full(1, 1000.0))]
    for i, events in enumerate(devices):
        ids, st, en = (np.array(x, float) for x in zip(*events))
        lines.append(tracing.Line(f"/device:TPU:{i}", reduce.OPS, OPS,
                                  ids.astype(np.int32), st, en))
    return lines


def _read(lines):
    run = harness.TraceRun(lines, "cycles", 64, 2,
                           {"hbm_bytes_per_s": 1e9}, None, 1.0)
    return harness.load_module("metrics", "collective_pct.wafer").read(run)


def test_collective_share_is_the_union_of_collective_ops():
    """Worked by hand.  Device 0: a permute start [100, 150], its done
    [140, 200] (overlapping: union [100, 200]), an all-reduce [500, 520]
    and one starting before the window ([-30, 10], counts 10): 130 ns;
    the fusion that reads a permute's result and the copy do not count.
    Device 1: a done running past the window ([980, 1100], counts 20), an
    all-reduce [300, 350] and a psum named by its op [600, 640]: 110 ns.
    Mean share (130 + 110) / 2 / 1000."""
    lines = _trace(
        [(0, 100, 150), (1, 140, 200), (2, 500, 520), (2, -30, 10),
         (3, 200, 500), (4, 600, 700)],
        [(3, 0, 300), (2, 300, 350), (4, 400, 600), (5, 600, 640),
         (4, 640, 900), (1, 980, 1100)])
    assert _read(lines) == pytest.approx(100.0 * (130 + 110) / 2 / 1000)


def test_collective_share_is_zero_without_collectives():
    lines = _trace([(3, 0, 400), (4, 500, 600)], [(4, 0, 1000)])
    assert _read(lines) == 0.0


def test_collective_share_is_none_on_one_chip():
    assert _read(_trace([(0, 100, 150), (1, 140, 200)])) is None


if __name__ == "__main__":
    print(json.dumps(_on_four_devices()))
