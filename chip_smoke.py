"""Bring-up run of the simulator's main path on a TPU.

    python chip_smoke.py              # one chip: phases 1-3
    python chip_smoke.py --chips 4    # four chips: the cross-chip phase only

Phases (one process; designs and host traffic come from ``--seed``):

  1. Device and setup: JAX version, devices, compile-cache directory, and
     the fused engine's resolved epoch mode and Pallas ``interpret`` flag.
     Exits non-zero before any work unless JAX sees a TPU and the fused
     engine runs natively on it.
  2. The flagship wafer (``configs.manycore.WAFER``: a 256x256 torus of
     ``ManycoreCell``, 65,536 cores) on ``engine="fused"``, its 2 pods x
     (2x2) granules folded onto one chip as batch rows, running the
     two-phase ring allreduce to completion.  Every core's total must equal
     the global sum — one equality that witnesses every packet across
     both tiers.  The run is made twice from reset: cold (compile + run)
     and warm (run only); both must stop at the same cycle.
  3. An interactive host-I/O session: a pipeline chain driven by a seeded
     ``tx.send_many`` / ``run(cycles=)`` / ``rx.drain()`` script on
     ``engine="fused"`` and on ``engine="single"``.  Received traffic,
     ``sim.cycle`` and every stage's probed state must be bit-identical.

The wafer always runs at ``WAFER``'s full 256x256 size; the phase
functions take sizes so that the tests can run them small on the CPU.

With ``--chips 4`` only the cross-chip phase runs: the wafer on a
``pod``=2 x ``g``=2 mesh (the tier exchange is a real ``ppermute``
between chips) against the same partition folded onto one chip in the
same process; final state and cycle count must be bit-identical, and the
credit slots the partition never sends on must hold what each exchange
leaves there.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``; it is printed
only when every phase passed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.manycore import WAFER  # noqa: E402
from repro.core import (  # noqa: E402
    ChannelGraph, FusedEngine, Simulation, fold_mesh, tiered_grid_partition,
)
from repro.core.compat import make_mesh  # noqa: E402
from repro.core.compile_cache import enable_compile_cache  # noqa: E402
from repro.hw.manycore import (  # noqa: E402
    ManycoreCell, allreduce_done, expected_total, make_core_params,
)
from repro.hw.pipestage import make_chain  # noqa: E402
from repro.kernels import granule_step  # noqa: E402

# Granule layouts of the wafer: axis sizes (outermost first) and the
# nested block split of ``tiered_grid_partition``.  The one-chip layout is
# the example's 2 pods x (2x2) granules; the four-chip one maps
# pod x g onto a 2x2 mesh.
ONE_CHIP = ({"pod": 2, "gr": 2, "gc": 2}, [(2, 1), (2, 2)])
FOUR_CHIP = ({"pod": 2, "g": 2}, [(2, 1), (2, 1)])


def _check(ok, what) -> None:
    """A phase's result check (kept under ``python -O``, unlike assert)."""
    if not ok:
        raise AssertionError(what)


def _done(s):
    return allreduce_done(s.block_states[0], s.tables.active[0])


def _peak_bytes(dev) -> int | None:
    stats = dev.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def run_wafer(rows: int, cols: int, *, layout=ONE_CHIP, devices=None,
              seed: int = 0, k_inner: int = WAFER.k_inner,
              k_outer: int = WAFER.k_outer,
              capacity: int = WAFER.queue_capacity) -> dict:
    """The torus allreduce on ``engine="fused"``, with ``layout``'s
    granules folded onto ``devices`` (default: all).  Runs cold, resets,
    runs warm, and checks the global-sum invariant on the warm run.
    Returns the session and the measurements."""
    axes, split = layout
    values = np.random.RandomState(seed).randint(
        1, 98, size=rows * cols).astype(np.float32)
    graph = ChannelGraph.torus(
        ManycoreCell(rows, cols), rows, cols,
        params=make_core_params(values.reshape(rows, cols)),
        capacity=capacity,
    )
    mesh, batch_axes = fold_mesh(axes, devices)
    names = list(axes)
    eng = FusedEngine(
        graph, tiered_grid_partition(rows, cols, split), mesh,
        tiers=[((names[0],), k_outer), (tuple(names[1:]), k_inner)],
        batch_axes=batch_axes,
    )
    sim = Simulation(eng)
    walls, cycles = [], []
    for _ in range(2):  # cold (compile + run), then warm (run only)
        sim.reset(jax.random.key(seed))
        t0 = time.perf_counter()
        sim.run(until=_done, cache_key="allreduce")
        sim.block_until_ready()
        walls.append(time.perf_counter() - t0)
        cycles.append(sim.cycle)
    _check(cycles[0] == cycles[1], f"cold/warm runs stopped at {cycles}")
    totals = np.asarray(eng.gather_group(sim.state, 0).total)
    want = expected_total(values)
    _check(np.array_equal(totals, np.full_like(totals, want)), (
        f"allreduce mismatch: {np.unique(totals)[:5]} != {want}"))
    return {
        "sim": sim, "cores": rows * cols, "cycles": cycles[1],
        "mesh": dict(mesh.shape), "batch_axes": batch_axes,
        "compile_s": walls[0] - walls[1], "run_s": walls[1],
    }


def run_chain_session(n_stages: int, steps: int, *, seed: int = 0,
                      devices=None) -> dict:
    """One seeded host send/run/drain script on a ``make_chain`` pipeline,
    on ``engine="fused"`` (one-device mesh, K=1) and ``engine="single"``.
    Capacity 2 makes the fused engine's registers cycle-identical to the
    single netlist's queues, so every drain must match.  Returns the
    per-engine traces."""
    dev = (jax.devices() if devices is None else devices)[:1]
    sims = {
        "fused": make_chain(n_stages, capacity=2).build(
            engine="fused", mesh=make_mesh((1,), ("gx",), devices=dev), K=1),
        "single": make_chain(n_stages, capacity=2).build(),
    }
    out = {}
    for name, sim in sims.items():
        rng = np.random.RandomState(seed)
        sim.reset(seed)
        tx, rx = sim.tx("tx"), sim.rx("rx")
        got = []
        for step in range(steps):
            k = int(rng.randint(0, 3))
            if k:
                tx.send_many(rng.randint(0, 1 << 20, size=(k, 2))
                             .astype(np.float32))
            sim.run(cycles=int(rng.randint(1, 5)))
            got.append(np.asarray(rx.drain()))
        # empty the chain: a full rx queue backpressures it, so drain
        # every cycle until everything sent has come back
        for _ in range(4 * (n_stages + tx.sent + tx.pending)):
            if rx.received == tx.sent and not tx.pending:
                break
            sim.run(cycles=1)
            got.append(np.asarray(rx.drain()))
        out[name] = {
            "rx": got, "cycle": sim.cycle, "sent": tx.sent,
            "probe": [np.asarray(sim.probe(i).count) for i in range(n_stages)],
        }
    fu, si = out["fused"], out["single"]
    _check(fu["cycle"] == si["cycle"], (fu["cycle"], si["cycle"]))
    _check(len(fu["rx"]) == len(si["rx"]), "drain counts differ")
    for i, (a, b) in enumerate(zip(fu["rx"], si["rx"])):
        _check(a.shape == b.shape and np.array_equal(a, b), f"drain {i}")
    for i, (a, b) in enumerate(zip(fu["probe"], si["probe"])):
        _check(np.array_equal(a, b), f"stage {i}: {a} != {b}")
    received = sum(len(r) for r in si["rx"])
    _check(received == si["sent"] > 0, (received, si["sent"]))
    return out


def run_wafer_across_chips(rows: int, cols: int, *, seed: int = 0,
                           devices=None) -> dict:
    """``FOUR_CHIP`` on a 4-device mesh vs the same partition folded onto
    the first device: final dynamic state and cycle count must match."""
    devs = list(jax.devices() if devices is None else devices)
    spread = run_wafer(rows, cols, layout=FOUR_CHIP, devices=devs[:4],
                       seed=seed)
    folded = run_wafer(rows, cols, layout=FOUR_CHIP, devices=devs[:1],
                       seed=seed)
    _check(spread["batch_axes"] is None and folded["mesh"] == {"device": 1},
           f"layouts: {spread['mesh']} / {folded['batch_axes']}")
    _check(spread["cycles"] == folded["cycles"],
           f"cycles {spread['cycles']} != {folded['cycles']}")
    a = jax.device_get(spread["sim"].state)
    b = jax.device_get(folded["sim"].state)
    # Credit slots the partition never sends on are dead (never read), and
    # the two credit returns leave different values there.  A ppermute
    # writes 0 where no peer sends.  The on-device batch move gathers
    # through 0-padded tables, so a dead slot reads batch row 0's returned
    # credit in its column: the value held by the live slot whose receiver
    # is row 0, or 0 where row 0 receives nothing.  Live slots come from
    # the partition's exchange tables, and the dead ones must hold exactly
    # those values, so a stray write to either is still caught.
    eng = folded["sim"].engine
    live = []
    for t, (ca, cb) in enumerate(zip(a.credits, b.credits)):
        m = np.asarray(eng._send_mask[t])  # (granule, slot)
        ca, cb = np.asarray(ca).reshape(m.shape), np.asarray(cb).reshape(
            m.shape)
        to_row0 = m & (np.asarray(eng._bat_rev[t]).reshape(m.shape) == 0)
        pad = np.broadcast_to(np.where(to_row0, cb, 0).sum(0), m.shape)
        _check(np.all(ca[~m] == 0),
               f"tier {t}: a dead credit slot on the mesh is not 0")
        _check(np.array_equal(cb[~m], pad[~m]),
               f"tier {t}: a dead credit slot on one chip is not the "
               f"padding read")
        live.append(m.reshape(np.shape(a.credits[t])))
    a = a.replace(tables=None, credits=tuple(
        np.where(m, c, 0) for m, c in zip(live, a.credits)))
    b = b.replace(tables=None, credits=tuple(
        np.where(m, c, 0) for m, c in zip(live, b.credits)))
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(a),
                            jax.tree.leaves(b)):
        _check(np.array_equal(np.asarray(x), np.asarray(y)),
               f"state leaf {jax.tree_util.keystr(path)} differs")
    per_dev: dict[str, int] = {}
    for leaf in jax.tree.leaves(spread["sim"].state):
        for shard in leaf.addressable_shards:
            key = str(shard.device)
            per_dev[key] = per_dev.get(key, 0) + shard.data.nbytes
    return {"spread": spread, "folded": folded, "bytes_per_device": per_dev}


def _fmt(r: dict) -> str:
    return (f"{r['cores']} cores, {r['cycles']} simulated cycles, "
            f"compile {r['compile_s']:.3f} s (cold - warm), "
            f"run {r['run_s']:.3f} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # ---- phase 1: device and setup
    devs = jax.devices()
    mode = granule_step.resolve_mode("auto")
    interpret = granule_step.resolve_interpret("auto")
    print(f"jax {jax.__version__}; devices {devs}")
    print(f"device_kind {devs[0].device_kind!r}; fused epoch mode {mode!r}, "
          f"interpret {interpret}")
    if devs[0].platform != "tpu":
        print(f"no TPU: JAX sees platform {devs[0].platform!r}",
              file=sys.stderr)
        return 2
    if interpret:
        print("the fused engine would run in interpret mode", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, JAX sees "
              f"{len(devs)}", file=sys.stderr)
        return 2
    cdir = enable_compile_cache()
    print(f"compile cache {cdir} ({len(os.listdir(cdir))} entries before "
          f"this run)")

    if args.chips == 4:
        r = run_wafer_across_chips(WAFER.grid_rows, WAFER.grid_cols,
                                   seed=args.seed, devices=devs)
        print(f"[4 chips] mesh {r['spread']['mesh']}: {_fmt(r['spread'])}")
        print(f"[1 chip ] batch {r['folded']['batch_axes']}: "
              f"{_fmt(r['folded'])}")
        print(f"final state and cycle count bit-identical; bytes per device "
              f"{r['bytes_per_device']}")
    else:
        # ---- phase 2: the wafer allreduce on one chip
        r = run_wafer(WAFER.grid_rows, WAFER.grid_cols, devices=devs[:1],
                      seed=args.seed)
        print(f"[wafer] batch {r['batch_axes']}: {_fmt(r)}; peak device "
              f"bytes {_peak_bytes(devs[0])}")
        # ---- phase 3: interactive host I/O, fused vs single
        t0 = time.perf_counter()
        s = run_chain_session(64, 200, seed=args.seed, devices=devs)
        print(f"[host-io] 64-stage chain, {s['single']['sent']} packets "
              f"round-tripped, cycle {s['single']['cycle']}, fused == single "
              f"bit-identical ({time.perf_counter() - t0:.3f} s)")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
