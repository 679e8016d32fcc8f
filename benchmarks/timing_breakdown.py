"""Paper Table IV: timing breakdown of the distributed run — plus the
ISSUE 7 per-phase epoch split and the procs blocking-wait fractions.

The paper splits the million-core run into launch (2m30s) / boot (1m20s) /
simulate (7m04s).  Our analogue for the distributed engine: build (trace +
compile) / setup (state init + placement) / run, on a 4-device grid.

The **phase rows** (``breakdown_phase_*``) split one wafer epoch into the
four costs the overlapped schedule rearranges — granule-local compute
(step), egress drain, the inter-device ``ppermute`` transfer, and ingress
fill — by *differencing* four compiled variants of the same epoch:

    step    = T(inner cycles only)
    drain   = T(epoch, commit dropped, permute dropped) - step
    permute = T(epoch, commit dropped)                  - (step + drain)
    fill    = T(full serial epoch)                      - (step+drain+perm)

"commit dropped" keeps a data dependence on the in-flight slab (a
runtime-zero folded into the epoch counter) so XLA cannot dead-code the
drain/permute being measured.  Negative differences are clamped: on a
2-CPU container the clamp absorbs timer noise, not real work.  The same
subprocess times the serial and overlapped full epochs, and ``bench``
closes the loop against ``repro.core.perfmodel``: fit the unhidden
residual on ONE config (``fit_overlap_residual``), scale it by the
communication-time ratio (the residual is the exchange fraction the
backend's scheduler failed to hide, so it tracks exchange volume), and
predict the OTHER config's overlapped epoch time
(``overlapped_epoch_time``) — the relative error is the
``breakdown_overlap_model`` row, gated <= 30% on the committed
trajectory file by ``benchmarks.schema``.

The **procs wait rows** run the same 2-tier free-running fleet twice —
strict serial exchanges vs the split issue/commit schedule — and report
each worker fleet's mean blocking-wait fraction (time stuck in shm-ring
pops/pushes over total run time, measured inside the workers): the
receive-late win is structural, so the fraction, unlike wall time on a
throttled container, is stable enough to gate on.

The **procs measurement rows** (ISSUE 10) come from the flight
recorder instead of differencing: each worker's per-phase wall times
(ingest / step / exchange_issue / exchange_commit / flush / epoch) ride
the shm telemetry ring to the launcher and fold into the
``procs.phase.*.s`` histograms, read here per epoch.
"""
import time

from .common import emit, run_subprocess
from repro.core import perfmodel

CODE = """
import time, numpy as np, jax
from repro.core import Simulation
from repro.core.compat import make_mesh
from repro.core.distributed import GridEngine
from repro.hw.systolic import SystolicCell, make_cell_params
rng = np.random.RandomState(0)
M, Kd, N = {dims}
A = rng.randn(M, Kd).astype(np.float32)
B = rng.randn(Kd, N).astype(np.float32)
mesh = make_mesh((2, 2), ('gr','gc'))
sim = Simulation(
    GridEngine(SystolicCell(m_stream=M), Kd, N, mesh, K=16, capacity=62))
t0 = time.perf_counter()
sim.reset(jax.random.key(0), cell_params=make_cell_params(A, B))
sim.block_until_ready()
t_setup = time.perf_counter() - t0
t0 = time.perf_counter()
sim.run(epochs=1).block_until_ready()   # includes compile
t_build = time.perf_counter() - t0
t0 = time.perf_counter()
sim.run(epochs=8).block_until_ready()
t_run = time.perf_counter() - t0
print(f'BREAKDOWN {t_build:.3f} {t_setup:.3f} {t_run:.3f}')
"""

# ---------------------------------------------- ISSUE 7: per-phase epoch split
PHASE_CODE = """
import time
import numpy as np, jax
import jax.numpy as jnp
from repro.core import ChannelGraph, Simulation, tiered_grid_partition
from repro.core.compat import make_mesh
from repro.core.distributed import GraphEngine
from repro.hw.manycore import ManycoreCell, make_core_params

R = C = {size}
EPOCHS = {epochs}
ROUNDS = {rounds}

def build(tiers, **kw):
    values = (np.arange(R * C) % 97 + 1).astype(np.float32)
    graph = ChannelGraph.torus(
        ManycoreCell(R, C), R, C,
        params=make_core_params(values.reshape(R, C)), capacity=62)
    mesh = make_mesh((2, 2), ('pod', 'gx'))
    part = tiered_grid_partition(R, C, [(2, 1), (1, 2)])
    return GraphEngine(graph, part, mesh, tiers=tiers, **kw)

def scanned(eng, body):
    # one jitted dispatch = EPOCHS epoch-shaped bodies, so this host's
    # ~ms per-call dispatch overhead amortizes out of the phase numbers
    def run(state):
        local = eng._local_view(state)
        out = jax.lax.scan(lambda s, _: (body(s), None), local, None,
                           length=EPOCHS)[0]
        return eng._global_view(out)
    return jax.jit(eng._wrap(run))

def depend_only_commit(st, t, pending):
    # anti-DCE commit: fold the in-flight counts into the epoch counter as
    # a runtime zero (counts are >= 0, so min >> 31 is 0 — but the compiler
    # cannot prove it), keeping the drain/permute alive without the
    # fill/credit work being differenced away
    if pending is None:
        return st
    _, cnt_in = pending
    dep = (jnp.min(cnt_in) >> 31).astype(st.epoch.dtype)
    return st.replace(epoch=st.epoch + dep)

def variants(tiers):
    serial = build(tiers, overlap=False)
    over = build(tiers, overlap=True)
    nofill = build(tiers, overlap=False)
    nofill._exchange_commit = depend_only_commit
    noperm = build(tiers, overlap=False)
    noperm._exchange_commit = depend_only_commit
    noperm._class_shift = lambda part, t, rev=False: part
    cpe = serial.cycles_per_epoch
    return serial, {
        'step': scanned(serial, lambda s: serial._inner_cycles(s, cpe)),
        'noperm': scanned(noperm, noperm._epoch),
        'nofill': scanned(nofill, nofill._epoch),
        'serial': scanned(serial, serial._epoch),
        'overlap': scanned(over, over._epoch),
    }

for sched, tiers in {configs}:
    eng, fns = variants(tiers)
    state = Simulation(eng).reset(jax.random.key(0)).state
    for fn in fns.values():  # compile + one shakeout call each
        jax.block_until_ready(fn(state))
        jax.block_until_ready(fn(state))
    best = {}
    keys = list(fns)
    for r in range(ROUNDS):  # order-rotated rounds, best-of (see wafer_scale)
        for k in keys[r % len(keys):] + keys[:r % len(keys)]:
            time.sleep(0.4)
            t0 = time.perf_counter()
            jax.block_until_ready(fns[k](state))
            dt = time.perf_counter() - t0
            best[k] = min(best.get(k, dt), dt)
    us = {k: v / EPOCHS * 1e6 for k, v in best.items()}
    nb = sum(int(np.prod(eng.K_tiers[:t]))
             for t in range(len(eng.tiers)) if eng.tier_classes[t])
    print(f"PHASE {sched} {nb} {us['step']:.1f} {us['noperm']:.1f} "
          f"{us['nofill']:.1f} {us['serial']:.1f} {us['overlap']:.1f}")
"""

# ------------------------------------- ISSUE 7: procs blocking-wait fraction
PROCS_CODE = """
import numpy as np
from repro.core import Simulation
from repro.core.graph import (
    ChannelGraph, PartitionTree, Tier, tiered_grid_partition)
from repro.hw.manycore import ManycoreCell, make_core_params
from repro.runtime import ProcsEngine

R = C = 8
EPOCHS = {epochs}

from repro.obs.registry import REGISTRY

PHASES = ('step', 'exchange_issue', 'exchange_commit', 'ingest', 'flush',
          'epoch')

def run_one(overlap):
    values = (np.arange(R * C) % 7 + 1).astype(np.float32)
    graph = ChannelGraph.torus(
        ManycoreCell(R, C), R, C,
        params=make_core_params(values.reshape(R, C)), capacity=8)
    part = tiered_grid_partition(R, C, [(2, 1), (2, 1)])
    ptree = PartitionTree(
        part, (Tier(axes=('pod',), K=2), Tier(axes=('g',), K=4)),
        {'pod': 2, 'g': 2})
    eng = ProcsEngine(graph, ptree, timeout=120.0, overlap=overlap)
    sim = Simulation(eng)
    sim.reset(0)
    sim.run(epochs=10)  # settle: fill the rings, warm the steppers
    sim.run(epochs=EPOCHS)
    frac = float(np.mean(
        [w['wait_fraction'] for w in eng.worker_stats(sim.state)]))
    # ISSUE 10: direct per-phase measurement through the shm telemetry
    # rings (replaces compiled-variant differencing for the procs engine)
    REGISTRY.clear()
    eng.set_tracing(True)
    sim.run(epochs=EPOCHS)
    eng.set_tracing(False)
    eng.flush_telemetry()
    snap = REGISTRY.snapshot()
    eng.close()
    # seconds per epoch: exchange phases record one sample per tier and
    # epoch, the others one per epoch
    hist = lambda p: snap.get(f'procs.phase.{p}.s', {})  # noqa: E731
    n_ep = max(hist('epoch').get('count', 0), 1)
    return frac, {p: hist(p).get('sum', 0.0) / n_ep for p in PHASES}

for mode, overlap in (('serial', False), ('overlap', True)):
    frac, means = run_one(overlap)
    print(f'PWAIT {mode} {frac:.4f}')
    print(f"PMEAS {mode} " + " ".join(
        f"{p}={means[p]:.6f}" for p in PHASES))
"""


def bench(smoke: bool = False):
    out = run_subprocess(CODE.replace("{dims}", "8, 6, 6" if smoke else "32, 16, 16"),
                         devices=4)
    for line in out.splitlines():
        if line.startswith("BREAKDOWN"):
            _, build, setup, run = line.split()
            total = float(build) + float(setup) + float(run)
            emit("breakdown_build", float(build) * 1e6,
                 f"{float(build)/total*100:.0f}% (paper launch: 23%)")
            emit("breakdown_setup", float(setup) * 1e6,
                 f"{float(setup)/total*100:.0f}% (paper boot: 12%)")
            emit("breakdown_run", float(run) * 1e6,
                 f"{float(run)/total*100:.0f}% (paper simulate: 65%)")

    # ---- per-phase epoch split + perfmodel overlap validation (ISSUE 7) ----
    # two schedules on the same wafer: fit the unhidden residual on the
    # first, predict the second (different K => different boundary count
    # and compute/communication balance)
    configs = [
        ("Ko4_Ki8", [(("pod",), 4), (("gx",), 8)]),
        ("Ko2_Ki4", [(("pod",), 2), (("gx",), 4)]),
    ]
    # 8x8 in BOTH modes: the 16x16 wafer is compute-bound on this host
    # (comm ~15% of the epoch), which starves the differencing of signal;
    # the 8x8 config is communication-heavy, which is the regime the
    # overlap model is about.  Full mode buys accuracy with longer scans
    # (64-epoch timed calls ride out CFS-throttling dips) and more rounds.
    code = (PHASE_CODE
            .replace("{size}", "8")
            .replace("{epochs}", "16" if smoke else "64")
            .replace("{rounds}", "2" if smoke else "6")
            .replace("{configs}", repr(configs)))
    phases: dict[str, tuple[int, dict[str, float]]] = {}
    for line in run_subprocess(code, devices=4, timeout=1800).splitlines():
        if not line.startswith("PHASE"):
            continue
        _, sched, nb, step, noperm, nofill, serial, overlap = line.split()
        t = dict(step=float(step), noperm=float(noperm), nofill=float(nofill),
                 serial=float(serial), overlap=float(overlap))
        phases[sched] = (int(nb), t)
        drain = max(t["noperm"] - t["step"], 0.0)
        perm = max(t["nofill"] - t["noperm"], 0.0)
        fill = max(t["serial"] - t["nofill"], 0.0)
        for phase, us in (("step", t["step"]), ("drain", drain),
                          ("permute", perm), ("fill", fill)):
            emit(f"breakdown_phase_{phase}_{sched}", us,
                 f"{us / t['serial'] * 100:.0f}% of the {t['serial']:.0f} "
                 f"us/epoch serial wafer epoch ({sched}; compiled-variant "
                 f"differencing, see module docstring)")
        emit(f"breakdown_epoch_overlap_{sched}", t["overlap"],
             f"split-exchange epoch {t['serial']:.0f} -> {t['overlap']:.0f} "
             f"us ({t['serial'] / t['overlap']:.2f}x; {nb} exchange "
             f"boundaries/epoch)")
    if len(phases) == 2:
        (nb_a, a), (nb_b, b) = (phases[s] for s, _ in configs)
        comm_a = max(a["serial"] - a["step"], 0.0)
        comm_b = max(b["serial"] - b["step"], 0.0)
        resid = perfmodel.fit_overlap_residual(a["step"], comm_a, a["overlap"])
        scaled = resid * (comm_b / comm_a if comm_a > 0.0 else 1.0)
        pred = perfmodel.overlapped_epoch_time(b["step"], comm_b, scaled)
        err = abs(pred - b["overlap"]) / b["overlap"] * 100.0
        emit("breakdown_overlap_model", err,
             f"overlap model rel err {err:.1f}%: unhidden residual "
             f"{resid:.0f} us fitted on {configs[0][0]} "
             f"({nb_a} boundaries/epoch), scaled by the comm-time ratio "
             f"{comm_b:.0f}/{comm_a:.0f}, predicts {configs[1][0]} "
             f"({nb_b} boundaries) overlapped epoch {pred:.0f} us vs "
             f"measured {b['overlap']:.0f} us")

    # ---- procs blocking-wait fraction, serial vs receive-late (ISSUE 7) ----
    pcode = PROCS_CODE.replace("{epochs}", "40" if smoke else "120")
    waits: dict[str, float] = {}
    for line in run_subprocess(pcode, devices=1, timeout=900).splitlines():
        if line.startswith("PWAIT"):
            _, mode, frac = line.split()
            waits[mode] = float(frac)
        elif line.startswith("PMEAS"):
            # ISSUE 10: telemetry-measured per-epoch phase seconds (direct
            # worker-side timing via the shm telemetry ring, NOT inferred
            # by differencing compiled variants)
            parts = line.split()
            mode = parts[1]
            means = dict(p.split("=") for p in parts[2:])
            epoch_s = float(means.get("epoch", 0.0)) or 1.0
            for phase, s in means.items():
                if phase == "epoch":
                    continue
                us = float(s) * 1e6
                emit(f"breakdown_procs_meas_{phase}_{mode}", us,
                     f"{float(s) / epoch_s * 100:.0f}% of the "
                     f"{epoch_s * 1e6:.0f} us/epoch {mode} procs epoch "
                     "(telemetry-ring measurement, per-worker mean)")
    for mode, frac in sorted(waits.items()):
        other = waits.get("serial" if mode == "overlap" else "overlap", 0.0)
        emit(f"breakdown_procs_wait_{mode}", frac * 100.0,
             f"mean worker blocking-wait fraction {frac:.3f} of run time "
             f"({mode} exchange schedule, 4-worker 2-tier 8x8 fleet"
             + (f"; vs {other:.3f} {'serial' if mode == 'overlap' else 'overlap'})"
                if other else ")"))


if __name__ == "__main__":
    bench()
