"""Flight recorder (ISSUE 10; DESIGN.md §Observability).

Covered here:

  * metrics registry units: dotted-name validation, kind collisions,
    the disabled fast path, histogram summaries, snapshot ordering;
  * the shm telemetry ring property test: random emit/drain
    interleavings against ``core/queue.py``'s QueueArray — both accept
    and refuse pushes identically, and the ring's record payloads come
    back FIFO;
  * ``TelemetryWriter`` drop accounting (non-blocking emit into a full
    ring drops + counts, never waits);
  * ``records_to_events`` folding drained records into recorder spans
    and registry histograms;
  * trace recorder units: span/instant/track metadata, the bounded
    buffer, Chrome-format export validated by ``obs.schema``;
  * ``validate_stats``/``validate_trace`` accept the real thing and
    reject malformed layouts;
  * every engine family's ``stats()`` passes the ONE schema;
  * tracing is observation-only: traced vs untraced host traffic is
    bit-identical on the in-process engines AND a 4-worker procs fleet
    (whose trace carries per-worker ingest/step/exchange/flush spans);
  * a kill drill under ``sim.trace`` leaves a ``recovery_incident``
    instant (with incarnation tag) in the exported timeline;
  * a 2-host bridged fleet reports ``connect_s`` separately from the
    steady-state ``wait_fraction`` (the cold-start dilution bugfix);
  * ``run(until=...)`` emits ``session.dispatch`` and counts
    ``session.cycles``;
  * every ``sb.*`` device scope is in the op metadata of the fused
    engine's ``run_until`` program on a wafer-layout torus;
  * ``obs.report`` renders phase breakdown / stragglers / incidents.
"""
import json
import os
import re

import jax
import numpy as np
import pytest

from repro.core import queue as qmod
from repro.obs import report as oreport, schema as oschema, telemetry
from repro.obs import trace as otrace
from repro.obs.registry import REGISTRY, MetricsRegistry
from repro.obs.trace import TID_SESSION, TraceRecorder
from repro.runtime import ShmRing

from test_session import Increment, build_chain, io_script, _sessions_k1

_TIMEOUT = 60.0  # generous: 2-CPU CI boxes timeshare the workers


def procs_build(net, **kw):
    kw.setdefault("timeout", _TIMEOUT)
    return net.build(engine="procs", **kw)


@pytest.fixture
def closing():
    sims = []
    yield sims.append
    for sim in sims:
        try:
            sim.engine.close()
        except Exception:
            pass


# ------------------------------------------------------- metrics registry
def test_registry_kinds_and_snapshot():
    reg = MetricsRegistry()
    reg.inc("a.b.count")
    reg.inc("a.b.count", 2.0)
    reg.set("a.b.gauge", 7.5)
    for v in (1.0, 3.0, 2.0):
        reg.observe("a.b.hist", v)
    snap = reg.snapshot()
    assert list(snap) == sorted(snap)  # stable, sorted export
    assert snap["a.b.count"] == 3.0
    assert snap["a.b.gauge"] == 7.5
    h = snap["a.b.hist"]
    assert h == {"count": 3, "sum": 6.0, "mean": 2.0, "min": 1.0, "max": 3.0}
    reg.clear()
    assert reg.snapshot() == {}


def test_registry_name_and_kind_errors():
    reg = MetricsRegistry()
    for bad in ("nodots", "Upper.case", "trailing.", ".leading", "a b.c"):
        with pytest.raises(ValueError):
            reg.inc(bad)
    reg.inc("x.count")
    with pytest.raises(TypeError):
        reg.set("x.count", 1.0)  # counter already, not a gauge
    with pytest.raises(TypeError):
        reg.observe("x.count", 1.0)


def test_registry_disabled_fast_path():
    """Disabled publishing must not even *create* metrics — the ≤1.02x
    tracing-off budget rides on this early return."""
    reg = MetricsRegistry(enabled=False)
    reg.inc("a.b")
    reg.set("a.c", 1.0)
    reg.observe("a.d", 1.0)
    assert reg.snapshot() == {}
    reg.inc("NOT A VALID NAME")  # not validated either: never reached


# ------------------------------------- telemetry ring vs queue.py semantics
def _ring(cap, tag):
    return ShmRing.create(f"t_obs_{os.getpid()}_{tag}", cap,
                          telemetry.TELEM_RECORD_BYTES)


@pytest.mark.parametrize("seed", range(6))
def test_telemetry_ring_matches_queue_semantics(seed):
    """Random emit/drain interleavings: the telemetry ring accepts and
    refuses 48-byte records exactly like the paper's credit-free queue
    at the same capacity, and drained payloads come back FIFO."""
    cap = 4
    rng = np.random.RandomState(seed)
    ring = _ring(cap, f"prop{seed}")
    try:
        q = qmod.make_queues(1, 6, cap)
        expect = []  # FIFO model of what the ring holds
        for i in range(60):
            do_push, do_pop = bool(rng.randint(2)), bool(rng.randint(2))
            assert ring.size() == int(qmod.size(q)[0])
            assert ring.free() == int(qmod.free(q)[0])
            assert ring.empty() == bool(qmod.empty(q)[0])
            assert ring.full() == bool(qmod.full(q)[0])
            if do_pop:
                rec = ring.pop_record()
                front, tail, valid = qmod.pop_single(
                    q.buf[0], q.head[0], q.tail[0], cap)
                q = q.replace(tail=q.tail.at[0].set(tail))
                assert (rec is not None) == bool(valid)
                if rec is not None:
                    row = telemetry._PACK.unpack(rec)
                    assert row == expect.pop(0)
            if do_push:
                row = (telemetry.TEV_STEP, float(i), 0.5 * i, 0.001, 0.0, 0.0)
                ok_ring = ring.push_record(telemetry._PACK.pack(*row))
                buf, head, ok = qmod.push_single(
                    q.buf[0], q.head[0], q.tail[0], cap,
                    np.full((6,), float(i), np.float32))
                q = q.replace(buf=q.buf.at[0].set(buf),
                              head=q.head.at[0].set(head))
                assert ok_ring == bool(ok)
                if ok_ring:
                    expect.append(row)
        drained = telemetry.drain(ring)
        np.testing.assert_array_equal(
            drained, np.asarray(expect, np.float64).reshape(-1, 6))
    finally:
        ring.close()


def test_telemetry_writer_drops_when_full():
    cap = 8  # SPSC ring holds cap-1 records
    ring = _ring(cap, "drop")
    try:
        w = telemetry.TelemetryWriter(ring)
        for i in range(cap + 3):
            w.emit(telemetry.TEV_EPOCH, float(i), 0.0, 0.0)
        assert w.emitted == cap - 1
        assert w.dropped == 4
        assert telemetry.drain(ring).shape == (cap - 1, 6)
        assert telemetry.drain(ring).shape == (0, 6)  # drained dry
    finally:
        ring.close()


def test_records_to_events_folds_spans_and_histograms():
    rec = TraceRecorder()
    rec.enabled = True
    reg = MetricsRegistry()
    rows = np.asarray([  # ts, dur in ns; the epoch's wait (v0) in s
        [telemetry.TEV_STEP, 32.0, 1.0e9, 10e6, 0.0, 0.0],
        [telemetry.TEV_ISSUE, 2.0, 1.011e9, 2e6, 0.0, 0.0],
        [telemetry.TEV_EPOCH, 5.0, 1.0e9, 15e6, 0.004, 0.0],
        [telemetry.TEV_OCC, 0.0, 1.016e9, 0.0, 3.0, 2.0],
    ], np.float64)
    n = telemetry.records_to_events(rows, worker=3, pid=0,
                                    recorder=rec, registry=reg)
    assert n == 4
    names = [(e["name"], e["tid"]) for e in rec.events]
    assert names == [("step", 3), ("exchange_issue", 3), ("epoch", 3)]
    assert rec.events[0]["args"] == {"cycles": 32}
    assert rec.events[1]["args"] == {"tier": 2}
    assert rec.events[2]["args"] == {"epoch": 5, "wait_s": 0.004}
    assert (rec.events[0]["ts"], rec.events[0]["dur"]) == (1e6, 1e4)  # us
    snap = reg.snapshot()
    assert snap["procs.phase.step.s"]["count"] == 1
    assert snap["procs.worker.3.epoch.s"]["sum"] == pytest.approx(0.015)
    assert snap["procs.worker.3.wait.s"]["sum"] == pytest.approx(0.004)
    assert snap["procs.ring.occupancy"]["max"] == 3.0


# --------------------------------------------------------- trace recorder
def test_trace_recorder_export_is_valid_perfetto(tmp_path):
    rec = TraceRecorder()
    rec.span("ignored", 0, 1)  # disabled: no-op
    assert rec.events == []
    rec.enabled = True
    rec.set_process(0, "procs:local")
    rec.set_track(0, 0, "worker 0")
    rec.set_track(0, TID_SESSION, "session")
    rec.span("step", 1_000_000_000, 500_000_000, pid=0, tid=0,
             cat="worker")
    rec.span("session.dispatch", 2_000_000_000, 1_000, cat="session")
    rec.instant("recovery_incident", cat="recovery", args={"incarnation": 1})
    path = str(tmp_path / "t.json")
    rec.export(path)
    doc = oschema.validate_trace_file(path)
    evs = doc["traceEvents"]
    metas = [e for e in evs if e["ph"] == "M"]
    assert {(m["name"], m["args"]["name"]) for m in metas} == {
        ("process_name", "procs:local"), ("thread_name", "worker 0"),
        ("thread_name", "session")}
    span = next(e for e in evs if e["name"] == "step")
    assert span["ts"] == 1e6 and span["dur"] == 0.5e6  # ns -> µs
    assert any(e["ph"] == "i" and e["name"] == "recovery_incident"
               for e in evs)
    assert doc["otherData"]["dropped"] == 0


def test_trace_recorder_bounded_buffer():
    rec = TraceRecorder(max_events=5)
    rec.enabled = True
    for i in range(9):
        rec.span(f"s{i}", i, 100)
    assert len(rec.events) == 5
    assert rec.dropped == 4
    rec.clear()
    assert rec.events == [] and rec.dropped == 0


def test_validate_trace_rejects_malformed():
    ok = {"traceEvents": [{"name": "a", "ph": "X", "ts": 0, "dur": 1,
                           "pid": 0, "tid": 0}]}
    oschema.validate_trace(ok)
    for bad in (
        {"traceEvents": [{"name": "a", "ph": "Z", "ts": 0,
                          "pid": 0, "tid": 0}]},      # unknown phase
        {"traceEvents": [{"name": "a", "ph": "X", "ts": 0,
                          "pid": 0, "tid": 0}]},      # span without dur
        {"traceEvents": [{"ph": "i", "ts": 0, "pid": 0, "tid": 0}]},
        {"notTraceEvents": []},
    ):
        with pytest.raises(ValueError):
            oschema.validate_trace(bad)


# ----------------------------------------------------------- stats schema
def test_validate_stats_rejects_malformed():
    good = {"schema": oschema.STATS_SCHEMA, "engine": "single",
            "cycle": 0, "epoch": 0,
            "ports": {"tx": {"tx": {"sent": 0, "pending": 0,
                                    "occupancy": 0, "credit": 0}},
                      "rx": {"rx": {"received": 0, "occupancy": 0,
                                    "credit": 0}}}}
    oschema.validate_stats(good)
    bad_engine = dict(good, engine="warp")
    with pytest.raises(ValueError):
        oschema.validate_stats(bad_engine)
    with pytest.raises(ValueError):
        oschema.validate_stats(dict(good, bogus=1))
    with pytest.raises(ValueError):
        oschema.validate_stats({k: v for k, v in good.items()
                                if k != "ports"})
    broken_tx = json.loads(json.dumps(good))
    del broken_tx["ports"]["tx"]["tx"]["credit"]
    with pytest.raises(ValueError):
        oschema.validate_stats(broken_tx)
    with pytest.raises(ValueError):
        oschema.validate_stats(dict(good, bridges=[{"link": 0}]))


def test_stats_schema_every_engine(closing):
    """The ONE stats layout, engine-independent: single/graph/fused via
    the K=1 chain sessions, procs via a 2-worker fleet."""
    sims = dict(_sessions_k1())
    sims["procs"] = procs_build(build_chain(capacity=2), n_workers=2,
                                partition=[0, 1, 1], K=1)
    closing(sims["procs"])
    for name, sim in sims.items():
        sim.reset(0)
        sim.tx("tx").send_many([[1.0, 0.0], [2.0, 0.0]])
        sim.run(cycles=3)
        sim.rx("rx")
        st = oschema.validate_stats(sim.stats())
        assert st["engine"] == name
        assert "metrics" in st, name
        if name == "single":
            assert set(st["detail"]) == {"push_count", "pop_count"}


# ---------------------------------------- tracing is observation-only
def test_traced_bit_identical_in_process(tmp_path):
    """single/graph/fused: the io_script traffic is bit-identical with
    the flight recorder on vs off."""
    ref = {}
    for name, sim in _sessions_k1().items():
        sim.reset(0)
        ref[name] = io_script(sim, n_steps=12)
    for name, sim in _sessions_k1().items():
        sim.reset(0)
        with sim.trace(str(tmp_path / f"{name}.json")):
            got = io_script(sim, n_steps=12)
        for step, (a, b) in enumerate(zip(ref[name], got)):
            np.testing.assert_array_equal(a, b,
                                          err_msg=f"{name} boundary {step}")
        doc = oschema.validate_trace_file(str(tmp_path / f"{name}.json"))
        assert any(e["name"] == "session.dispatch"
                   for e in doc["traceEvents"])


def test_procs_trace_per_worker_spans_bit_identical(closing, tmp_path):
    """4-worker fleet: sim.trace() yields a Perfetto-valid timeline with
    one track per worker carrying the full phase taxonomy, while the
    host-visible traffic stays bit-identical to an untraced run."""
    path = str(tmp_path / "procs.json")
    sim = procs_build(build_chain(4, capacity=2), n_workers=4,
                      partition=[0, 1, 2, 3], K=2)
    closing(sim)
    sim.reset(0)
    with sim.trace(path):
        got = io_script(sim, n_steps=12)
    st = oschema.validate_stats(sim.stats())
    assert st["metrics"]["procs.phase.epoch.s"]["count"] > 0
    sim.engine.close()

    doc = oschema.validate_trace_file(path)
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    worker_tids = {e["tid"] for e in spans if e.get("cat") == "worker"}
    assert worker_tids == {0, 1, 2, 3}
    names = {e["name"] for e in spans if e.get("cat") == "worker"}
    assert {"ingest", "step", "exchange_issue", "exchange_commit",
            "flush", "epoch"} <= names
    text = oreport.summarize(doc)
    assert "phase breakdown" in text and "straggler" in text

    sim2 = procs_build(build_chain(4, capacity=2), n_workers=4,
                       partition=[0, 1, 2, 3], K=2)
    closing(sim2)
    sim2.reset(0)
    got2 = io_script(sim2, n_steps=12)
    assert len(got) == len(got2)
    for step, (a, b) in enumerate(zip(got, got2)):
        np.testing.assert_array_equal(a, b, err_msg=f"boundary {step}")


def test_recovery_incident_lands_in_trace(closing, tmp_path):
    """Kill drill under the recorder: the healed fleet's timeline holds
    the recovery_incident instant tagged with the new incarnation."""
    path = str(tmp_path / "drill.json")
    sim = procs_build(build_chain(3, capacity=4), n_workers=2,
                      partition=[0, 0, 1], K=1, on_fault="recover",
                      snapshot_every=2, backoff_s=0.0, fault_plan="kill:1@3")
    closing(sim)
    sim.reset(0)
    with sim.trace(path):
        io_script(sim, n_steps=8, seed=1)
    st = sim.stats()
    assert st["faults"]["restarts"] == 1
    assert st["metrics"]["recovery.restarts"] >= 1.0

    doc = oschema.validate_trace_file(path)
    incidents = [e for e in doc["traceEvents"]
                 if e.get("ph") == "i" and e["name"] == "recovery_incident"]
    assert len(incidents) == 1
    assert incidents[0]["args"]["incarnation"] == 1
    assert incidents[0]["args"]["fault"] == "WorkerDiedError"
    assert any(e["name"] == "snapshot" for e in doc["traceEvents"]
               if e.get("ph") == "X")
    text = oreport.summarize(doc)
    assert "recovery_incident" in text


def test_bridged_fleet_connect_vs_wait(closing, tmp_path):
    """2-host fleet: stats separate the one-time rendezvous cost
    (connect_s) from the steady-state pump wait_fraction, and traced
    traffic stays bit-identical."""
    ref = procs_build(build_chain(3, capacity=4), n_workers=2,
                      partition=[0, 0, 1], K=1)
    closing(ref)
    ref.reset(0)
    want = io_script(ref, n_steps=8)
    ref.engine.close()

    path = str(tmp_path / "fleet.json")
    sim = procs_build(build_chain(3, capacity=4), n_workers=2,
                      partition=[0, 0, 1], K=1, hosts=2)
    closing(sim)
    sim.reset(0)
    with sim.trace(path):
        got = io_script(sim, n_steps=8)
    st = oschema.validate_stats(sim.stats())
    assert st["bridges"], "2-host fleet must report bridge rows"
    for row in st["bridges"]:
        assert row["connect_s"] >= 0.0
        assert 0.0 <= row["wait_fraction"] <= 1.0
    doc = oschema.validate_trace_file(path)
    assert any(e["name"] == "bridge_counters" for e in doc["traceEvents"])
    for step, (a, b) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(a, b, err_msg=f"boundary {step}")


# ------------------------------------------- session spans, device scopes
def test_run_until_records_dispatch_and_cycles():
    """``run(until=...)`` goes straight to the engine's compiled
    while-loop: one ``session.dispatch`` span per call, and the cycles it
    ran counted into ``session.cycles`` at the next cycle read."""
    sim = _sessions_k1()["fused"]
    sim.reset(0)
    before = REGISTRY.snapshot()
    rec = otrace.recorder()
    prev, n0 = rec.enabled, len(rec.events)
    rec.enabled = True
    try:
        sim.run(until=lambda s: s.cycle >= 5, cache_key="obs.until5")
        sim.run(until=lambda s: s.cycle >= 9, cache_key="obs.until9")
        assert sim.cycle == 9
        names = [e["name"] for e in rec.events[n0:]]
    finally:
        rec.enabled = prev
        del rec.events[n0:]
    assert names.count("session.dispatch") == 2
    assert names.count("session.run") == 2
    assert names.count("session.read") == 1
    after = sim.stats()["metrics"]
    delta = lambda k: after[k] - before.get(k, 0.0)  # noqa: E731
    assert delta("session.dispatches") == 2
    assert delta("session.cycles") == 9


def test_fused_run_until_program_carries_every_scope():
    """The fused engine's ``run_until`` program on a 16x16 torus with
    ``wafer_64k``'s tiles and tiers, every granule axis folded as batch
    rows (so the tier exchange and the per-row split/join are compiled):
    every ``sb.*`` scope names ops in its HLO's op metadata."""
    from repro.core import (ChannelGraph, FusedEngine, fold_mesh,
                            tiered_grid_partition)
    from repro.hw.manycore import (ManycoreCell, allreduce_done,
                                   make_core_params)

    R = C = 16
    graph = ChannelGraph.torus(
        ManycoreCell(R, C), R, C,
        params=make_core_params(np.ones((R, C), np.float32)), capacity=62)
    mesh, batch = fold_mesh({"pod": 2, "gr": 2, "gc": 2}, jax.devices()[:1])
    assert set(batch) == {"pod", "gr", "gc"}
    eng = FusedEngine(graph, tiered_grid_partition(R, C, [(2, 1), (2, 2)]),
                      mesh, tiers=[(("pod",), 4), (("gr", "gc"), 16)],
                      batch_axes=batch)
    state = eng.place(eng.init(jax.random.key(0)))

    def done(s):
        return allreduce_done(s.block_states[0], s.tables.active[0])

    lowered = jax.jit(
        lambda st: eng.run_until(st, done, 1, donate=False)).lower(state)
    hlo = lowered.compiler_ir("hlo").get_hlo_module().to_string()
    paths = set(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in otrace.SCOPES:
        assert any(re.search(rf"(^|/){re.escape(scope)}(/|$)", p)
                   for p in paths), scope


# ---------------------------------------------------------------- report
def test_report_summarize_synthetic():
    doc = {"traceEvents": [
        {"name": "step", "cat": "worker", "ph": "X", "ts": 0.0,
         "dur": 2e4, "pid": 0, "tid": 0},
        {"name": "exchange_commit", "cat": "worker", "ph": "X",
         "ts": 2e4, "dur": 6e4, "pid": 0, "tid": 0},
        {"name": "step", "cat": "worker", "ph": "X", "ts": 0.0,
         "dur": 1e4, "pid": 0, "tid": 1},
        {"name": "recovery_incident", "cat": "recovery", "ph": "i",
         "s": "p", "ts": 5e4, "pid": 0, "tid": TID_SESSION,
         "args": {"incarnation": 2}},
        {"name": "thread_name", "ph": "M", "pid": 0, "tid": 0,
         "args": {"name": "worker 0"}},
    ]}
    text = oreport.summarize(oschema.validate_trace(doc), top=2)
    assert "exchange_commit" in text
    assert "worker 0" in text           # straggler named via metadata
    assert "recovery_incident" in text
    assert "incarnation" in text
