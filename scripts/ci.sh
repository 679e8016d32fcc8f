#!/usr/bin/env bash
# CPU gate: tier-1 tests, the runtime and fleet drills, the flight
# recorder, and every example in smoke mode.  Speed is measured on the
# chip only (chipbench/, cells declared in BENCHMARK.json); no stage here
# times anything.
#
#   scripts/ci.sh            # everything
#   scripts/ci.sh tests      # tier-1 tests only
#   scripts/ci.sh procs      # multiprocess-runtime smoke (hard timeout)
#   scripts/ci.sh fleet      # 2-launcher TCP-bridged fleet smoke
#   scripts/ci.sh obs        # flight-recorder smoke: traced run + its suite
#   scripts/ci.sh examples   # all examples, smoke-sized, via the session API
#
# Stages:
#   * tests: the tier-1 pytest run;
#   * procs: the free-running fleet on a smoke wafer (plain, batched with
#     the overlapped exchange), its fault drills (kill -> bit-identical
#     recovery, stall -> FleetStallError) and an env-knob drill
#     (REPRO_ON_FAULT/REPRO_FAULT_PLAN) through a real example, each
#     under a hard timeout;
#   * fleet: the 2-launcher TCP-bridged fleet — framing, loopback
#     bit-exactness, link-kill recovery and the 2-pod tiered wafer across
#     the bridge, each under a hard timeout;
#   * obs: a REPRO_TRACE smoke wafer whose exported Perfetto trace is
#     validated and rendered, and the obs test suite;
#   * examples: every example through the Simulation session API.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

stage="${1:-all}"

if [[ "$stage" == "all" || "$stage" == "tests" ]]; then
    if ! python -c "import hypothesis" 2>/dev/null; then
        echo "WARNING: hypothesis not installed — property-based queue/systolic"
        echo "         tests will be SKIPPED.  For full coverage run:"
        echo "         pip install -r requirements-dev.txt"
    fi
    echo "=== tier-1 tests ==="
    python -m pytest -x -q
fi

if [[ "$stage" == "all" || "$stage" == "procs" ]]; then
    # The free-running fleet synchronizes through blocking shm rings, so a
    # protocol bug shows up as a DEADLOCK — the hard timeout turns that
    # into a fast failure instead of a hung CI job.  (The launcher's own
    # heartbeat watchdog fires first in-process; `timeout` is the backstop.)
    echo "=== procs runtime: 4-worker wafer smoke (hard 300s timeout) ==="
    timeout 300 python -m pytest -q tests/test_runtime.py \
        -k "wafer or kill" -x
    echo "=== procs runtime: 4-worker tiered wafer example ==="
    timeout 300 python examples/wafer_scale.py --rows 8 --cols 8 \
        --k-inner 4 --engine procs
    echo "=== procs runtime: batched fleet, overlapped exchange ==="
    # signature-batched workers + the ISSUE 7 split issue/commit schedule:
    # one stacked dispatch per worker epoch, receive-late shm-ring pops
    timeout 300 python examples/wafer_scale.py --rows 8 --cols 8 \
        --k-inner 4 --engine procs --batch-signatures --overlap
    echo "=== self-healing fleet: fault drills (hard 300s timeout) ==="
    # ISSUE 8: a plan-killed worker must auto-recover bit-identically, a
    # clean mid-run exit must be detected fast, and a credit deadlock
    # must be diagnosed as FleetStallError — never a hung CI job
    timeout 300 python -m pytest -q tests/test_recovery.py -x \
        -k "stall or clean_exit or (kill_recovery and not 1 and not 2)"
    echo "=== self-healing fleet: env-knob drill (REPRO_ON_FAULT) ==="
    # the same recovery path driven purely by env knobs through a real
    # example: worker 2 is killed mid-allreduce and the invariant at the
    # end of the example still holds on the healed fleet
    REPRO_ON_FAULT=recover REPRO_FAULT_PLAN="kill:2@3" \
        timeout 300 python examples/wafer_scale.py --rows 8 --cols 8 \
        --k-inner 4 --engine procs
fi

if [[ "$stage" == "all" || "$stage" == "fleet" ]]; then
    # ISSUE 9: two cooperating launcher processes joined only by loopback
    # TCP ring bridges.  Same deadlock philosophy as the procs stage: a
    # bridge-protocol bug stalls the fleet, so every step runs under a
    # hard timeout and the in-process watchdog (which now covers bridges
    # as first-class members) fires first with a typed error.
    echo "=== bridged fleet: framing + plan/link units ==="
    timeout 300 python -m pytest -q tests/test_bridge.py -x \
        -k "not fleet_"
    echo "=== bridged fleet: 2-launcher loopback bit-exactness ==="
    timeout 300 python -m pytest -q tests/test_bridge.py -x \
        -k "fleet_bit_exact or fleet_io_parity"
    echo "=== bridged fleet: link-kill recovery drill ==="
    timeout 300 python -m pytest -q tests/test_bridge.py -x \
        -k "fleet_linkkill"
    echo "=== bridged fleet: 2-pod tiered wafer across 2 launchers ==="
    # the acceptance scenario: the pod boundary rides the TCP bridge, the
    # allreduce invariant still witnesses every packet crossing it
    timeout 300 python examples/wafer_scale.py --rows 8 --cols 8 \
        --k-inner 4 --engine procs --hosts 2
fi

if [[ "$stage" == "all" || "$stage" == "obs" ]]; then
    # ISSUE 10: the flight recorder end to end — a procs smoke wafer run
    # traced via the REPRO_TRACE env knob must export a Perfetto-loadable
    # timeline (validated by repro.obs.schema, rendered by
    # repro.obs.report), and the obs test suite must pass (bit-identical
    # traced-vs-untraced traffic on every engine, recovery incidents in
    # the timeline).
    OBS_TRACE="${TMPDIR:-/tmp}/repro_ci_trace.json"
    echo "=== flight recorder: traced smoke wafer (REPRO_TRACE) ==="
    REPRO_TRACE="$OBS_TRACE" timeout 300 python examples/wafer_scale.py \
        --rows 8 --cols 8 --k-inner 4 --engine procs
    echo "=== flight recorder: validate + report the exported trace ==="
    python -m repro.obs.schema "$OBS_TRACE"
    python -m repro.obs.report "$OBS_TRACE" --top 5
    echo "=== flight recorder: obs test suite (hard 600s timeout) ==="
    timeout 600 python -m pytest -q tests/test_obs.py -x
fi

if [[ "$stage" == "all" || "$stage" == "examples" ]]; then
    # Every example, smoke-sized, through the Simulation session API.
    echo "=== example: quickstart (session Tx/Rx ports) ==="
    python examples/quickstart.py
    echo "=== example: heterogeneous SoC (4 fake devices) ==="
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python examples/heterogeneous_soc.py
    echo "=== example: systolic matmul (session run_until sweep) ==="
    python examples/systolic_matmul.py --rows 4 --cols 4 --m 6
    echo "=== example: wafer-scale tiered torus (8 fake devices) ==="
    python examples/wafer_scale.py --rows 16 --cols 16
    echo "=== example: train pipeline (tiny config, crash/restore) ==="
    python examples/train_pipeline.py --smoke
fi

echo "CI OK"
