"""``chip_smoke.py``'s phases on the CPU, through the same functions the
script calls on the chip, at small sizes: the logic of every phase is
covered on every change without chip time.  ``main()`` itself must refuse
to run anywhere but a TPU."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def test_wafer_phase_small_torus():
    """The one-chip layout (2 pods x 2x2 granules, all folded onto one
    device) runs the allreduce to the global sum, cold and warm alike."""
    r = chip_smoke.run_wafer(8, 8, devices=jax.devices()[:1], seed=3)
    assert r["batch_axes"] == {"pod": 2, "gr": 2, "gc": 2}
    assert r["cycles"] > 0 and r["cycles"] % 32 == 0  # whole epochs
    assert r["run_s"] > 0


def test_host_io_phase_fused_matches_single():
    s = chip_smoke.run_chain_session(6, 40, seed=1)
    assert s["fused"]["cycle"] == s["single"]["cycle"]
    assert s["single"]["sent"] > 20
    assert [int(c) for c in s["fused"]["probe"]] == [s["single"]["sent"]] * 6


def test_cross_chip_phase_on_four_cpu_devices():
    """The --chips 4 comparison on four simulated devices: the spread run
    shards its state over all four and matches the folded run bit for
    bit (asserted inside ``run_wafer_across_chips``)."""
    code = textwrap.dedent(f"""
        import sys; sys.path.insert(0, {ROOT!r})
        import chip_smoke
        r = chip_smoke.run_wafer_across_chips(8, 8, seed=2)
        assert r["spread"]["mesh"] == {{"pod": 2, "g": 2}}, r["spread"]["mesh"]
        assert r["folded"]["batch_axes"] == {{"pod": 2, "g": 2}}
        assert len(r["bytes_per_device"]) == 4, r["bytes_per_device"]
        print("CROSS-CHIP-OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "CROSS-CHIP-OK" in out.stdout


def test_main_refuses_without_tpu():
    """On the CPU the script exits non-zero before any phase and prints no
    result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert "[wafer]" not in out.stdout
    for line in out.stdout.splitlines():
        try:
            assert "ok" not in json.loads(line)
        except (ValueError, TypeError):
            pass


def test_fold_mesh_maps_layout_onto_devices():
    from repro.core import fold_mesh

    dev = jax.devices()[:1]
    mesh, batch = fold_mesh({"pod": 2, "gr": 2, "gc": 2}, dev)
    assert dict(mesh.shape) == {"device": 1}
    assert batch == {"pod": 2, "gr": 2, "gc": 2}
    mesh, batch = fold_mesh({"pod": 1, "g": 3}, dev)
    assert dict(mesh.shape) == {"pod": 1} and batch == {"g": 3}
    assert np.asarray(mesh.devices).size == 1


def test_compile_cache_rule(monkeypatch, tmp_path):
    """One rule for the whole program: an explicit directory wins; else
    ``JAX_COMPILATION_CACHE_DIR`` (which JAX reads itself — nothing is set
    in code); else one fixed directory inside the checkout."""
    from repro.core import compile_cache as cc

    knobs = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_persistent_cache_min_compile_time_secs")
    prev = {k: getattr(jax.config, k) for k in knobs}
    try:
        monkeypatch.setenv(cc.ENV, str(tmp_path / "env"))
        jax.config.update("jax_compilation_cache_dir", "untouched")
        assert cc.enable_compile_cache() == str(tmp_path / "env")
        assert jax.config.jax_compilation_cache_dir == "untouched"
        assert cc.enable_compile_cache(str(tmp_path / "x")) == str(
            tmp_path / "x")
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "x")
        monkeypatch.delenv(cc.ENV)
        path = cc.enable_compile_cache()
        assert path == os.path.join(os.path.abspath(ROOT), ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        for k, v in prev.items():
            jax.config.update(k, v)
