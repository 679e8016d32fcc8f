"""Compile rehearsals for a TPU v5e, made on the CPU (no chip needed).

The TPU compiler is installed here and compiles for a described chip, so
these tests catch what interpret mode and XLA:CPU cannot: an op the chip's
compiler refuses, a kernel layout it cannot lower, a program that does not
fit.  Each compiles the main path's device code at its real size:

  * the fused engine's epoch body for one WAFER granule (8,192 cores) in
    the mode ``fuse="auto"`` resolves to, and that body at WAFER's and the
    benchmark's queue sizes holds no loop but the epoch loop;
  * that body moves only 32-bit elements through its gathers;
  * the same body as a resident Pallas kernel — refused by the chip's
    compiler, which is why ``auto`` never picks it (a TPU run with
    ``fuse="pallas"`` fails at compile time instead of falling back);
  * the register engine's systolic tile kernel at a full-size tile.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.manycore import WAFER
from repro.core import ChannelGraph, FusedEngine, fold_mesh, tiered_grid_partition
from repro.hw.manycore import ManycoreCell, make_core_params
from repro.kernels import granule_step
from repro.kernels import systolic_step as sy


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these.
    prev_cache = jax.config.jax_enable_compilation_cache
    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            yield topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", prev_cache)
        compilation_cache.reset_cache()
        if prev_log is None:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _granule_row(cfg):
    """The fused engine as ``chip_smoke.py`` builds it on one chip, at
    ``cfg``'s sizes, and the shapes of one granule's cycle carry (batch
    row 0)."""
    R, C = cfg.grid_rows, cfg.grid_cols
    vals = np.ones((R, C), np.float32)
    graph = ChannelGraph.torus(
        ManycoreCell(R, C), R, C, params=make_core_params(vals),
        capacity=cfg.queue_capacity)
    mesh, batch = fold_mesh({"pod": 2, "gr": 2, "gc": 2}, jax.devices()[:1])
    eng = FusedEngine(
        graph, tiered_grid_partition(R, C, [(2, 1), (2, 2)]), mesh,
        tiers=[(("pod",), cfg.k_outer), (("gr", "gc"), cfg.k_inner)],
        batch_axes=batch)
    state = jax.eval_shape(eng.init, jax.random.key(0))
    rows = jax.eval_shape(
        lambda s: eng._rows_split(eng._local_view(s)), state)
    return eng, rows[0]


@pytest.fixture(scope="module")
def wafer_row():
    """The WAFER fused engine and one granule's cycle carry."""
    return _granule_row(WAFER)


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _epoch_body(eng, mode, k=WAFER.k_inner):
    return jax.jit(lambda c: granule_step.epoch_loop(
        eng._cycle_body, c, k, consts=eng._t6_row(0),
        mode=mode, interpret=False))


def test_fused_wafer_granule_epoch_compiles(one_chip, wafer_row, monkeypatch):
    monkeypatch.delenv("REPRO_EPOCH_MODE", raising=False)
    eng, row = wafer_row
    mode = granule_step.resolve_mode("auto")
    assert mode == "xla"
    assert int(np.prod(row[3][0].value.shape)) == WAFER.grid_rows * \
        WAFER.grid_cols // 8  # one of the 8 granules
    compiled = _epoch_body(eng, mode).lower(_on(one_chip, row)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0
    assert mem.temp_size_in_bytes < 1 << 30


# The benchmark's ``wafer_64k`` sizes: WAFER's grid at the paper's queue
# depth and inner sync period.
CELL = dataclasses.replace(WAFER, queue_capacity=62, k_inner=16)


@pytest.mark.parametrize("cfg", [WAFER, CELL], ids=["wafer", "cell"])
def test_fused_wafer_epoch_has_no_queue_loop(one_chip, cfg):
    """The compiled epoch body holds one loop, the epoch loop itself: the
    queue push is a dense select, not a scatter that XLA:TPU would lower
    to a scalar loop with one trip per queue row, every cycle."""
    eng, row = _granule_row(cfg)
    text = _epoch_body(eng, "xla", cfg.k_inner).lower(
        _on(one_chip, row)).compile().as_text()
    assert len(re.findall(r"\swhile\(", text)) == 1
    scatters = [n for n in re.findall(r'op_name="([^"]*)"', text)
                if n.endswith("/scatter")]
    assert scatters == []


@pytest.mark.parametrize("cfg", [WAFER, CELL], ids=["wafer", "cell"])
def test_fused_wafer_epoch_gathers_are_32_bit(one_chip, cfg):
    """Every gather in the compiled epoch body moves 32-bit elements: the
    valid/ready flags ride the payload row gathers as 32-bit columns.
    XLA:TPU packs narrower elements (``pred`` four to a word) and gathers
    them one element at a time."""
    eng, row = _granule_row(cfg)
    text = _epoch_body(eng, "xla", cfg.k_inner).lower(
        _on(one_chip, row)).compile().as_text()
    kinds = re.findall(r"=\s*(\w+)\[[^\]]*\]\S*\s+gather\(", text)
    assert kinds, "no gather found in the compiled body"
    assert set(kinds) <= {"f32", "s32", "u32"}, sorted(set(kinds))


def test_fused_wafer_granule_pallas_body_refused(one_chip, wafer_row):
    """The resident Pallas body does not lower for a TPU (rank-0 blocks,
    N-D row gathers): ``fuse="pallas"`` fails loudly on the chip."""
    eng, row = wafer_row
    with pytest.raises(Exception, match="rank >= 1|gather"):
        _epoch_body(eng, "pallas").lower(_on(one_chip, row)).compile()


def test_systolic_tile_kernel_compiles(one_chip):
    R, C, M, K = 128, 128, 16, 16
    f32, i32, b = jnp.float32, jnp.int32, jnp.bool_
    shapes = dict(
        b=((R, C), f32), a_reg=((R, C), f32), a_v=((R, C), b),
        p_reg=((R, C), f32), p_v=((R, C), b), a_idx=((R, C), i32),
        y_idx=((R, C), i32), a_buf=((R, C, M), f32), y_buf=((R, C, M), f32),
        is_west=((R, C), b), is_north=((R, C), b), is_south=((R, C), b),
        is_east=((R, C), b), west_slab=((R, K), f32), west_cnt=((R,), i32),
        north_slab=((C, K), f32), north_cnt=((C,), i32),
    )
    state = {k: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
             for k, (s, d) in shapes.items()}
    compiled = jax.jit(
        lambda st: sy.systolic_step(st, K, interpret=False)
    ).lower(state).compile()
    assert "tpu_custom_call" in compiled.as_text()
