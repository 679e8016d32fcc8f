"""Pallas TPU kernel for the manycore hot loop (paper §IV-B).

Advances a granule's tile of systolic MAC cells **K cycles entirely in
VMEM**, replacing ~10 HBM-roundtrip XLA ops per cycle (peek / step /
push / pop of the generic queue engine) with one fused kernel.  This is the
"FPGA bridge" move of the paper (Table I): the same latency-insensitive
block behaviour, implemented on a faster backend behind identical epoch
boundaries.

Channel model inside the tile: depth-1 elastic registers (a valid/value
pair per hop) instead of 62-deep queues — a legal latency-insensitive
implementation choice, so the computed result is identical (property-tested
against both the oracle and the deep-queue engine).  Tile boundaries are
epoch slabs (up to K packets per boundary row/column per epoch), which is
exactly the granule-exchange unit of ``core.distributed``.

All per-cell dynamic indexing (stream source gather, output collection,
slab append) is expressed as one-hot multiply-accumulate — the TPU-safe
formulation (no data-dependent gathers in VMEM) and the same op order as
``ref.systolic_step_ref``, giving bitwise-comparable f32 results.

Kernel layout (what the TPU compiler accepts; ``systolic_step`` converts
at the boundary): every array is at least 2-D, per-row vectors are
``(R, 1)`` columns and per-column vectors ``(1, C)`` rows, the stream
buffers put M leading (``(M, R, C)``), the north/south slabs put K on the
sublanes (``(K, C)``), flags travel as int32, and the neighbour shifts are
``pltpu.roll`` lane/sublane rotations with the wrapped edge masked — no
gathers, scatters or rank-changing reshapes inside the kernel.

VMEM budget (interior tile, M=1): ~13 (R, C) f32/int32 arrays + 4 (R|C, K)
slabs ≈ 0.15 MB at (32, 64), K=62 — far under budget, so R, C can grow to
fill VMEM (the perf knob in EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _systolic_kernel(
    # inputs (refs)
    b_ref, a_reg_ref, a_v_ref, p_reg_ref, p_v_ref, a_idx_ref, y_idx_ref,
    a_buf_ref, y_buf_ref, is_w_ref, is_n_ref, is_s_ref, is_e_ref,
    west_slab_ref, west_cnt_ref, north_slab_ref, north_cnt_ref,
    e_limit_ref, s_limit_ref,
    # outputs (refs)
    a_reg_o, a_v_o, p_reg_o, p_v_o, a_idx_o, y_idx_o, y_buf_o,
    widx_o, nidx_o, east_slab_o, east_cnt_o, south_slab_o, south_cnt_o,
    *, k_cycles: int,
):
    i32, f32 = jnp.int32, jnp.float32
    b = b_ref[...]
    R, C = b.shape
    M = a_buf_ref.shape[0]
    K = west_slab_ref.shape[-1]
    is_w, is_n = is_w_ref[...] != 0, is_n_ref[...] != 0
    is_s, is_e = is_s_ref[...] != 0, is_e_ref[...] != 0
    a_buf = a_buf_ref[...]
    west_slab, west_cnt = west_slab_ref[...], west_cnt_ref[...]
    north_slab, north_cnt = north_slab_ref[...], north_cnt_ref[...]
    e_limit, s_limit = e_limit_ref[...], s_limit_ref[...]

    col = jax.lax.broadcasted_iota(i32, (R, C), 1)
    row = jax.lax.broadcasted_iota(i32, (R, C), 0)
    first_col, last_col = col == 0, col == C - 1
    first_row, last_row = row == 0, row == R - 1
    k_lane = jax.lax.broadcasted_iota(i32, (R, K), 1)
    k_sub = jax.lax.broadcasted_iota(i32, (K, C), 0)
    m_lead = jax.lax.broadcasted_iota(i32, (M, R, C), 0)

    def east(x):  # value of the west neighbour: x[r, c-1] (wraps at c=0)
        return pltpu.roll(x, 1, 1)

    def south(x):  # value of the north neighbour: x[r-1, c]
        return pltpu.roll(x, 1, 0)

    def cycle(_, carry):
        (a_reg, a_v, p_reg, p_v, a_idx, y_idx, y_buf,
         widx, nidx, east_slab, east_cnt, south_slab, south_cnt) = carry
        a_vb, p_vb = a_v != 0, p_v != 0

        w_slab_val = jnp.sum(west_slab * (widx == k_lane).astype(f32),
                             axis=1, keepdims=True)
        w_val = jnp.where(first_col, w_slab_val, east(a_reg))
        w_vld = jnp.where(first_col, (widx < west_cnt).astype(i32),
                          east(a_v)) != 0
        n_slab_val = jnp.sum(north_slab * (nidx == k_sub).astype(f32),
                             axis=0, keepdims=True)
        n_val = jnp.where(first_row, n_slab_val, south(p_reg))
        n_vld = jnp.where(first_row, (nidx < north_cnt).astype(i32),
                          south(p_v)) != 0

        a_src = jnp.sum(a_buf * (a_idx[None] == m_lead).astype(f32), axis=0)
        a_in = jnp.where(is_w, a_src, w_val)
        a_ok = (is_w & (a_idx < M)) | (~is_w & w_vld)
        p_in = jnp.where(is_n, 0.0, n_val)
        p_ok = is_n | n_vld

        # boundary emission is credit-bounded: col C-1 / row R-1 may only
        # fire while the receiver has advertised slab space.
        e_free = (jnp.where(last_col, (east_cnt < e_limit).astype(i32),
                            1 - a_v) != 0) | is_e
        s_free = (jnp.where(last_row, (south_cnt < s_limit).astype(i32),
                            1 - p_v) != 0) | is_s

        fire = a_ok & p_ok & e_free & s_free
        y = p_in + a_in * b

        cons_a = (fire & ~is_w).astype(i32)
        cons_p = (fire & ~is_n).astype(i32)
        widx = widx + cons_a[:, 0:1]
        nidx = nidx + cons_p[0:1, :]
        # a consumed input frees the neighbour register it came from
        drain_a = ~last_col & (pltpu.roll(cons_a, C - 1, 1) != 0)
        drain_p = ~last_row & (pltpu.roll(cons_p, R - 1, 0) != 0)
        a_v2 = a_vb & ~drain_a
        p_v2 = p_vb & ~drain_p

        emit_e = fire & ~is_e
        emit_s = fire & ~is_s
        a_reg = jnp.where(fire, a_in, a_reg)
        p_reg = jnp.where(fire, y, p_reg)
        to_east = emit_e[:, C - 1:C].astype(i32)
        to_south = emit_s[R - 1:R, :].astype(i32)
        a_v = (a_v2 | (emit_e & ~last_col)).astype(i32)
        p_v = (p_v2 | (emit_s & ~last_row)).astype(i32)
        east_slab = east_slab + (
            a_in[:, C - 1:C] * (east_cnt == k_lane).astype(f32)
        ) * to_east.astype(f32)
        east_cnt = east_cnt + to_east
        south_slab = south_slab + (
            y[R - 1:R, :] * (south_cnt == k_sub).astype(f32)
        ) * to_south.astype(f32)
        south_cnt = south_cnt + to_south

        collect = fire & is_s
        y_buf = y_buf + (y[None] * (y_idx[None] == m_lead).astype(f32)) \
            * collect[None].astype(f32)
        a_idx = a_idx + (fire & is_w).astype(i32)
        y_idx = y_idx + collect.astype(i32)
        return (a_reg, a_v, p_reg, p_v, a_idx, y_idx, y_buf,
                widx, nidx, east_slab, east_cnt, south_slab, south_cnt)

    init = (
        a_reg_ref[...], a_v_ref[...], p_reg_ref[...], p_v_ref[...],
        a_idx_ref[...], y_idx_ref[...], y_buf_ref[...],
        jnp.zeros((R, 1), i32), jnp.zeros((1, C), i32),
        jnp.zeros((R, K), f32), jnp.zeros((R, 1), i32),
        jnp.zeros((K, C), f32), jnp.zeros((1, C), i32),
    )
    outs = jax.lax.fori_loop(0, k_cycles, cycle, init)
    for ref, val in zip(
        (a_reg_o, a_v_o, p_reg_o, p_v_o, a_idx_o, y_idx_o, y_buf_o,
         widx_o, nidx_o, east_slab_o, east_cnt_o, south_slab_o,
         south_cnt_o), outs,
    ):
        ref[...] = val


def systolic_step(state: dict, k_cycles: int, *, interpret: bool = False) -> dict:
    """Run K cycles of a systolic tile; returns the updated state dict.

    ``state`` uses the layout documented in ``ref.systolic_step_ref``;
    ``widx``/``nidx`` are reset to 0 on entry (slab indices are per-epoch)
    and the east/south slabs are produced fresh.
    """
    R, C = state["b"].shape
    M = state["a_buf"].shape[-1]
    K = state["west_slab"].shape[-1]
    f32, i32 = jnp.float32, jnp.int32
    col = lambda v: jnp.reshape(v, (R, 1))  # noqa: E731 — per-row vector
    row = lambda v: jnp.reshape(v, (1, C))  # noqa: E731 — per-column vector
    flag = lambda v: jnp.asarray(v).astype(i32)  # noqa: E731
    lead_m = lambda v: jnp.moveaxis(v, -1, 0)  # noqa: E731 — (R,C,M)->(M,R,C)
    out_shape = (
        jax.ShapeDtypeStruct((R, C), f32),     # a_reg
        jax.ShapeDtypeStruct((R, C), i32),     # a_v
        jax.ShapeDtypeStruct((R, C), f32),     # p_reg
        jax.ShapeDtypeStruct((R, C), i32),     # p_v
        jax.ShapeDtypeStruct((R, C), i32),     # a_idx
        jax.ShapeDtypeStruct((R, C), i32),     # y_idx
        jax.ShapeDtypeStruct((M, R, C), f32),  # y_buf
        jax.ShapeDtypeStruct((R, 1), i32),     # widx
        jax.ShapeDtypeStruct((1, C), i32),     # nidx
        jax.ShapeDtypeStruct((R, K), f32),     # east_slab
        jax.ShapeDtypeStruct((R, 1), i32),     # east_cnt
        jax.ShapeDtypeStruct((K, C), f32),     # south_slab (K on sublanes)
        jax.ShapeDtypeStruct((1, C), i32),     # south_cnt
    )
    kernel = functools.partial(_systolic_kernel, k_cycles=k_cycles)
    (a_reg, a_v, p_reg, p_v, a_idx, y_idx, y_buf, widx, nidx,
     east_slab, east_cnt, south_slab, south_cnt) = pl.pallas_call(
        kernel, out_shape=out_shape, interpret=interpret,
    )(
        state["b"], state["a_reg"], flag(state["a_v"]), state["p_reg"],
        flag(state["p_v"]), state["a_idx"], state["y_idx"],
        lead_m(state["a_buf"]), lead_m(state["y_buf"]),
        flag(state["is_west"]), flag(state["is_north"]),
        flag(state["is_south"]), flag(state["is_east"]),
        state["west_slab"], col(state["west_cnt"]),
        jnp.swapaxes(state["north_slab"], 0, 1), row(state["north_cnt"]),
        col(state.get("east_limit", jnp.full((R,), K, i32))),
        row(state.get("south_limit", jnp.full((C,), K, i32))),
    )
    new = dict(state)
    new.update(
        a_reg=a_reg, a_v=a_v != 0, p_reg=p_reg, p_v=p_v != 0,
        a_idx=a_idx, y_idx=y_idx, y_buf=jnp.moveaxis(y_buf, 0, -1),
        widx=widx.reshape(R), nidx=nidx.reshape(C),
        east_slab=east_slab, east_cnt=east_cnt.reshape(R),
        south_slab=jnp.swapaxes(south_slab, 0, 1),
        south_cnt=south_cnt.reshape(C),
    )
    return new
