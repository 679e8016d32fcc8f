"""Jit'd public wrappers for the Pallas kernels, with custom VJPs.

Backend selection per op:

  * ``pallas`` — the Mosaic TPU kernel (this container validates it in
    interpret mode through the unit tests; on TPU it is the default).
  * ``xla`` — a blocked pure-XLA implementation with the *same* tiling
    structure (scan over KV/Q blocks, online/two-pass softmax, O(T*block)
    memory).  This is what jit paths use on CPU — including the dry-run, so
    the lowered HLO's FLOPs/bytes/collectives are representative of the
    kernel's behaviour rather than of the interpret-mode emulation loop.
  * ``ref`` — dense jnp oracle for tiny smoke-test shapes.

All blocked implementations are written carry-free (block results are scan
*outputs*, never carried accumulators) so GSPMD never has to pick a sharding
for a big loop-carried tensor — that single property is worth ~3x peak temp
memory at train_4k scale (see EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import rglru_scan as _rg
from . import systolic_step as _sy
from . import ref as ref

_ON_TPU = None


def _on_tpu() -> bool:
    global _ON_TPU
    if _ON_TPU is None:
        _ON_TPU = jax.default_backend() == "tpu"
    return _ON_TPU


# ===================================================== flash attention
def _block_mask(q0, k0, bq, bk, T, causal, window):
    q_pos = q0 + jnp.arange(bq)
    k_pos = k0 + jnp.arange(bk)
    mask = jnp.ones((bq, bk), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    return mask


def _xla_flash_fwd_impl(q, k, v, causal, window, scale, bq, bk):
    """Two-pass blocked attention in XLA: returns (o, lse).

    Pass 1 computes per-row LSE by scanning Q blocks; pass 2 recomputes
    scores and combines with V.  2x score FLOPs (like any recompute-based
    flash) but zero big carries and O(bq*S) transient memory.
    """
    B, Hq, T, D = q.shape
    _, Hkv, S, _ = k.shape
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, T, D).astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    nq = T // bq

    qb = jnp.moveaxis(qg.reshape(B, Hkv, G, nq, bq, D), 3, 0)  # (nq,B,Hkv,G,bq,D)

    def one_block(args):
        qi, i = args
        s = jnp.einsum("bkgqd,bksd->bkgqs", qi, kf)  # (B,Hkv,G,bq,S)
        mask = _block_mask(i * bq, 0, bq, S, T, causal, window)
        s = jnp.where(mask[None, None, None], s, -1e30)
        lse = jax.nn.logsumexp(s, axis=-1)  # (B,Hkv,G,bq)
        p = jnp.exp(s - lse[..., None])
        o = jnp.einsum("bkgqs,bksd->bkgqd", p, vf)
        return o, lse

    def scan_body(_, args):
        return None, one_block(args)

    _, (ob, lseb) = jax.lax.scan(scan_body, None, (qb, jnp.arange(nq)))
    o = jnp.moveaxis(ob, 0, 3).reshape(B, Hq, T, D)
    lse = jnp.moveaxis(lseb, 0, 3).reshape(B, Hq, T)
    return o.astype(q.dtype), lse


def _flash_bwd_impl(q, k, v, o, lse, do, causal, window, scale, bq, bk):
    """Carry-free flash backward: two block scans with stacked outputs."""
    B, Hq, T, D = q.shape
    _, Hkv, S, _ = k.shape
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, T, D).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    dog = do.reshape(B, Hkv, G, T, D).astype(jnp.float32)
    lseg = lse.reshape(B, Hkv, G, T)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    deltag = delta.reshape(B, Hkv, G, T)

    # ---- dk, dv: scan over KV blocks (each depends on all Q — no carry).
    nk = S // bk
    kb = jnp.moveaxis(kf.reshape(B, Hkv, nk, bk, D), 2, 0)
    vb = jnp.moveaxis(vf.reshape(B, Hkv, nk, bk, D), 2, 0)

    def kv_block(_, args):
        kj, vj, j = args
        s = jnp.einsum("bkgtd,bksd->bkgts", qg, kj) * scale  # (B,Hkv,G,T,bk)
        mask = _block_mask(0, j * bk, T, bk, T, causal, window)
        p = jnp.where(mask[None, None, None], jnp.exp(s - lseg[..., None]), 0.0)
        dvj = jnp.einsum("bkgts,bkgtd->bksd", p, dog)
        dp = jnp.einsum("bkgtd,bksd->bkgts", dog, vj)
        ds = p * (dp - deltag[..., None]) * scale
        dkj = jnp.einsum("bkgts,bkgtd->bksd", ds, qg)
        return None, (dkj, dvj)

    _, (dkb, dvb) = jax.lax.scan(kv_block, None, (kb, vb, jnp.arange(nk)))
    dk = jnp.moveaxis(dkb, 0, 2).reshape(B, Hkv, S, D)
    dv = jnp.moveaxis(dvb, 0, 2).reshape(B, Hkv, S, D)

    # ---- dq: scan over Q blocks (each depends on all KV — no carry).
    nq = T // bq
    qb = jnp.moveaxis(qg.reshape(B, Hkv, G, nq, bq, D), 3, 0)
    dob = jnp.moveaxis(dog.reshape(B, Hkv, G, nq, bq, D), 3, 0)
    lseb = jnp.moveaxis(lseg.reshape(B, Hkv, G, nq, bq), 3, 0)
    deltab = jnp.moveaxis(deltag.reshape(B, Hkv, G, nq, bq), 3, 0)

    def q_block(_, args):
        qi, doi, lsei, deltai, i = args
        s = jnp.einsum("bkgqd,bksd->bkgqs", qi, kf) * scale
        mask = _block_mask(i * bq, 0, bq, S, T, causal, window)
        p = jnp.where(mask[None, None, None], jnp.exp(s - lsei[..., None]), 0.0)
        dp = jnp.einsum("bkgqd,bksd->bkgqs", doi, vf)
        ds = p * (dp - deltai[..., None]) * scale
        dqi = jnp.einsum("bkgqs,bksd->bkgqd", ds, kf)
        return None, dqi

    _, dqb = jax.lax.scan(q_block, None, (qb, dob, lseb, deltab, jnp.arange(nq)))
    dq = jnp.moveaxis(dqb, 0, 3).reshape(B, Hq, T, D)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, window, scale, bq, bk, backend):
    if backend == "pallas":
        return _fa.flash_attention(
            q, k, v, causal=causal, window=window, sm_scale=scale,
            block_q=bq, block_k=bk, interpret=not _on_tpu(),
        )
    o, _ = _xla_flash_fwd_impl(q, k, v, causal, window, scale, bq, bk)
    return o


def _flash_fwd(q, k, v, causal, window, scale, bq, bk, backend):
    if backend == "pallas":
        o, lse = _fa.flash_attention(
            q, k, v, causal=causal, window=window, sm_scale=scale,
            block_q=bq, block_k=bk, interpret=not _on_tpu(), return_lse=True,
        )
    else:
        o, lse = _xla_flash_fwd_impl(q, k, v, causal, window, scale, bq, bk)
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, window, scale, bq, bk, backend, res, do):
    q, k, v, o, lse = res
    return _flash_bwd_impl(q, k, v, o, lse, do, causal, window, scale, bq, bk)


_flash.defvjp(_flash_fwd, _flash_vjp_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "sm_scale", "block_q", "block_k",
                     "use_kernel", "backend"),
)
def flash_attention(
    q, k, v, *, causal=True, window=None, sm_scale=None,
    block_q=512, block_k=512, use_kernel=True, backend=None,
):
    """(B, Hq, T, D) x (B, Hkv, S, D)^2 -> (B, Hq, T, D).

    backend: None (auto: pallas on TPU, xla elsewhere) | 'pallas' | 'xla'.
    ``use_kernel=False`` falls back to the dense jnp oracle (tiny shapes).
    """
    if not use_kernel:
        return ref.attention_ref(q, k, v, causal=causal, window=window, sm_scale=sm_scale)
    if backend is None:
        backend = "pallas" if _on_tpu() else "xla"
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if backend == "pallas":
        bq, bk = min(128, block_q), min(128, block_k)
    else:
        bq, bk = block_q, block_k
    bq = min(bq, q.shape[2])
    bk = min(bk, k.shape[2])
    return _flash(q, k, v, causal, window, scale, bq, bk, backend)


# ===================================================== rglru
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _rglru(x, a, h0, block_t, block_d, backend):
    if backend == "pallas":
        return _rg.rglru_scan(
            x, a, h0, block_t=block_t, block_d=block_d, interpret=not _on_tpu()
        )
    return ref.rglru_ref(x, a, h0)


def _rglru_fwd(x, a, h0, block_t, block_d, backend):
    h, h_last = _rglru(x, a, h0, block_t, block_d, backend)
    return (h, h_last), (a, h, h0)


def _rglru_bwd(block_t, block_d, backend, res, grads):
    a, h, h0 = res
    dh, dh_last = grads
    dh = dh.astype(jnp.float32)
    af = a.astype(jnp.float32)
    hf = h.astype(jnp.float32)
    dh = dh.at[:, -1].add(dh_last.astype(jnp.float32))

    # adjoint of h_t = a_t h_{t-1} + x_t:
    #   g_t = dh_t + a_{t+1} g_{t+1}  (reverse linear recurrence)
    #   dx_t = g_t ; da_t = g_t * h_{t-1} ; dh0 = a_0 g_0
    a_next = jnp.concatenate([af[:, 1:], jnp.zeros_like(af[:, :1])], axis=1)

    def combine(c2, c1):  # reverse scan
        a2, g2 = c2
        a1, g1 = c1
        return a1 * a2, g1 + a1 * g2

    _, g = jax.lax.associative_scan(combine, (a_next, dh), axis=1, reverse=True)
    h_prev = jnp.concatenate([h0.astype(jnp.float32)[:, None], hf[:, :-1]], axis=1)
    dx = g.astype(a.dtype)
    da = (g * h_prev).astype(a.dtype)
    dh0 = (af[:, 0] * g[:, 0]).astype(a.dtype)
    return dx, da, dh0


_rglru.defvjp(_rglru_fwd, _rglru_bwd)


@functools.partial(jax.jit, static_argnames=("block_t", "block_d", "use_kernel", "backend"))
def rglru(x, a, h0=None, *, block_t=256, block_d=256, use_kernel=True, backend=None):
    """Linear recurrence h_t = a_t h_{t-1} + x_t -> (h, h_last)."""
    if not use_kernel:
        return ref.rglru_ref(x, a, h0)
    if backend is None:
        backend = "pallas" if _on_tpu() else "xla"
    if h0 is None:
        h0 = jnp.zeros((x.shape[0], x.shape[2]), x.dtype)
    return _rglru(x, a, h0, block_t, block_d, backend)


# ===================================================== systolic
def systolic_step(state: dict, k_cycles: int) -> dict:
    """K fused cycles of a systolic tile (see kernels/systolic_step.py).

    Refused on a TPU: the kernel compiles for a v5e, but its results there
    have never been checked against ``engine="single"`` on a chip, so a
    wrong answer would go unnoticed.  Elsewhere it runs interpreted."""
    if _on_tpu():
        raise NotImplementedError(
            "engine='register' has not been checked on a TPU; use "
            "engine='fused', which runs any systolic grid bit-identically")
    return _systolic_step(state, k_cycles)


@functools.partial(jax.jit, static_argnames=("k_cycles",))
def _systolic_step(state: dict, k_cycles: int) -> dict:
    return _sy.systolic_step(state, k_cycles, interpret=True)


# ===================================================== slstm
from . import slstm_scan as _sl  # noqa: E402


def slstm_scan(r: dict, pre, carry0, *, block_t: int = 128, backend=None):
    """sLSTM recurrence with R resident in VMEM (TPU) / lax.scan (CPU).

    Returns (hs, (cs, ns, ms), final_carry).  Used by the custom-VJP
    forward in models/recurrent.py; the backward consumes the sequences.
    """
    if backend is None:
        backend = "pallas" if _on_tpu() else "xla"
    T = pre.shape[1]
    if backend == "pallas" and T % min(block_t, T) == 0:
        return _sl.slstm_scan(
            r, pre, carry0, block_t=block_t, interpret=not _on_tpu()
        )
    return ref.slstm_scan_ref(r, pre, carry0)
