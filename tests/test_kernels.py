"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.ref import systolic_step_ref


# ------------------------------------------------------------- flash attn
@pytest.mark.parametrize("backend", ["pallas", "xla"])
@pytest.mark.parametrize(
    "B,Hq,Hkv,T,S,D",
    [
        (1, 2, 2, 128, 128, 64),    # MHA
        (2, 4, 2, 256, 256, 64),    # GQA
        (1, 8, 1, 128, 128, 128),   # MQA
        (1, 2, 2, 384, 384, 80),    # non-pow2 head dim (hubert)
    ],
)
@pytest.mark.parametrize("causal,window", [(True, None), (False, None), (True, 64)])
def test_flash_attention_sweep(backend, B, Hq, Hkv, T, S, D, causal, window):
    rng = np.random.RandomState(hash((B, Hq, T, D)) % 2**31)
    q = jnp.asarray(rng.randn(B, Hq, T, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, Hkv, S, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, Hkv, S, D), jnp.float32)
    out = ops.flash_attention(
        q, k, v, causal=causal, window=window, backend=backend,
        block_q=128, block_k=128,
    )
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 4, 128, 64), dtype)
    k = jnp.asarray(rng.randn(1, 2, 128, 64), dtype)
    v = jnp.asarray(rng.randn(1, 2, 128, 64), dtype)
    out = ops.flash_attention(q, k, v, backend="pallas")
    want = ref.attention_ref(q, k, v)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), atol=tol, rtol=tol
    )
    assert out.dtype == dtype


@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_flash_attention_grads(backend):
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 4, 128, 32), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 128, 32), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, 128, 32), jnp.float32)

    def loss_k(q, k, v):
        return (ops.flash_attention(q, k, v, backend=backend, block_q=64, block_k=64) ** 2).sum()

    def loss_r(q, k, v):
        return (ref.attention_ref(q, k, v) ** 2).sum()

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4, rtol=1e-4)


# ------------------------------------------------------------- rglru
@pytest.mark.parametrize("B,T,D,bt,bd", [(1, 256, 256, 256, 256), (2, 512, 512, 128, 256)])
def test_rglru_sweep(B, T, D, bt, bd):
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(B, T, D), jnp.float32)
    a = jnp.asarray(rng.uniform(0.3, 0.999, (B, T, D)), jnp.float32)
    h0 = jnp.asarray(rng.randn(B, D), jnp.float32)
    h, hl = ops.rglru(x, a, h0, block_t=bt, block_d=bd, backend="pallas")
    hr, hlr = ref.rglru_ref(x, a, h0)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=2e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(hl), np.asarray(hlr), atol=2e-4, rtol=1e-4)


def test_rglru_matches_naive_loop():
    rng = np.random.RandomState(3)
    B, T, D = 1, 64, 256
    x = np.asarray(rng.randn(B, T, D), np.float32)
    a = np.asarray(rng.uniform(0.5, 0.99, (B, T, D)), np.float32)
    h = np.zeros((B, D), np.float32)
    hs = []
    for t in range(T):
        h = a[:, t] * h + x[:, t]
        hs.append(h.copy())
    want = np.stack(hs, axis=1)
    got, _ = ops.rglru(jnp.asarray(x), jnp.asarray(a), backend="pallas")
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4, rtol=1e-4)


def test_rglru_grad_vs_ref():
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(2, 256, 256), jnp.float32)
    a = jnp.asarray(rng.uniform(0.5, 0.99, (2, 256, 256)), jnp.float32)
    h0 = jnp.asarray(rng.randn(2, 256), jnp.float32)
    w = jnp.asarray(rng.randn(256), jnp.float32)

    def lk(x, a, h0):
        h, hl = ops.rglru(x, a, h0)
        return (h * w).sum() + (hl**2).sum()

    def lr(x, a, h0):
        h, hl = ref.rglru_ref(x, a, h0)
        return (h * w).sum() + (hl**2).sum()

    gk = jax.grad(lk, argnums=(0, 1, 2))(x, a, h0)
    gr = jax.grad(lr, argnums=(0, 1, 2))(x, a, h0)
    for a_, b_ in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a_), np.asarray(b_), atol=1e-3, rtol=1e-3)


# ------------------------------------------------------------- systolic step
def _tile_state(rng, M, R, C, K):
    rr, cc = np.meshgrid(np.arange(R), np.arange(C), indexing="ij")
    A = rng.randn(M, R).astype(np.float32)
    B = rng.randn(R, C).astype(np.float32)
    a_buf = np.zeros((R, C, M), np.float32)
    a_buf[:, 0, :] = A.T
    z = jnp.zeros
    return A, B, dict(
        b=jnp.asarray(B), a_reg=z((R, C)), a_v=z((R, C), bool),
        p_reg=z((R, C)), p_v=z((R, C), bool),
        a_idx=z((R, C), jnp.int32), y_idx=z((R, C), jnp.int32),
        a_buf=jnp.asarray(a_buf), y_buf=z((R, C, M)),
        is_west=jnp.asarray(cc == 0), is_north=jnp.asarray(rr == 0),
        is_south=jnp.asarray(rr == R - 1), is_east=jnp.asarray(cc == C - 1),
        west_slab=z((R, K)), west_cnt=z((R,), jnp.int32),
        north_slab=z((C, K)), north_cnt=z((C,), jnp.int32),
        widx=z((R,), jnp.int32), nidx=z((C,), jnp.int32),
        east_slab=z((R, K)), east_cnt=z((R,), jnp.int32),
        south_slab=z((C, K)), south_cnt=z((C,), jnp.int32),
    )


@pytest.mark.parametrize("M,R,C,K", [(4, 3, 3, 4), (6, 4, 5, 8), (8, 2, 2, 16)])
def test_systolic_kernel_vs_oracle_and_matmul(M, R, C, K):
    rng = np.random.RandomState(M * 100 + R * 10 + C)
    A, B, state = _tile_state(rng, M, R, C, K)
    s_k, s_r = dict(state), dict(state)
    for _ in range(6 * (M + R + C)):
        s_k = ops.systolic_step(s_k, K)
        s_r.update(
            widx=jnp.zeros((R,), jnp.int32), nidx=jnp.zeros((C,), jnp.int32),
            east_slab=jnp.zeros((R, K)), east_cnt=jnp.zeros((R,), jnp.int32),
            south_slab=jnp.zeros((C, K)), south_cnt=jnp.zeros((C,), jnp.int32),
        )
        s_r = systolic_step_ref(s_r, K)
        for key in ("a_reg", "a_v", "p_reg", "p_v", "y_buf", "y_idx", "a_idx"):
            np.testing.assert_allclose(
                np.asarray(s_k[key], np.float32),
                np.asarray(s_r[key], np.float32),
                atol=1e-6, err_msg=key,
            )
        if bool((np.asarray(s_k["y_idx"][R - 1]) >= M).all()):
            break
    Y = np.asarray(s_k["y_buf"][R - 1]).T
    np.testing.assert_allclose(Y, A @ B, rtol=1e-5)


def test_systolic_kernel_refused_on_tpu(monkeypatch):
    """The register engine's kernel refuses to run natively on a TPU, where
    its results have not been checked, instead of answering unchecked."""
    _, _, state = _tile_state(np.random.RandomState(0), 4, 3, 3, 4)
    monkeypatch.setattr(ops, "_ON_TPU", True)
    with pytest.raises(NotImplementedError, match="engine='fused'"):
        ops.systolic_step(state, 4)


def test_systolic_kernel_boundary_slabs():
    """West/north slab ingress and east/south egress move packets in order."""
    rng = np.random.RandomState(9)
    M, R, C, K = 4, 2, 2, 8
    _, B, state = _tile_state(rng, M, R, C, K)
    # interior tile: disable edge flags, feed west+north via slabs
    state.update(
        is_west=jnp.zeros((R, C), bool), is_north=jnp.zeros((R, C), bool),
        is_south=jnp.zeros((R, C), bool), is_east=jnp.zeros((R, C), bool),
        west_slab=jnp.asarray(np.arange(R * K, dtype=np.float32).reshape(R, K)),
        west_cnt=jnp.full((R,), 3, jnp.int32),
        north_slab=jnp.zeros((C, K)),
        north_cnt=jnp.full((C,), 3, jnp.int32),
    )
    out = ops.systolic_step(dict(state), K)
    # every fed packet pair must eventually exit; after K cycles with 3 inputs
    # the egress counters are bounded by inputs
    assert int(out["east_cnt"].sum()) <= 3 * R
    assert int(out["south_cnt"].sum()) <= 3 * C
    # conservation: packets consumed from west == forwarded east (+ in-flight)
    consumed = int(out["widx"].sum())
    inflight = int(out["a_v"].sum())
    assert consumed == int(out["east_cnt"].sum()) + inflight


# ------------------------------------------------------------- mlstm chunk
def test_mlstm_chunked_matches_stepwise():
    """Chunkwise-parallel mLSTM == sequential recurrent decode, step by step."""
    from repro.models.recurrent import mlstm_chunked

    rng = np.random.RandomState(11)
    B, T, H, hd = 2, 32, 2, 8
    q = jnp.asarray(rng.randn(B, T, H, hd), jnp.float32)
    k = jnp.asarray(rng.randn(B, T, H, hd), jnp.float32) / np.sqrt(hd)
    v = jnp.asarray(rng.randn(B, T, H, hd), jnp.float32)
    log_i = jnp.asarray(rng.randn(B, T, H), jnp.float32)
    log_f = jnp.asarray(np.log(rng.uniform(0.6, 0.95, (B, T, H))), jnp.float32)

    state = (
        jnp.zeros((B, H, hd, hd)), jnp.zeros((B, H, hd)),
        jnp.full((B, H), -jnp.inf),
    )
    h8, _ = mlstm_chunked(q, k, v, log_i, log_f, state, chunk=8)
    h32, _ = mlstm_chunked(q, k, v, log_i, log_f, state, chunk=32)
    np.testing.assert_allclose(np.asarray(h8), np.asarray(h32), atol=1e-5)

    # sequential recurrence oracle
    C = np.zeros((B, H, hd, hd)); n = np.zeros((B, H, hd)); m = np.full((B, H), -np.inf)
    qn, kn, vn = map(np.asarray, (q, k, v))
    lin, lfn = np.asarray(log_i), np.asarray(log_f)
    outs = []
    for t in range(T):
        m_new = np.maximum(lfn[:, t] + m, lin[:, t])
        fdec = np.exp(lfn[:, t] + m - m_new)
        iexp = np.exp(lin[:, t] - m_new)
        C = C * fdec[..., None, None] + iexp[..., None, None] * (
            kn[:, t][..., :, None] @ vn[:, t][..., None, :]
        )
        n = n * fdec[..., None] + iexp[..., None] * kn[:, t]
        m = m_new
        num = np.einsum("bhd,bhde->bhe", qn[:, t], C)
        den = np.maximum(np.abs(np.einsum("bhd,bhd->bh", qn[:, t], n)), np.exp(-m))
        outs.append(num / (den[..., None] + 1e-6))
    want = np.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(h8), want, atol=1e-4, rtol=1e-4)


# ------------------------------------------------------------- slstm scan
@pytest.mark.parametrize("B,T,d,H,bt", [(1, 32, 16, 2, 8), (2, 64, 32, 4, 16), (2, 128, 64, 4, 128)])
def test_slstm_kernel_vs_oracle(B, T, d, H, bt):
    from repro.kernels.slstm_scan import slstm_scan
    from repro.kernels.ref import slstm_scan_ref

    rng = np.random.RandomState(B * 100 + T)
    hd = d // H
    r = {g: jnp.asarray(rng.randn(H, hd, hd) * 0.3, jnp.float32) for g in "ifzo"}
    pre = jnp.asarray(rng.randn(B, T, 4, d), jnp.float32)
    z = jnp.zeros((B, d))
    carry0 = (z, z, z, jnp.full((B, d), -jnp.inf))
    hs_k, seqs_k, fin_k = slstm_scan(r, pre, carry0, block_t=bt, interpret=True)
    hs_r, seqs_r, fin_r = slstm_scan_ref(r, pre, carry0)
    np.testing.assert_allclose(np.asarray(hs_k), np.asarray(hs_r), atol=1e-6)
    for a, b in zip(seqs_k, seqs_r):
        np.testing.assert_allclose(
            np.nan_to_num(np.asarray(a)), np.nan_to_num(np.asarray(b)), atol=1e-6
        )
    for a, b in zip(fin_k, fin_r):
        np.testing.assert_allclose(
            np.nan_to_num(np.asarray(a)), np.nan_to_num(np.asarray(b)), atol=1e-6
        )
