"""Paper Table I: a faster backend behind the same interface.

The paper put RTL on FPGAs for ~8,000x over RTL simulation.  Our analogue:
the same systolic-cell network simulated by (a) an interpreted pure-Python
cycle loop ("RTL simulator"), (b) the compiled single-netlist engine,
(c) the distributed GraphEngine and (d) the fused-epoch engine — identical
latency-insensitive semantics, bit-identical results, only the backend
changes.

The compiled backend is ASSERTED to beat the interpreted one.  Wall
times are min-of-N to shed scheduler noise; they are host timings of
whatever backend JAX runs on, not device measurements.
"""
import time

import jax
import numpy as np

from .common import emit
from repro.hw.systolic import (
    collect_result, cycles_needed, make_systolic_network,
)


def python_reference_sim(A, B, cycles):
    """Interpreted cycle-accurate simulation (deque channels)."""
    import collections

    M, K = A.shape
    _, N = B.shape
    east = {}
    south = {}
    for r in range(K):
        for c in range(N):
            east[(r, c)] = collections.deque(maxlen=7)
            south[(r, c)] = collections.deque(maxlen=7)
    a_idx = np.zeros((K, N), int)
    y = [[[] for _ in range(N)] for _ in range(K)]
    for _ in range(cycles):
        fires = []
        for r in range(K):
            for c in range(N):
                if c == 0:
                    a_ok = a_idx[r, c] < M
                    a_val = A[a_idx[r, c], r] if a_ok else 0.0
                else:
                    a_ok = len(east[(r, c - 1)]) > 0
                    a_val = east[(r, c - 1)][0] if a_ok else 0.0
                if r == 0:
                    p_ok, p_val = True, 0.0
                else:
                    p_ok = len(south[(r - 1, c)]) > 0
                    p_val = south[(r - 1, c)][0] if p_ok else 0.0
                e_free = c == N - 1 or len(east[(r, c)]) < 7
                s_free = r == K - 1 or len(south[(r, c)]) < 7
                if a_ok and p_ok and e_free and s_free:
                    fires.append((r, c, a_val, p_val + a_val * B[r, c]))
        for r, c, a_val, yv in fires:
            if c == 0:
                a_idx[r, c] += 1
            else:
                east[(r, c - 1)].popleft()
            if r > 0:
                south[(r - 1, c)].popleft()
            if c < N - 1:
                east[(r, c)].append(a_val)
            if r < K - 1:
                south[(r, c)].append(yv)
            else:
                y[r][c].append(yv)
    return np.array([y[K - 1][c] for c in range(N)]).T


def _best_of(fn, n: int = 3):
    """(min wall time of fn() over n runs, last result); 1st call warms."""
    fn()
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best, out


def bench(smoke: bool = False):
    rng = np.random.RandomState(0)
    M, K, N = (6, 4, 4) if smoke else (12, 8, 8)
    A = rng.randn(M, K).astype(np.float32)
    B = rng.randn(K, N).astype(np.float32)
    cycles = cycles_needed(M, K, N)

    # interpreted backend
    t_py, Y_py = _best_of(lambda: python_reference_sim(A, B, cycles), n=2)
    hz_py = cycles / t_py

    # All compiled backends hang off the unified build(engine=...) session
    # API — same Network description, different engine, identical results.
    # reset() happens once: only the compiled run is timed (the session
    # donates its state, so timed calls measure the in-place loop).
    net, grid = make_systolic_network(A, B)
    sim = net.build()  # engine="single" session
    sim.reset(jax.random.key(0)).block_until_ready()
    t_jit, _ = _best_of(lambda: sim.run(cycles=cycles).block_until_ready())
    hz_jit = cycles / t_jit
    # the stream is exhausted by then: extra timed runs leave y_buf fixed
    Y = collect_result(sim.engine, sim.state, grid)

    from repro.core.compat import make_mesh

    k_epoch = 4
    n_epochs = -(-cycles // k_epoch)
    mesh = make_mesh((1,), ("gx",))

    def run_engine(engine):
        esim = net.build(engine=engine, mesh=mesh, K=k_epoch)
        esim.reset(jax.random.key(0)).block_until_ready()
        t, _ = _best_of(
            lambda: esim.run(epochs=n_epochs).block_until_ready()
        )
        flat = esim.engine.gather_group(esim.state, 0)
        Y_e = np.stack([flat.y_buf[(K - 1) * N + c] for c in range(N)], axis=1)
        return t, Y_e

    t_graph, Y_g = run_engine("graph")
    hz_graph = cycles / t_graph
    t_fused, Y_f = run_engine("fused")
    hz_fused = cycles / t_fused

    np.testing.assert_allclose(Y, A @ B, rtol=1e-4)
    np.testing.assert_allclose(Y_py, A @ B, rtol=1e-4)
    np.testing.assert_allclose(Y_g, A @ B, rtol=1e-4)
    np.testing.assert_allclose(Y_f, A @ B, rtol=1e-4)
    emit("backend_interpreted", t_py / cycles * 1e6, f"{hz_py:.0f} Hz sim clock")
    emit("backend_compiled", t_jit / cycles * 1e6,
         f"{hz_jit:.0f} Hz sim clock, {hz_jit/hz_py:.0f}x speedup "
         f"(paper Table I: 7300-8900x FPGA vs RTL)")
    emit("backend_graph_engine", t_graph / cycles * 1e6,
         f"{hz_graph:.0f} Hz sim clock via build(engine='graph'), K={k_epoch}")
    emit("backend_fused_engine", t_fused / cycles * 1e6,
         f"{hz_fused:.0f} Hz sim clock via build(engine='fused'), K={k_epoch}")
    # ISSUE 3 regression gate: compiled must never lose to interpreted again
    assert hz_jit >= hz_py, (
        f"compiled single-netlist backend ({hz_jit:.0f} Hz) slower than the "
        f"interpreted reference ({hz_py:.0f} Hz) — thunk-runtime regression?"
    )


if __name__ == "__main__":
    bench()
