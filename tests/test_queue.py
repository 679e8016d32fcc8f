"""Queue semantics vs a Python deque oracle (paper §III-B), property-based,
and `cycle` vs a per-queue NumPy reference, bit for bit."""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import queue as qmod


def make(n=1, W=1, cap=8):
    return qmod.make_queues(n, W, cap)


def test_paper_semantics_full_empty():
    q = make(cap=8)
    assert bool(qmod.empty(q)[0])
    assert int(qmod.free(q)[0]) == 7  # capacity-1 usable slots, like the paper
    for i in range(7):
        q, ok, _ = qmod.cycle(
            q, jnp.full((1, 1), float(i)), jnp.array([True]), jnp.array([False])
        )
        assert bool(ok[0])
    assert bool(qmod.full(q)[0])
    # push into a full queue must fail
    q2, ok, _ = qmod.cycle(q, jnp.full((1, 1), 99.0), jnp.array([True]), jnp.array([False]))
    assert not bool(ok[0])


@settings(max_examples=30, deadline=None)
@given(
    st.lists(
        st.tuples(st.booleans(), st.booleans(), st.floats(0, 100)),
        min_size=1, max_size=60,
    )
)
def test_fifo_matches_deque_oracle(ops):
    """Random push/pop interleavings preserve FIFO order and occupancy."""
    cap = 8
    q = make(cap=cap)
    oracle = collections.deque()
    for do_push, do_pop, val in ops:
        front_before = oracle[0] if oracle else None
        q, pushed, popped = qmod.cycle(
            q,
            jnp.full((1, 1), val, jnp.float32),
            jnp.array([do_push]),
            jnp.array([do_pop]),
        )
        # pop observes the pre-cycle front
        if do_pop and front_before is not None:
            assert bool(popped[0])
            got = front_before
            oracle.popleft()
        else:
            assert not bool(popped[0])
        if do_push and len(oracle) < cap - 1 + (1 if (do_pop and front_before is not None) else 0):
            # push succeeds unless full *pre-cycle*
            pass
        if bool(pushed[0]):
            oracle.append(np.float32(val))
        assert int(qmod.size(q)[0]) == len(oracle)
        if oracle:
            front, valid = qmod.peek(q)
            assert bool(valid[0])
            np.testing.assert_allclose(front[0, 0], oracle[0], rtol=1e-6)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 7), st.integers(0, 7), st.integers(1, 7))
def test_drain_fill_roundtrip(n_in, limit, max_n):
    """drain()+fill() moves exactly min(size, limit, max_n) packets FIFO."""
    cap = 8
    src = make(cap=cap)
    dst = make(cap=cap)
    for i in range(n_in):
        src, ok, _ = qmod.cycle(
            src, jnp.full((1, 1), float(i)), jnp.array([True]), jnp.array([False])
        )
    src2, slab, cnt = qmod.drain(src, max_n, limit=jnp.array([limit]))
    moved = min(n_in, limit, max_n)
    assert int(cnt[0]) == moved
    assert int(qmod.size(src2)[0]) == n_in - moved
    dst2 = qmod.fill(dst, slab, cnt)
    assert int(qmod.size(dst2)[0]) == moved
    for i in range(moved):
        front, valid = qmod.peek(dst2)
        assert bool(valid[0])
        np.testing.assert_allclose(front[0, 0], float(i))
        dst2, _, _ = qmod.cycle(
            dst2, jnp.zeros((1, 1)), jnp.array([False]), jnp.array([True])
        )


def test_batched_queues_independent():
    q = make(n=4, cap=8)
    pv = jnp.array([True, False, True, False])
    q, ok, _ = qmod.cycle(q, jnp.arange(4.0).reshape(4, 1), pv, jnp.zeros(4, bool))
    np.testing.assert_array_equal(np.asarray(qmod.size(q)), [1, 0, 1, 0])


def _ref_cycle(buf, head, tail, cap, payload, valid, ready):
    """Per-queue reference of ``qmod.cycle`` in plain NumPy."""
    buf, head, tail = buf.copy(), head.copy(), tail.copy()
    n = head.shape[0]
    did_push = np.zeros(n, bool)
    did_pop = np.zeros(n, bool)
    for i in range(n):
        h, t = int(head[i]), int(tail[i])
        if valid[i] and (h + 1) % cap != t:
            buf[i, h] = payload[i]
            head[i] = (h + 1) % cap
            did_push[i] = True
        if ready[i] and h != t:
            tail[i] = (t + 1) % cap
            did_pop[i] = True
    return buf, head, tail, did_push, did_pop


_CASES = ["random", "full", "empty", "wrap", "push_pop"]


def _start(rng, case, n, cap, W):
    """Initial (buf, head, tail) and first-cycle (valid, ready) masks."""
    buf = rng.standard_normal((n, cap, W)).astype(np.float32)
    tail = rng.randint(0, cap, n).astype(np.int32)
    valid, ready = rng.rand(n) < 0.5, rng.rand(n) < 0.5
    if case == "random":
        size = rng.randint(0, cap, n)
    elif case == "full":  # every push refused
        size, valid = np.full(n, cap - 1), np.ones(n, bool)
    elif case == "empty":  # every pop refused
        size, ready = np.zeros(n, int), np.ones(n, bool)
    elif case == "wrap":  # head at capacity-1, pushes wrap to slot 0
        size = rng.randint(0, cap - 1, n)
        tail = ((cap - 1 - size) % cap).astype(np.int32)
        valid = np.ones(n, bool)
    else:  # "push_pop": both handshakes asked in one cycle
        size = rng.randint(1, max(cap - 1, 2), n)
        valid, ready = np.ones(n, bool), np.ones(n, bool)
    head = ((tail + size) % cap).astype(np.int32)
    return buf, head, tail, valid, ready


@pytest.mark.parametrize("batched", [False, True], ids=["flat", "vmap"])
@pytest.mark.parametrize("case", _CASES)
@pytest.mark.parametrize("W", [1, 2])
@pytest.mark.parametrize("cap", [2, 8, 62])
def test_cycle_matches_numpy_reference(cap, W, case, batched):
    """``cycle`` equals a per-queue NumPy ring, bit for bit, over a seeded
    sequence of push/pop masks — flat, and under ``jax.vmap`` over a batch
    axis as ``GraphEngine`` calls it."""
    rng = np.random.RandomState(cap * 100 + W * 10 + _CASES.index(case))
    B, n, steps = (3 if batched else 1), 5, 2 * cap + 4
    starts = [_start(rng, case, n, cap, W) for _ in range(B)]
    buf, head, tail = (np.stack([s[k] for s in starts]) for k in range(3))
    valid0, ready0 = (np.stack([s[k] for s in starts]) for k in (3, 4))
    step = qmod.cycle
    if batched:
        step = jax.vmap(step)
    step = jax.jit(step)

    def squeeze(x):
        return x if batched else x[0]

    q = qmod.QueueArray(buf=jnp.asarray(squeeze(buf)),
                        head=jnp.asarray(squeeze(head)),
                        tail=jnp.asarray(squeeze(tail)), capacity=cap)
    for s in range(steps):
        payload = rng.standard_normal((B, n, W)).astype(np.float32)
        if s == 0:
            valid, ready = valid0, ready0
        else:
            valid, ready = rng.rand(B, n) < 0.6, rng.rand(B, n) < 0.4
        want = [_ref_cycle(buf[b], head[b], tail[b], cap, payload[b],
                           valid[b], ready[b]) for b in range(B)]
        buf, head, tail, did_push, did_pop = (
            np.stack([w[k] for w in want]) for k in range(5))
        q, got_push, got_pop = step(
            q, jnp.asarray(squeeze(payload)), jnp.asarray(squeeze(valid)),
            jnp.asarray(squeeze(ready)))
        for got, ref in ((q.buf, buf), (q.head, head), (q.tail, tail),
                         (got_push, did_push), (got_pop, did_pop)):
            np.testing.assert_array_equal(np.asarray(got), squeeze(ref))
        if s == 0 and case == "full":
            assert not did_push.any()
        if s == 0 and case == "empty":
            assert not did_pop.any()
        if s == 0 and case == "wrap":
            assert did_push.all() and (head == 0).all()
        if s == 0 and case == "push_pop" and cap > 2:
            assert did_push.all() and did_pop.all()
