"""SPSC queues as functional ring buffers (paper §III-B).

The paper's queue is a 4KB page: 4B head (next write), 4B tail (next read),
and 62 slots of 64B packets.  Semantics reproduced exactly:

  * write: ``next_head = (head+1) % capacity``; FULL if ``next_head == tail``;
    otherwise write slot ``head`` and advance.
  * read:  EMPTY if ``tail == head``; otherwise read slot ``tail`` and advance.

so a queue of capacity C holds at most C-1 packets — property-tested against
a Python deque oracle in ``tests/test_queue.py``.

The paper's *memory* optimizations (cached head/tail, separate cache lines,
acquire/release) are host-CPU coherence tricks with no TPU analogue; their
role — avoiding synchronization traffic on every packet — is played here by
*epoch batching*: queue state lives in device memory and producer/consumer
exchange head/tail information once per epoch, not per packet (DESIGN.md §2).

All operations are masked and batched: a ``QueueArray`` stores N queues with
stacked buffers so that a whole network's channels update in a handful of
fused XLA ops (the TPU-native equivalent of "queues are fast").
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .struct import pytree_dataclass, static_field

# Paper default: 62 packet slots per queue (4KB page / 64B packets).
DEFAULT_CAPACITY = 62


@pytree_dataclass
class QueueArray:
    """``n`` SPSC ring buffers with a shared capacity and payload width.

    buf:  (n, capacity, payload_words) payload storage
    head: (n,) int32 — next slot to write
    tail: (n,) int32 — next slot to read
    """

    buf: jax.Array
    head: jax.Array
    tail: jax.Array
    capacity: int = static_field(default=DEFAULT_CAPACITY)

    @property
    def n(self) -> int:
        return self.buf.shape[0]

    @property
    def payload_words(self) -> int:
        return self.buf.shape[2]


def make_queues(
    n: int,
    payload_words: int,
    capacity: int = DEFAULT_CAPACITY,
    dtype=jnp.float32,
) -> QueueArray:
    return QueueArray(
        buf=jnp.zeros((n, capacity, payload_words), dtype=dtype),
        head=jnp.zeros((n,), dtype=jnp.int32),
        tail=jnp.zeros((n,), dtype=jnp.int32),
        capacity=capacity,
    )


# --------------------------------------------------------------------------
# Occupancy queries (pre-cycle snapshot reads).
# --------------------------------------------------------------------------

def size(q: QueueArray) -> jax.Array:
    """(n,) number of packets currently enqueued."""
    return (q.head - q.tail) % q.capacity


def free(q: QueueArray) -> jax.Array:
    """(n,) number of packets that can still be pushed (capacity-1 max)."""
    return (q.capacity - 1) - size(q)


def empty(q: QueueArray) -> jax.Array:
    return q.head == q.tail


def full(q: QueueArray) -> jax.Array:
    return (q.head + 1) % q.capacity == q.tail


def peek(q: QueueArray) -> tuple[jax.Array, jax.Array]:
    """Front packet of every queue: ((n, W) payload, (n,) valid)."""
    payload = jnp.take_along_axis(q.buf, q.tail[:, None, None], axis=1)[:, 0, :]
    return payload, ~empty(q)


# --------------------------------------------------------------------------
# Single-cycle handshake update (paper §II-A bridge semantics).
# --------------------------------------------------------------------------

def _push_one(buf, head, payload, do_push):
    """Write ``payload`` at slot ``head`` of one queue's buffer if do_push."""
    cur = jax.lax.dynamic_index_in_dim(buf, head, axis=0, keepdims=False)
    row = jnp.where(do_push, payload, cur)
    return jax.lax.dynamic_update_index_in_dim(buf, row, head, axis=0)


def cycle(
    q: QueueArray,
    push_payload: jax.Array,
    push_valid: jax.Array,
    pop_ready: jax.Array,
) -> tuple[QueueArray, jax.Array, jax.Array]:
    """Apply one simulation cycle of handshakes to all queues at once.

    Per queue: the producer drives ``(push_payload, push_valid)`` and sees
    ``ready = ~full`` (pre-cycle); the consumer sees ``(front, ~empty)``
    (pre-cycle) and drives ``pop_ready``.  Both handshakes may fire in the
    same cycle — SPSC push touches ``head``, pop touches ``tail``, so they
    commute, exactly as in the shared-memory implementation.

    The push is one dense select over the capacity axis, not a batched
    dynamic update: XLA:TPU lowers the latter (and any scatter) to a
    scalar loop with one trip per queue.

    Returns (new_queues, did_push, did_pop).
    """
    do_push = push_valid & ~full(q)
    do_pop = pop_ready & ~empty(q)

    slot = (jnp.arange(q.capacity, dtype=q.head.dtype)[None, :, None]
            == q.head[:, None, None])
    buf = jnp.where(
        slot & do_push[:, None, None],
        push_payload[:, None, :].astype(q.buf.dtype),
        q.buf,
    )
    head = jnp.where(do_push, (q.head + 1) % q.capacity, q.head)
    tail = jnp.where(do_pop, (q.tail + 1) % q.capacity, q.tail)
    return q.replace(buf=buf, head=head, tail=tail), do_push, do_pop


# --------------------------------------------------------------------------
# Single-queue host-side handshakes (external-port I/O). Same ring
# conventions as ``cycle`` but for one queue's raw (capacity, W) storage, so
# engines never re-implement the head/tail arithmetic.
# --------------------------------------------------------------------------

def push_single(buf, head, tail, capacity, payload):
    """Push ``payload`` into one queue. Returns (buf, head, did_push)."""
    ok = (head + 1) % capacity != tail
    buf = _push_one(buf, head, payload, ok)
    return buf, jnp.where(ok, (head + 1) % capacity, head), ok


def pop_single(buf, head, tail, capacity):
    """Pop one queue's front. Returns (front, tail, did_pop)."""
    valid = head != tail
    front = jax.lax.dynamic_index_in_dim(buf, tail, axis=0, keepdims=False)
    return front, jnp.where(valid, (tail + 1) % capacity, tail), valid


def fill_single(buf, head, tail, capacity, payloads, limit=None):
    """Push up to ``len(payloads)`` packets into one queue (host batch I/O).

    payloads: (k, W) with k <= capacity-1.  Packets beyond the queue's free
    space are NOT written (the host-side caller keeps them buffered — the
    session's host-tier "credit").  ``limit`` optionally caps the count
    further (a traced scalar: the multiprocess runtime passes the shm
    ring's record count so padding rows never land).  Returns
    (buf, head, n_pushed).
    """
    k = payloads.shape[0]
    if k > capacity - 1:
        raise ValueError(f"fill_single: {k} packets > capacity-1={capacity - 1}")
    n_free = (capacity - 1) - (head - tail) % capacity
    count = jnp.minimum(jnp.int32(k), n_free.astype(jnp.int32))
    if limit is not None:
        count = jnp.minimum(count, jnp.asarray(limit, jnp.int32))
    offs = jnp.arange(k, dtype=jnp.int32)
    idx = (head + offs) % capacity
    cur = buf[idx]
    rows = jnp.where((offs < count)[:, None], payloads, cur)
    buf = buf.at[idx].set(rows, mode="promise_in_bounds", unique_indices=True)
    return buf, (head + count) % capacity, count


def drain_single(buf, head, tail, capacity, max_n: int, limit=None):
    """Pop up to ``max_n`` packets from one queue (host batch I/O).

    ``limit`` optionally caps the count further (a traced scalar: the shm
    ring's free space in the multiprocess runtime, so a flush never
    overruns the host-facing ring).  Returns (payloads (max_n, W), tail,
    count); rows beyond ``count`` are stale and must be masked by the
    caller.
    """
    n_avail = (head - tail) % capacity
    count = jnp.minimum(n_avail, max_n).astype(jnp.int32)
    if limit is not None:
        count = jnp.minimum(count, jnp.asarray(limit, jnp.int32))
    offs = jnp.arange(max_n, dtype=jnp.int32)
    idx = (tail + offs) % capacity
    return buf[idx], (tail + count) % capacity, count


# --------------------------------------------------------------------------
# Host-port operations on one queue of a QueueArray, addressed by ``idx``
# (an int row for the single netlist, a (dev..., local) tuple for the
# distributed engines).  Every engine's external-port surface routes
# through these four, so the ring/truncation logic lives exactly once.
# --------------------------------------------------------------------------

def host_push(q: QueueArray, idx, payload):
    """Push one packet into queue ``idx``.  Returns (queues, did_push)."""
    buf, head, ok = push_single(
        q.buf[idx], q.head[idx], q.tail[idx], q.capacity, payload
    )
    return q.replace(
        buf=q.buf.at[idx].set(buf), head=q.head.at[idx].set(head)
    ), ok


def host_pop(q: QueueArray, idx):
    """Pop queue ``idx``'s front.  Returns (queues, front, valid)."""
    front, tail, valid = pop_single(
        q.buf[idx], q.head[idx], q.tail[idx], q.capacity
    )
    return q.replace(tail=q.tail.at[idx].set(tail)), front, valid


def host_push_many(q: QueueArray, idx, payloads):
    """Batched push into queue ``idx``: what fits lands, the rest is
    refused (count returned) — oversize batches are truncated to the ring
    maximum of capacity-1, never an error.  Returns (queues, n_pushed)."""
    payloads = payloads[: q.capacity - 1]
    buf, head, n = fill_single(
        q.buf[idx], q.head[idx], q.tail[idx], q.capacity, payloads
    )
    return q.replace(
        buf=q.buf.at[idx].set(buf), head=q.head.at[idx].set(head)
    ), n


def host_pop_many(q: QueueArray, idx, max_n: int):
    """Batched pop from queue ``idx``.  Returns (queues, payloads
    (max_n, W), count); rows beyond count are stale."""
    pays, tail, cnt = drain_single(
        q.buf[idx], q.head[idx], q.tail[idx], q.capacity, max_n
    )
    return q.replace(tail=q.tail.at[idx].set(tail)), pays, cnt


# --------------------------------------------------------------------------
# Epoch (bulk) operations — used by the distributed exchange. These move up
# to ``max_n`` packets in one fused op, amortizing inter-device traffic over
# many packets (the paper's "queues are unlikely to be a bottleneck" claim,
# restated for ICI).
# --------------------------------------------------------------------------

def drain(q: QueueArray, max_n: int, limit: jax.Array | None = None):
    """Pop up to ``max_n`` packets from each queue.

    limit: optional (n,) per-queue cap (credit count from the receiver).
    Returns (new_queues, payloads (n, max_n, W), count (n,)).
    Slots beyond ``count`` contain stale data; consumers must mask by count.
    """
    n_avail = size(q)
    count = jnp.minimum(n_avail, max_n).astype(jnp.int32)
    if limit is not None:
        count = jnp.minimum(count, limit.astype(jnp.int32))
    offs = jnp.arange(max_n, dtype=jnp.int32)  # (max_n,)
    idx = (q.tail[:, None] + offs[None, :]) % q.capacity  # (n, max_n)
    payloads = jnp.take_along_axis(q.buf, idx[:, :, None], axis=1)  # (n,max_n,W)
    tail = (q.tail + count) % q.capacity
    return q.replace(tail=tail), payloads, count


def _fill_one(buf, head, payloads, count, capacity):
    """Push ``count`` rows of ``payloads`` into one queue at ``head``."""
    max_n = payloads.shape[0]
    offs = jnp.arange(max_n, dtype=jnp.int32)
    idx = (head + offs) % capacity  # (max_n,)
    mask = offs < count
    cur = buf[idx]  # gather (max_n, W)
    rows = jnp.where(mask[:, None], payloads, cur)
    return buf.at[idx].set(rows, mode="promise_in_bounds", unique_indices=max_n <= capacity)


def fill(q: QueueArray, payloads: jax.Array, count: jax.Array) -> QueueArray:
    """Push ``count[i]`` packets from ``payloads[i]`` into queue i.

    Caller must guarantee ``count <= free(q)`` (the credit protocol in
    ``distributed.py`` does).  Counts are clamped defensively anyway.
    """
    max_n = payloads.shape[1]
    if max_n > q.capacity - 1:
        # A wrap-around of the scatter index window could alias masked
        # (write-back) slots onto real writes, whose ordering is unspecified.
        raise ValueError(
            f"fill: max_n={max_n} must be <= capacity-1={q.capacity - 1}"
        )
    count = jnp.minimum(count.astype(jnp.int32), free(q))
    buf = jax.vmap(lambda b, h, p, c: _fill_one(b, h, p, c, q.capacity))(
        q.buf, q.head, payloads, count
    )
    head = (q.head + count) % q.capacity
    return q.replace(buf=buf, head=head)


def stage_drain(
    q: QueueArray, idx: jax.Array, max_n: int,
    limit: jax.Array | None = None,
):
    """Drain up to ``max_n`` packets from queue rows ``idx`` into a slab.

    The tier-exchange staging primitive: one gather selects the egress
    rows, one bulk :func:`drain` empties them into a contiguous
    ``(len(idx), max_n, W)`` slab (credit-bounded when ``limit`` is
    given), and only the selected rows' tails advance.  Rows whose count
    resolves to 0 write back their original tail, so padding ``idx``
    entries (masked by a 0 ``limit``) are harmless even when duplicated.
    Returns ``(new_q, slab, count)``.
    """
    sub = QueueArray(
        buf=q.buf[idx], head=q.head[idx], tail=q.tail[idx],
        capacity=q.capacity,
    )
    sub2, slab, count = drain(sub, max_n, limit=limit)
    return q.replace(tail=q.tail.at[idx].set(sub2.tail)), slab, count


def stage_fill(
    q: QueueArray, idx: jax.Array, payloads: jax.Array, count: jax.Array,
) -> QueueArray:
    """Land a slab into queue rows ``idx`` — the inverse of
    :func:`stage_drain`.

    ``payloads``: (len(idx), max_n, W); ``count``: (len(idx),).  Rows with
    ``count == 0`` are written back unchanged, so duplicate padding
    indices are harmless.
    """
    sub = QueueArray(
        buf=q.buf[idx], head=q.head[idx], tail=q.tail[idx],
        capacity=q.capacity,
    )
    sub2 = fill(sub, payloads, count)
    return q.replace(
        buf=q.buf.at[idx].set(sub2.buf),
        head=q.head.at[idx].set(sub2.head),
    )
