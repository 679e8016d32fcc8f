"""A minimal latency-insensitive pipeline stage (paper §II-A's "DUT").

The simplest useful Block: forward the inbound packet, adding ``delta``
to word 0, under a full ready/valid handshake.  One block type, arbitrary
chain/ring topologies — the unit cell for host-I/O scenarios, the
engine-parity tests, and the multiprocess runtime (its workers unpickle
the block by reference, so it lives in the package, not in a script).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.block import Block
from ..core.network import Network
from ..core.struct import pytree_dataclass


@pytree_dataclass
class PipeStageState:
    count: jax.Array  # () int32 — handshakes forwarded


class PipeStage(Block):
    """Forward ``in`` -> ``out``, adding ``delta`` to word 0 on the way."""

    in_ports = ("in",)
    out_ports = ("out",)
    payload_words = 2

    def __init__(self, delta: float = 1.0):
        self.delta = float(delta)

    def init_state(self, key):
        return PipeStageState(count=jnp.zeros((), jnp.int32))

    def step(self, state, rx, tx_ready):
        pay, valid = rx["in"]
        fire = valid & tx_ready["out"]
        return (
            state.replace(count=state.count + fire.astype(jnp.int32)),
            {"in": fire},
            {"out": (pay.at[0].add(self.delta), fire)},
        )


def make_chain(n: int, capacity: int = 8, delta: float = 1.0) -> Network:
    """n-stage chain with host ports "tx" (into stage 0) and "rx" (out of
    stage n-1) — the canonical host-I/O scenario."""
    net = Network(payload_words=2, capacity=capacity)
    blk = PipeStage(delta)
    insts = [net.instantiate(blk, name=f"s{i}") for i in range(n)]
    net.external_in(insts[0]["in"], "tx")
    for a, b in zip(insts, insts[1:]):
        net.connect(a["out"], b["in"])
    net.external_out(insts[-1]["out"], "rx")
    return net


def make_ring(n: int, capacity: int = 8, delta: float = 1.0) -> Network:
    """n-stage closed ring — one block type, perfectly uniform topology
    (every granule of a one-stage-per-worker partition has the same
    compiled shape: the prebuilt-cache build-time scenario)."""
    net = Network(payload_words=2, capacity=capacity)
    blk = PipeStage(delta)
    insts = [net.instantiate(blk, name=f"s{i}") for i in range(n)]
    for i in range(n):
        net.connect(insts[i]["out"], insts[(i + 1) % n]["in"])
    return net
