"""Pipeline parallelism as a Switchboard network (DESIGN.md §3).

The paper's modular-decomposition idea applied to model execution: pipeline
*stages are blocks*, the stage-to-stage activation stream is a
*latency-insensitive channel*, and the schedule is the same epoch-batched
dataflow as ``core.distributed`` — a GPipe-style fill/drain wavefront where
each tick moves one microbatch one hop via ``ppermute`` (the channel) and
computes where a microbatch is present (the ready/valid handshake; idle
stages are masked, which is exactly a de-asserted ``valid``).

Intended placement: the ``pod`` axis (DCI) — stage cuts are where the paper
put its TCP bridges, because the channel tolerates the extra latency.

The backward schedule needs no extra code: ``jax.grad`` through the
``shard_map``-ed tick scan reverses the permutes, yielding the mirrored
drain/fill wavefront automatically (verified equal to the unpipelined
reference in tests/test_pipeline.py).
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .compat import shard_map

PyTree = Any


class Pipeline:
    """Run ``stage_fn`` as an S-stage pipeline over mesh axis ``axis``.

    stage_fn(stage_params, h) -> h' must be shape-preserving across stages
    (the classic homogeneous-stage pipeline; embed/head live outside).
    Stage s holds ``params[s]`` (leaves stacked on a leading S dim).
    """

    def __init__(self, stage_fn: Callable, mesh: Mesh, axis: str = "stage"):
        self.stage_fn = stage_fn
        self.mesh = mesh
        self.axis = axis
        self.S = mesh.shape[axis]

    def __call__(self, stage_params: PyTree, x: jax.Array) -> jax.Array:
        """x: (M, mb, d) microbatches; returns (M, mb, d) outputs."""
        S, axis = self.S, self.axis
        M = x.shape[0]
        n_ticks = M + S - 1
        fwd_perm = [(s, s + 1) for s in range(S - 1)]

        def run(params, x):
            params = jax.tree.map(lambda p: p[0], params)  # local stage params
            sid = jax.lax.axis_index(axis)
            mb_shape = x.shape[1:]

            def tick(carry, t):
                h, outbuf = carry
                # channel hop: previous stage's output arrives (stage 0
                # receives zeros = invalid, and instead loads microbatch m).
                h_in = jax.lax.ppermute(h, axis, fwd_perm) if fwd_perm else h
                m = t - sid  # microbatch index at this stage this tick
                feed = jnp.clip(t, 0, M - 1)
                h_in = jnp.where(sid == 0, x[feed], h_in)
                active = (m >= 0) & (m < M)
                h_out = self.stage_fn(params, h_in)
                h_out = jnp.where(active, h_out, jnp.zeros_like(h_out))
                # last stage collects finished microbatches
                collect = active & (sid == S - 1)
                outbuf = jnp.where(
                    collect,
                    jax.lax.dynamic_update_index_in_dim(
                        outbuf, h_out, jnp.clip(m, 0, M - 1), axis=0
                    ),
                    outbuf,
                )
                return (h_out, outbuf), None

            h0 = jnp.zeros(mb_shape, x.dtype)
            out0 = jnp.zeros((M,) + mb_shape, x.dtype)
            (_, outbuf), _ = jax.lax.scan(
                tick, (h0, out0), jnp.arange(n_ticks)
            )
            # only stage S-1 holds real outputs; psum broadcasts them.
            outbuf = jnp.where(sid == S - 1, outbuf, jnp.zeros_like(outbuf))
            return jax.lax.psum(outbuf, axis)

        return shard_map(
            run,
            mesh=self.mesh,
            in_specs=(P(self.axis), P()),
            out_specs=P(),
        )(stage_params, x)


def stage_shardings(mesh: Mesh, params_stacked: PyTree, axis: str = "stage") -> PyTree:
    sh = NamedSharding(mesh, P(axis))
    return jax.tree.map(lambda _: sh, params_stacked)
