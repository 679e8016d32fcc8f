"""Step builders shared by dryrun.py and train.py.

Builds jitted train/prefill/decode steps for an (arch config, shape,
mesh, strategy) cell, with all in/out shardings resolved from
``sharding.partition`` rules.  Everything here works on either concrete
arrays or ShapeDtypeStructs (dry-run).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import model as M
from ..models.config import ModelConfig
from ..optim.optimizer import AdamW
from ..sharding import partition as SP
from ..configs.registry import ShapeSpec

PyTree = Any


# ------------------------------------------------------------- abstractions
def abstract_params(cfg: ModelConfig) -> PyTree:
    return jax.eval_shape(lambda: M.init_params(cfg, jax.random.key(0)))


def abstract_opt_state(cfg: ModelConfig, opt: AdamW, params_shapes: PyTree) -> PyTree:
    return jax.eval_shape(opt.init, params_shapes)


def abstract_batch(cfg: ModelConfig, shape: ShapeSpec) -> dict:
    b, s = shape.global_batch, shape.seq_len
    if cfg.input_mode == "embeddings":
        inputs = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.dtype(cfg.dtype))
    else:
        inputs = jax.ShapeDtypeStruct((b, s), jnp.int32)
    return {"inputs": inputs, "labels": jax.ShapeDtypeStruct((b, s), jnp.int32)}


def abstract_decode_state(cfg: ModelConfig, batch: int, max_seq: int) -> PyTree:
    return jax.eval_shape(lambda: M.init_decode_state(cfg, batch, max_seq))


# ------------------------------------------------------------- train step
def make_train_step(cfg: ModelConfig, opt: AdamW, constrain):
    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(M.loss_fn, has_aux=True)(
            params, cfg, batch, constrain
        )
        new_params, new_opt_state, om = opt.update(grads, opt_state, params)
        metrics = dict(metrics)
        metrics.update(om)
        metrics["loss"] = loss
        return new_params, new_opt_state, metrics

    return train_step


def jit_train_step(
    cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh, strategy: SP.Strategy,
    opt: AdamW | None = None, donate: bool = True,
):
    opt = opt or AdamW()
    constrain = SP.make_constrain(strategy, mesh, seq_len=shape.seq_len)
    step = make_train_step(cfg, opt, constrain)

    p_shapes = abstract_params(cfg)
    o_shapes = abstract_opt_state(cfg, opt, p_shapes)
    p_sh = SP.named_shardings(p_shapes, strategy, mesh)
    o_sh = _opt_shardings(o_shapes, p_sh, mesh)
    b_specs = SP.batch_specs(cfg, shape, strategy, mesh)
    b_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), b_specs,
                        is_leaf=lambda x: isinstance(x, P))

    jitted = jax.jit(
        step,
        in_shardings=(p_sh, o_sh, b_sh),
        out_shardings=(p_sh, o_sh, None),
        donate_argnums=(0, 1) if donate else (),
    )
    abstract = (p_shapes, o_shapes, abstract_batch(cfg, shape))
    return jitted, abstract


def _opt_shardings(opt_shapes, param_shardings, mesh):
    """Adam moments shard like their params; step counter replicated."""
    from ..optim.optimizer import AdamWState

    rep = NamedSharding(mesh, P())
    return AdamWState(step=rep, mu=param_shardings, nu=param_shardings)


# ------------------------------------------------------------- serve steps
def jit_prefill(cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh, strategy: SP.Strategy):
    constrain = SP.make_constrain(strategy, mesh, seq_len=shape.seq_len)
    b, s = shape.global_batch, shape.seq_len

    def prefill_step(params, inputs):
        return M.prefill(params, cfg, inputs, max_seq=s, constrain=constrain)

    p_shapes = abstract_params(cfg)
    p_sh = SP.named_shardings(p_shapes, strategy, mesh)
    st_shapes = abstract_decode_state(cfg, b, s)
    st_specs = SP.decode_state_specs(st_shapes, cfg, strategy, mesh)
    st_sh = jax.tree.map(lambda sp: NamedSharding(mesh, sp), st_specs,
                         is_leaf=lambda x: isinstance(x, P))
    dpb = SP._div(b, strategy.dp, mesh)
    if cfg.input_mode == "embeddings":
        in_sh = NamedSharding(mesh, P(dpb, None, None))
        inputs = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.dtype(cfg.dtype))
    else:
        in_sh = NamedSharding(mesh, P(dpb, None))
        inputs = jax.ShapeDtypeStruct((b, s), jnp.int32)

    jitted = jax.jit(
        prefill_step,
        in_shardings=(p_sh, in_sh),
        out_shardings=(st_sh, None),
    )
    return jitted, (p_shapes, inputs)


def jit_decode_step(cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh, strategy: SP.Strategy):
    constrain = SP.make_constrain(strategy, mesh)
    b, s = shape.global_batch, shape.seq_len

    def serve_step(params, states, token, pos):
        return M.decode_step(params, cfg, states, token, pos, constrain=constrain)

    p_shapes = abstract_params(cfg)
    p_sh = SP.named_shardings(p_shapes, strategy, mesh)
    st_shapes = abstract_decode_state(cfg, b, s)
    st_specs = SP.decode_state_specs(st_shapes, cfg, strategy, mesh)
    st_sh = jax.tree.map(lambda sp: NamedSharding(mesh, sp), st_specs,
                         is_leaf=lambda x: isinstance(x, P))
    dpb = SP._div(b, strategy.dp, mesh)
    tok_sh = NamedSharding(mesh, P(dpb))

    jitted = jax.jit(
        serve_step,
        in_shardings=(p_sh, st_sh, tok_sh, NamedSharding(mesh, P())),
        out_shardings=(st_sh, None),
        donate_argnums=(1,),
    )
    abstract = (
        p_shapes,
        st_shapes,
        jax.ShapeDtypeStruct((b,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
    )
    return jitted, abstract


def lower_cell(cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh, strategy: SP.Strategy):
    """Lower the right step for a cell; returns (lowered, kind)."""
    with jax.default_device(jax.devices()[0]):
        if shape.step == "train":
            jitted, abstract = jit_train_step(cfg, shape, mesh, strategy)
            return jitted.lower(*abstract), "train_step"
        if shape.step == "prefill":
            jitted, abstract = jit_prefill(cfg, shape, mesh, strategy)
            return jitted.lower(*abstract), "prefill"
        jitted, abstract = jit_decode_step(cfg, shape, mesh, strategy)
        return jitted.lower(*abstract), "serve_step"
