"""jit'd train-step factory: loss -> grads -> (clip) -> AdamW, with
microbatch gradient accumulation and explicit in/out shardings.

ZeRO placement (paper's hierarchical-ZeRO adapted to GSPMD):
  zero="none"   params+opt replicated over data axes (pure DP)
  zero="zero1"  params replicated, m/v sharded over data axes
  zero="zero3"  params+opt sharded over data axes (FSDP)
  zero="zero3_hier"  like zero3 but sharded over the pod-local axis only
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import ParallelConfig, TrainConfig
from repro.models import Model
from repro.models.spec import abstract_params
from repro.sharding import Rules, make_rules, tree_shardings
from repro.train.optimizer import (AdamState, adamw_abstract, adamw_init,
                                   adamw_update, compress_grads,
                                   compressor_init)
from repro.utils.profiling import scope

Params = Any


# ---------------------------------------------------------------------------
# sharding helpers
# ---------------------------------------------------------------------------

def opt_rules(mesh: Mesh, parallel: ParallelConfig) -> Rules:
    """Rules for optimizer state: ZeRO-1 shards opt even when params aren't."""
    if parallel.zero == "zero1":
        return make_rules(mesh, dataclasses.replace(parallel, zero="zero3"))
    return make_rules(mesh, parallel)


def state_shardings(model: Model, mesh: Mesh, parallel: ParallelConfig):
    """(param_shardings, opt_shardings) NamedSharding trees."""
    specs = model.specs()
    prules = make_rules(mesh, parallel)
    orules = opt_rules(mesh, parallel)
    p_sh = tree_shardings(prules, specs)
    m_sh = tree_shardings(orules, specs)
    opt_sh = AdamState(m=m_sh, v=m_sh,
                       step=NamedSharding(mesh, P()))
    return p_sh, opt_sh


def batch_shardings(mesh: Mesh, parallel: ParallelConfig, batch_tree: Any):
    """Shard every batch leaf's leading dim over the data axes."""
    rules = make_rules(mesh, parallel)

    def one(x):
        axes = ("batch",) + (None,) * (len(x.shape) - 1)
        return rules.sharding(x.shape, axes)

    return jax.tree_util.tree_map(one, batch_tree)


def abstract_batch(model: Model, batch_size: int, seq_len: int) -> dict:
    """ShapeDtypeStruct stand-ins for a training batch."""
    cfg = model.cfg
    b: dict = {
        "tokens": jax.ShapeDtypeStruct((batch_size, seq_len), jnp.int32),
        "labels": jax.ShapeDtypeStruct((batch_size, seq_len), jnp.int32),
        "weights": jax.ShapeDtypeStruct((batch_size, seq_len), jnp.float32),
    }
    if cfg.frontend == "patch_stub":
        b["patches"] = jax.ShapeDtypeStruct(
            (batch_size, cfg.num_patches, cfg.d_model), jnp.float32)
    if cfg.frontend == "audio_stub":
        b["frames"] = jax.ShapeDtypeStruct(
            (batch_size, cfg.encoder_seq, cfg.d_model), jnp.float32)
    return b


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------

def make_train_step(model: Model, tcfg: TrainConfig,
                    grad_shardings: Any = None) -> Callable:
    """Returns step(params, opt_state, batch) -> (params, opt_state, metrics).

    Pure function; jit with shardings via ``compile_train_step``.
    ``grad_shardings``: optional NamedSharding tree pinned onto the gradient
    tree right after autodiff — tells GSPMD to reduce each gradient straight
    into its ZeRO shard (reduce-scatter) instead of materializing a
    replicated all-reduce first.
    """
    bf16_grads = model.parallel.grad_dtype == "bfloat16"

    def _pin(grads):
        if grad_shardings is None:
            return grads
        return jax.tree_util.tree_map(
            jax.lax.with_sharding_constraint, grads, grad_shardings)

    def loss_fn(p, mb):
        loss, metrics = model.loss(p, mb)
        return loss, metrics

    if bf16_grads:
        # differentiate w.r.t. the bf16 cast of the params: gradients (and
        # their cross-device reductions) materialize in bf16; AdamW applies
        # them to the fp32 master copies.
        from repro.utils import cast_floating
        _grad = jax.value_and_grad(
            lambda pc, mb: loss_fn(pc, mb), has_aux=True)

        def grad_fn(p, mb):
            out, g = _grad(cast_floating(p, jnp.bfloat16), mb)
            return out, g
    else:
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def step(params, opt_state, batch):
        k = tcfg.microbatches
        if k > 1:
            mbs = jax.tree_util.tree_map(
                lambda x: x.reshape((k, x.shape[0] // k) + x.shape[1:]),
                batch)

            def body(acc, mb):
                (loss, metrics), grads = grad_fn(params, mb)
                acc = jax.tree_util.tree_map(jnp.add, acc, grads)
                return acc, metrics

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            grads, metrics_all = jax.lax.scan(body, zeros, mbs)
            grads = _pin(jax.tree_util.tree_map(lambda g: g / k, grads))
            metrics = jax.tree_util.tree_map(lambda m: m.mean(), metrics_all)
        else:
            (loss, metrics), grads = grad_fn(params, batch)
            grads = _pin(grads)

        with scope("optimizer"):
            new_params, new_opt, opt_metrics = adamw_update(
                grads, opt_state, params, tcfg)
        metrics.update(opt_metrics)
        return new_params, new_opt, metrics

    return step


def compile_train_step(model: Model, tcfg: TrainConfig, mesh: Mesh,
                       parallel: ParallelConfig, *,
                       batch_size: Optional[int] = None,
                       seq_len: Optional[int] = None,
                       lower_only: bool = False,
                       donate: bool = True):
    """Lower (and optionally compile) the train step with full shardings.

    Returns (fn_or_lowered, param_shardings, opt_shardings, batch_shardings).
    """
    bs = batch_size or tcfg.global_batch
    sl = seq_len or tcfg.seq_len
    p_sh, o_sh = state_shardings(model, mesh, parallel)
    ab = abstract_batch(model, bs, sl)
    b_sh = batch_shardings(mesh, parallel, ab)
    step = make_train_step(model, tcfg, grad_shardings=p_sh)
    jitted = jax.jit(
        step,
        in_shardings=(p_sh, o_sh, b_sh),
        out_shardings=(p_sh, o_sh, None),
        donate_argnums=(0, 1) if donate else (),
    )
    if lower_only:
        abstract_p = abstract_params(model.specs(), p_sh)
        abstract_o = _abstract_opt(abstract_p, o_sh)
        ab_sharded = jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            ab, b_sh)
        with mesh:
            lowered = jitted.lower(abstract_p, abstract_o, ab_sharded)
        return lowered, p_sh, o_sh, b_sh
    return jitted, p_sh, o_sh, b_sh


def _abstract_opt(abstract_p, o_sh) -> AdamState:
    m = jax.tree_util.tree_map(
        lambda p, sh: jax.ShapeDtypeStruct(p.shape, jnp.float32, sharding=sh),
        abstract_p, o_sh.m)
    v = jax.tree_util.tree_map(
        lambda p, sh: jax.ShapeDtypeStruct(p.shape, jnp.float32, sharding=sh),
        abstract_p, o_sh.v)
    return AdamState(m=m, v=v,
                     step=jax.ShapeDtypeStruct((), jnp.int32,
                                               sharding=o_sh.step))
