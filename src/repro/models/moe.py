"""Mixture-of-Experts FFN with three execution paths.

1. ``gshard``  — expert parallelism over the ``model`` mesh axis via
   shard_map + lax.all_to_all (GShard/Switch dispatch adapted to TPU: tokens
   are sequence-sharded across the model axis, scattered into per-expert
   capacity buffers, exchanged with a single all-to-all, processed with one
   dense batched matmul per shard (MXU-friendly), and combined with the
   reverse all-to-all). Used when num_experts % model_axis == 0.

2. ``tp``      — expert-tensor-parallel grouped matmul: every model shard
   holds an eff-slice of *all* experts, dispatches its data-shard's tokens
   locally into (E, C, d) capacity buffers and computes a batched matmul with
   its slice; partial outputs are psum-reduced over the model axis. No
   all-to-all; works for any expert count (e.g. mixtral's 8 experts on a
   16-wide model axis). FLOPs stay ~active (capacity-bounded), unlike a
   dense all-experts evaluation.

3. ``dense``   — evaluate all experts and combine with routing weights.
   Exact (no capacity drops); used for tiny smoke tests and as the decode
   path where weight reads, not FLOPs, dominate.

All paths share the router; dropped-token behaviour is capacity-based with
renormalized top-k gates (tokens past capacity fall through on the residual).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.config import ModelConfig, MoEConfig, ParallelConfig
from repro.models.layers import _act, mlp, mlp_specs
from repro.models.spec import ParamSpec
from repro.sharding import MODEL, Rules, data_axes

Params = Any


# ---------------------------------------------------------------------------
# specs
# ---------------------------------------------------------------------------

def moe_specs(d_model: int, cfg: MoEConfig, act: str) -> dict:
    E, eff = cfg.num_experts, cfg.expert_ff
    glu = act.endswith("_glu")
    s_in, s_out = d_model ** -0.5, eff ** -0.5
    specs = {
        "router": ParamSpec((d_model, E), ("embed", None), stddev=s_in),
        "w1": ParamSpec((E, d_model, eff), ("experts", "embed", "expert_mlp"),
                        stddev=s_in),
        "w2": ParamSpec((E, eff, d_model), ("experts", "expert_mlp", "embed"),
                        stddev=s_out),
    }
    if glu:
        specs["w3"] = ParamSpec((E, d_model, eff),
                                ("experts", "embed", "expert_mlp"),
                                stddev=s_in)
    if cfg.num_shared_experts:
        specs["shared"] = mlp_specs(d_model, cfg.num_shared_experts * eff, act)
    return specs


# ---------------------------------------------------------------------------
# router + local capacity dispatch (shared by gshard/tp paths)
# ---------------------------------------------------------------------------

def _route(router_w: jax.Array, x: jax.Array, cfg: MoEConfig):
    """x: (T, d) -> (gates (T,k), expert_idx (T,k), aux_loss, probs (T,E))."""
    logits = (x @ router_w.astype(x.dtype)).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)                 # (T, k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    return gates, idx, probs


def _aux_loss(probs: jax.Array, idx: jax.Array, E: int) -> jax.Array:
    """Switch-style load-balancing loss: E * sum_e f_e * P_e."""
    assign = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(axis=1)  # (T, E)
    f = assign.mean(axis=0)
    p = probs.mean(axis=0)
    return E * jnp.sum(f * p)


def _dispatch_indices(idx: jax.Array, E: int, C: int):
    """Position-in-expert for each (token, choice); >=C means dropped."""
    T, k = idx.shape
    flat = idx.reshape(-1)                                   # (T*k,)
    onehot = jax.nn.one_hot(flat, E, dtype=jnp.int32)        # (T*k, E)
    pos_all = jnp.cumsum(onehot, axis=0) - onehot            # prior count
    pos = jnp.take_along_axis(pos_all, flat[:, None], axis=1)[:, 0]
    keep = pos < C
    slot = jnp.where(keep, flat * C + pos, E * C)            # OOB -> dropped
    return slot.reshape(T, k), keep.reshape(T, k)


def _scatter_tokens(x: jax.Array, slot: jax.Array, E: int, C: int):
    """x: (T, d), slot: (T, k) -> buffer (E, C, d)."""
    T, d = x.shape
    k = slot.shape[1]
    buf = jnp.zeros((E * C, d), x.dtype)
    xk = jnp.broadcast_to(x[:, None, :], (T, k, d)).reshape(T * k, d)
    buf = buf.at[slot.reshape(-1)].add(xk, mode="drop")
    return buf.reshape(E, C, d)


def _gather_tokens(buf: jax.Array, slot: jax.Array, gates: jax.Array,
                   keep: jax.Array, dtype) -> jax.Array:
    """buffer (E, C, d), slot (T, k) -> (T, d) combined output."""
    E, C, d = buf.shape
    T, k = slot.shape
    flat = buf.reshape(E * C, d)
    out = jnp.take(flat, jnp.clip(slot.reshape(-1), 0, E * C - 1), axis=0)
    out = out.reshape(T, k, d)
    w = (gates * keep).astype(dtype)
    return jnp.einsum("tkd,tk->td", out, w)


def _expert_ffn(xb: jax.Array, w1, w2, w3, glu: bool, act: str,
                dtype) -> jax.Array:
    """Batched-over-experts FFN. xb: (E, C, d)."""
    h = jnp.einsum("ecd,edf->ecf", xb, w1.astype(dtype))
    h = _act(act, h)
    if glu:
        h = h * jnp.einsum("ecd,edf->ecf", xb, w3.astype(dtype))
    return jnp.einsum("ecf,efd->ecd", h, w2.astype(dtype))


# ---------------------------------------------------------------------------
# path 1: gshard (EP over model axis, all-to-all)
# ---------------------------------------------------------------------------

def _gshard_local(cfg: MoEConfig, act: str, dtype, C: int, glu: bool,
                  axis_names: tuple, router_w, w1, w2, w3, x):
    """Per-device body under shard_map. x: (B_loc, S_loc, d)."""
    B, S, d = x.shape
    E = cfg.num_experts
    xt = x.reshape(B * S, d)
    gates, idx, probs = _route(router_w, xt, cfg)
    aux = jax.lax.pmean(_aux_loss(probs, idx, E), axis_name=axis_names)
    slot, keep = _dispatch_indices(idx, E, C)
    buf = _scatter_tokens(xt, slot, E, C)                    # (E, C, d)
    # exchange: every model shard keeps E_loc experts, receives M chunks
    buf = jax.lax.all_to_all(buf, MODEL, split_axis=0, concat_axis=1,
                             tiled=True)                     # (E_loc, C*M, d)
    out = _expert_ffn(buf, w1, w2, w3, glu, act, dtype)
    out = jax.lax.all_to_all(out, MODEL, split_axis=1, concat_axis=0,
                             tiled=True)                     # (E, C, d)
    y = _gather_tokens(out, slot, gates, keep, dtype)
    return y.reshape(B, S, d), aux


def moe_gshard(params: Params, cfg: MoEConfig, x: jax.Array, *,
               rules: Rules, act: str, dtype) -> tuple[jax.Array, jax.Array]:
    mesh = rules.mesh
    M = mesh.shape[MODEL] if MODEL in mesh.axis_names else 1
    B, S, d = x.shape
    dax = data_axes(mesh)
    dp = math.prod(mesh.shape[a] for a in dax) if dax else 1
    toks_loc = (B // dp) * (S // M)
    E = cfg.num_experts
    C = max(1, int(math.ceil(toks_loc * cfg.top_k * cfg.capacity_factor / E)))
    glu = "w3" in params
    w3 = params["w3"] if glu else jnp.zeros((E, 1, 1), params["w1"].dtype)
    espec = P(MODEL, None, None)                             # (E, d, eff) EP
    fn = jax.shard_map(
        partial(_gshard_local, cfg, act, dtype, C, glu, mesh.axis_names),
        mesh=mesh,
        in_specs=(P(None, None), espec, espec, espec,
                  P(dax if dax else None, MODEL, None)),
        out_specs=(P(dax if dax else None, MODEL, None), P()),
        check_vma=False,
    )
    return fn(params["router"], params["w1"], params["w2"], w3, x)


# ---------------------------------------------------------------------------
# path 2: expert-tensor-parallel grouped matmul (no all-to-all)
# ---------------------------------------------------------------------------

def _tp_local(cfg: MoEConfig, act: str, dtype, C: int, glu: bool,
              axis_names: tuple, router_w, w1, w2, w3, x):
    """x: (B_loc, S, d) — replicated over model axis; weights eff-sliced.

    The eff-slice partial sums are reduced AFTER the token combine: psum of
    the dense (T, d) output instead of the (E, C, d) capacity buffers —
    combine is linear in the buffer, so the results are identical while the
    all-reduce shrinks by E*C/T (~2.5x at capacity 1.25) and runs in the
    compute dtype."""
    B, S, d = x.shape
    E = cfg.num_experts
    xt = x.reshape(B * S, d)
    gates, idx, probs = _route(router_w, xt, cfg)
    aux = jax.lax.pmean(_aux_loss(probs, idx, E), axis_name=axis_names)
    slot, keep = _dispatch_indices(idx, E, C)
    buf = _scatter_tokens(xt, slot, E, C)
    out = _expert_ffn(buf, w1, w2, w3, glu, act, dtype)      # partial (eff slice)
    y = _gather_tokens(out, slot, gates, keep, dtype)        # partial (T, d)
    y = jax.lax.psum(y.astype(dtype), axis_name=MODEL)       # sum eff slices
    return y.reshape(B, S, d), aux


def moe_tp(params: Params, cfg: MoEConfig, x: jax.Array, *,
           rules: Rules, act: str, dtype) -> tuple[jax.Array, jax.Array]:
    mesh = rules.mesh
    B, S, d = x.shape
    dax = data_axes(mesh)
    dp = math.prod(mesh.shape[a] for a in dax) if dax else 1
    toks_loc = (B // dp) * S
    E = cfg.num_experts
    C = max(1, int(math.ceil(toks_loc * cfg.top_k * cfg.capacity_factor / E)))
    glu = "w3" in params
    M = mesh.shape[MODEL] if MODEL in mesh.axis_names else 1
    w3 = params["w3"] if glu else jnp.zeros((E, 1, M), params["w1"].dtype)
    espec = P(None, None, MODEL)                 # (E, d, eff): eff TP-sliced
    fn = jax.shard_map(
        partial(_tp_local, cfg, act, dtype, C, glu, mesh.axis_names),
        mesh=mesh,
        in_specs=(P(None, None), espec, P(None, MODEL, None), espec,
                  P(dax if dax else None, None, None)),
        out_specs=(P(dax if dax else None, None, None), P()),
        check_vma=False,
    )
    return fn(params["router"], params["w1"], params["w2"], w3, x)


# ---------------------------------------------------------------------------
# path 3: dense all-experts (exact; smoke tests + decode)
# ---------------------------------------------------------------------------

def moe_dense(params: Params, cfg: MoEConfig, x: jax.Array, *,
              act: str, dtype) -> tuple[jax.Array, jax.Array]:
    B, S, d = x.shape
    E = cfg.num_experts
    xt = x.reshape(B * S, d)
    gates, idx, probs = _route(params["router"], xt, cfg)
    aux = _aux_loss(probs, idx, E)
    w = jnp.zeros((B * S, E), jnp.float32)
    w = w.at[jnp.arange(B * S)[:, None], idx].set(gates)
    h = jnp.einsum("td,edf->tef", xt, params["w1"].astype(dtype))
    h = _act(act, h)
    if "w3" in params:
        h = h * jnp.einsum("td,edf->tef", xt, params["w3"].astype(dtype))
    out_e = jnp.einsum("tef,efd->ted", h, params["w2"].astype(dtype))
    y = jnp.einsum("ted,te->td", out_e, w.astype(dtype))
    return y.reshape(B, S, d), aux


def moe_gather_decode(params: Params, cfg: MoEConfig, x: jax.Array, *,
                      act: str, dtype) -> tuple[jax.Array, jax.Array]:
    """Small-batch decode: gather only the top-k experts' weights per token.

    Beats dense-all when B*S*k << E (e.g. batch-1 long-context decode):
    HBM reads drop from all-E weights to k weights per token.
    """
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    gates, idx, probs = _route(params["router"], xt, cfg)
    aux = _aux_loss(probs, idx, cfg.num_experts)
    w1 = jnp.take(params["w1"], idx, axis=0)      # (T, k, d, eff)
    w2 = jnp.take(params["w2"], idx, axis=0)
    h = jnp.einsum("td,tkdf->tkf", xt, w1.astype(dtype))
    h = _act(act, h)
    if "w3" in params:
        w3 = jnp.take(params["w3"], idx, axis=0)
        h = h * jnp.einsum("td,tkdf->tkf", xt, w3.astype(dtype))
    out = jnp.einsum("tkf,tkfd->tkd", h, w2.astype(dtype))
    y = jnp.einsum("tkd,tk->td", out, gates.astype(dtype))
    return y.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# top-level entry
# ---------------------------------------------------------------------------

def moe_forward(params: Params, model_cfg: ModelConfig, x: jax.Array, *,
                rules: Optional[Rules], parallel: Optional[ParallelConfig],
                decode: bool, dtype) -> tuple[jax.Array, jax.Array]:
    cfg = model_cfg.moe
    act = model_cfg.mlp_act
    impl = "dense"
    if not decode and parallel is not None and rules is not None:
        mesh = rules.mesh
        M = mesh.shape[MODEL] if MODEL in mesh.axis_names else 1
        if parallel.moe_impl == "gshard":
            B, S, _ = x.shape
            dax = data_axes(mesh)
            dp = math.prod(mesh.shape[a] for a in dax) if dax else 1
            if (cfg.num_experts % M == 0 and S % M == 0 and B % dp == 0
                    and parallel.expert_parallel):
                impl = "gshard"
            elif cfg.expert_ff % M == 0 and B % dp == 0:
                impl = "tp"
        elif parallel.moe_impl == "dense":
            impl = "dense"
    if decode and parallel is not None:
        B, S, _ = x.shape
        if (parallel.decode_moe_impl == "gather"
                and B * S * cfg.top_k < cfg.num_experts):
            impl = "gather"

    if impl == "gshard":
        y, aux = moe_gshard(params, cfg, x, rules=rules, act=act, dtype=dtype)
    elif impl == "tp":
        y, aux = moe_tp(params, cfg, x, rules=rules, act=act, dtype=dtype)
    elif impl == "gather":
        y, aux = moe_gather_decode(params, cfg, x, act=act, dtype=dtype)
    else:
        y, aux = moe_dense(params, cfg, x, act=act, dtype=dtype)

    if cfg.num_shared_experts:
        y = y + mlp(params["shared"], x, act, dtype)
    return y, aux * cfg.router_aux_coef
