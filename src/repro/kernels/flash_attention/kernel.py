"""Pallas TPU flash-attention kernel (online softmax, VMEM-tiled).

The paper's framework (InternEvo) leans on FlashAttention for its training
throughput; this is the TPU-native adaptation: instead of a CUDA warp-level
kernel we tile for VMEM with MXU-aligned (128-multiple) block shapes and let
the innermost grid dimension walk KV blocks sequentially ("arbitrary"
semantics), carrying the online-softmax state (m, l, acc) in VMEM scratch
across block visits.

Supports GQA (query-head folding), causal masking, sliding windows (and
thereby gemma3's local:global interleave — window is static per layer) and
tanh soft-capping. Grid: (batch, q_heads, q_blocks, kv_blocks).

Position-based masking: both q and kv carry absolute positions; slots with
position < 0 are padding. This makes full/SWA/ring-buffer caches uniform.
Query positions enter as a (B, Sq, 1) column and KV positions as a
(B, 1, Skv) row, so each position block meets the TPU's (8, 128) tiling at
any batch size and the mask is a plain broadcast of the two.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret

NEG_INF = -2.0 ** 30

#: Declared streaming allowance for the static analyzer (RPL202,
#: ``repro.quality.pallas_cost``): operand positions (in ``pallas_call``
#: argument order) that are *deliberately* re-fetched across grid axes
#: their index_map ignores, with the reason. Everything not listed here
#: must have revisit factor 1 — a new revisit pattern is a perf bug until
#: declared.
STREAMING_OPERANDS = {
    0: "q_positions re-read per q-head (tiny (block_q, 1) i32 block)",
    1: "kv_positions re-streamed per (head, q-block) with the KV walk",
    3: "K streamed over every (q-head, q-block): the FlashAttention "
       "trade — O(S^2) HBM reads bought back by never materializing S^2 "
       "scores",
    4: "V streamed with K (same inner KV walk)",
}


def _kernel(q_pos_ref, kv_pos_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale: float, causal: bool,
            window: int, softcap: float, num_kv_blocks: int):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)            # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)            # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)            # (bk, D)
    q_pos = q_pos_ref[0]                           # (bq, 1)
    kv_pos = kv_pos_ref[0]                         # (1, bk)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = jnp.tanh(s / softcap) * softcap

    ok = kv_pos >= 0
    if causal:
        ok = ok & (kv_pos <= q_pos)
    if window > 0:
        ok = ok & ((q_pos - kv_pos) < window)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[:, :1]                          # (bq, 1)
    l_prev = l_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(ok, jnp.exp(s - m_new), 0.0)   # fully-masked rows -> 0
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == num_kv_blocks - 1)
    def _finish():
        l = l_scr[:, :1]
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l, 1e-20)).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           q_positions: jax.Array, kv_positions: jax.Array,
                           *, causal: bool = True, window: int = 0,
                           softcap: float = 0.0, block_q: int = 128,
                           block_kv: int = 128,
                           interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, H, Sq, D); k, v: (B, KV, Skv, D); positions: (B, S*).

    Sq/Skv must be multiples of block_q/block_kv (ops.py pads). H % KV == 0.
    ``interpret=None`` follows the platform (``runtime.resolve_interpret``).
    """
    interpret = resolve_interpret(interpret)
    B, H, Sq, D = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    assert H % KV == 0 and Sq % block_q == 0 and Skv % block_kv == 0
    G = H // KV
    nq, nk = Sq // block_q, Skv // block_kv
    grid = (B, H, nq, nk)

    q_spec = pl.BlockSpec((1, 1, block_q, D),
                          lambda b, h, iq, ik: (b, h, iq, 0))
    k_spec = pl.BlockSpec((1, 1, block_kv, D),
                          lambda b, h, iq, ik: (b, h // G, ik, 0))
    qp_spec = pl.BlockSpec((1, block_q, 1), lambda b, h, iq, ik: (b, iq, 0))
    kp_spec = pl.BlockSpec((1, 1, block_kv), lambda b, h, iq, ik: (b, 0, ik))
    o_spec = pl.BlockSpec((1, 1, block_q, D),
                          lambda b, h, iq, ik: (b, h, iq, 0))

    kernel = functools.partial(
        _kernel, scale=D ** -0.5, causal=causal, window=window,
        softcap=softcap, num_kv_blocks=nk)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[qp_spec, kp_spec, q_spec, k_spec, k_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # m (lane-replicated)
            pltpu.VMEM((block_q, 128), jnp.float32),   # l
            pltpu.VMEM((block_q, D), jnp.float32),     # acc
        ],
        interpret=interpret,
        **kwargs,
    )(q_positions.reshape(B, Sq, 1), kv_positions.reshape(B, 1, Skv), q, k, v)
