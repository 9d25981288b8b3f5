"""jit'd public wrapper: padding, layout handling, interpret/TPU dispatch.

Model code uses (B, S, H, D) layout; the kernel wants (B, H, S, D) with
block-multiple sequence lengths. Padding KV slots carry position -1 (masked
by construction); padded query rows are sliced off on return.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.utils import round_up


@partial(jax.jit, static_argnames=("causal", "window", "softcap", "block_q",
                                   "block_kv", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    q_positions: jax.Array, kv_positions: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    softcap: float = 0.0, block_q: int = 128,
                    block_kv: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, Sq, H, D); k, v: (B, Skv, KV, D); positions (B, S*) or (S*,)."""
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    q_pos = jnp.broadcast_to(jnp.asarray(q_positions), (B, Sq)).astype(jnp.int32)
    kv_pos = jnp.broadcast_to(jnp.asarray(kv_positions), (B, Skv)).astype(jnp.int32)

    bq = min(block_q, round_up(Sq, 8))
    bk = min(block_kv, round_up(Skv, 8))
    Sq_p, Skv_p = round_up(Sq, bq), round_up(Skv, bk)
    qt = jnp.swapaxes(q, 1, 2)                       # (B, H, Sq, D)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if Sq_p != Sq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, Sq_p - Sq), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, Sq_p - Sq)),
                        constant_values=0)
    if Skv_p != Skv:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, Skv_p - Skv), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, Skv_p - Skv), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, Skv_p - Skv)),
                         constant_values=-1)
    out = flash_attention_pallas(qt, kt, vt, q_pos, kv_pos, causal=causal,
                                 window=window, softcap=softcap,
                                 block_q=bq, block_kv=bk,
                                 interpret=interpret)
    return jnp.swapaxes(out[:, :, :Sq], 1, 2)
