"""Attention: GQA (w/ sliding-window + local:global) and MLA (DeepSeek-v2).

The trainable path uses a blockwise online-softmax implementation in pure jnp
(`flash_attention_jnp`) so 32k-token prefill never materializes an (S, S)
score matrix; it is also the oracle for the Pallas TPU kernel in
``repro.kernels.flash_attention``. Decode uses ring-buffer KV caches whose
slots carry absolute positions, which makes full, sliding-window and
local:global layers uniform (validity is just a predicate on slot position).
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.config import AttentionConfig
from repro.models.layers import apply_rope
from repro.models.spec import ParamSpec
from repro.utils.profiling import scope

Params = Any
NEG_INF = -2.0 ** 30  # large-but-finite; avoids NaNs for fully-masked rows


# ---------------------------------------------------------------------------
# specs
# ---------------------------------------------------------------------------

def attn_specs(cfg: AttentionConfig, d_model: int) -> dict:
    s = d_model ** -0.5
    if cfg.kind == "mla":
        qk = cfg.qk_nope_dim + cfg.qk_rope_dim
        specs = {
            "w_dkv": ParamSpec((d_model, cfg.kv_lora_rank + cfg.qk_rope_dim),
                               ("embed", None), stddev=s),
            "kv_norm": ParamSpec((cfg.kv_lora_rank,), (None,), init="ones"),
            "w_uk": ParamSpec((cfg.kv_lora_rank, cfg.num_heads, cfg.qk_nope_dim),
                              (None, "heads", None),
                              stddev=cfg.kv_lora_rank ** -0.5),
            "w_uv": ParamSpec((cfg.kv_lora_rank, cfg.num_heads, cfg.v_head_dim),
                              (None, "heads", None),
                              stddev=cfg.kv_lora_rank ** -0.5),
            "wo": ParamSpec((cfg.num_heads, cfg.v_head_dim, d_model),
                            ("heads", None, "embed"),
                            stddev=(cfg.num_heads * cfg.v_head_dim) ** -0.5),
        }
        if cfg.q_lora_rank:
            specs["w_dq"] = ParamSpec((d_model, cfg.q_lora_rank),
                                      ("embed", None), stddev=s)
            specs["q_norm"] = ParamSpec((cfg.q_lora_rank,), (None,), init="ones")
            specs["w_uq"] = ParamSpec((cfg.q_lora_rank, cfg.num_heads, qk),
                                      (None, "heads", None),
                                      stddev=cfg.q_lora_rank ** -0.5)
        else:
            specs["wq"] = ParamSpec((d_model, cfg.num_heads, qk),
                                    ("embed", "heads", None), stddev=s)
        return specs
    return {
        "wq": ParamSpec((d_model, cfg.num_heads, cfg.head_dim),
                        ("embed", "heads", None), stddev=s),
        "wk": ParamSpec((d_model, cfg.num_kv_heads, cfg.head_dim),
                        ("embed", "kv_heads", None), stddev=s),
        "wv": ParamSpec((d_model, cfg.num_kv_heads, cfg.head_dim),
                        ("embed", "kv_heads", None), stddev=s),
        "wo": ParamSpec((cfg.num_heads, cfg.head_dim, d_model),
                        ("heads", None, "embed"),
                        stddev=(cfg.num_heads * cfg.head_dim) ** -0.5),
    }


# ---------------------------------------------------------------------------
# blockwise online-softmax attention (pure jnp; Pallas oracle)
# ---------------------------------------------------------------------------

def _mask(q_pos, k_pos, window, causal):
    """Validity of (q, k) pairs. Positions < 0 are empty slots."""
    valid = k_pos >= 0
    if causal:
        valid &= k_pos <= q_pos
    valid &= jnp.where(window > 0, q_pos - k_pos < window, True)
    return valid


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    q_positions: jax.Array, kv_positions: jax.Array,
                    window: jax.Array | int = 0, causal: bool = True,
                    block_kv: int = 512, softcap: float = 0.0) -> jax.Array:
    """Dispatch: Pallas TPU kernel when enabled, else the jnp oracle path."""
    from repro.kernels import runtime
    if runtime.STATE.use_pallas:
        if not isinstance(window, int):
            raise TypeError("the Pallas attention kernel takes a static int "
                            f"window, got {type(window).__name__}")
        from repro.kernels.flash_attention import flash_attention as fa
        return fa(q, k, v, q_positions, kv_positions, causal=causal,
                  window=window, softcap=softcap)
    return flash_attention_jnp(q, k, v, q_positions=q_positions,
                               kv_positions=kv_positions, window=window,
                               causal=causal, block_kv=block_kv,
                               softcap=softcap)


def flash_attention_jnp(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        q_positions: jax.Array, kv_positions: jax.Array,
                        window: jax.Array | int = 0, causal: bool = True,
                        block_kv: int = 512,
                        softcap: float = 0.0) -> jax.Array:
    """Memory-O(S·block) attention via a scan over KV blocks.

    q: (B, Sq, H, D); k, v: (B, Skv, KV, D) with H % KV == 0 (GQA).
    q_positions: (Sq,) or (B, Sq); kv_positions: (Skv,) or (B, Skv).
    """
    B, Sq, H, D = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = D ** -0.5
    qb = q.reshape(B, Sq, KV, G, D)
    q_pos = jnp.broadcast_to(jnp.asarray(q_positions), (B, Sq))
    kv_pos = jnp.broadcast_to(jnp.asarray(kv_positions), (B, Skv))

    # pad Skv to a block multiple; padded slots get position -1 (masked out)
    nb = -(-Skv // block_kv)
    pad = nb * block_kv - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=-1)

    k_blk = k.reshape(B, nb, block_kv, KV, D).transpose(1, 0, 2, 3, 4)
    v_blk = v.reshape(B, nb, block_kv, KV, D).transpose(1, 0, 2, 3, 4)
    p_blk = kv_pos.reshape(B, nb, block_kv).transpose(1, 0, 2)

    def body(carry, xs):
        m, l, acc = carry                      # (B,KV,G,Sq), ..., (B,KV,G,Sq,D)
        kb, vb, pb = xs                        # (B,bk,KV,D), ..., (B,bk)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qb, kb,
                       preferred_element_type=jnp.float32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        ok = _mask(q_pos[:, None, None, :, None],
                   pb[:, None, None, None, :], window, causal)
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p.astype(v.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KV, G, Sq, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (k_blk, v_blk, p_blk))
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, D).astype(q.dtype)


def attention_ref(q, k, v, *, q_positions, kv_positions, window=0,
                  causal=True, softcap: float = 0.0) -> jax.Array:
    """O(S^2)-memory reference used in unit tests for small shapes."""
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    qb = q.reshape(B, Sq, KV, G, D)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qb, k,
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    q_pos = jnp.broadcast_to(jnp.asarray(q_positions), (B, Sq))
    kv_pos = jnp.broadcast_to(jnp.asarray(kv_positions), (B, k.shape[1]))
    ok = _mask(q_pos[:, None, None, :, None], kv_pos[:, None, None, None, :],
               window, causal)
    s = jnp.where(ok, s, NEG_INF)
    p = jnp.where(ok, jax.nn.softmax(s, axis=-1), 0.0)
    out = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(v.dtype), v)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA module
# ---------------------------------------------------------------------------

def gqa_forward(params: Params, cfg: AttentionConfig, x: jax.Array,
                positions: jax.Array, *, window: jax.Array | int,
                dtype: Any, block_kv: int = 512,
                kv_override: Optional[tuple] = None,
                causal: bool = True) -> jax.Array:
    """Full-sequence attention (training / prefill). x: (B, S, d)."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dtype))
    if kv_override is None:
        k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dtype))
        v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dtype))
        kv_positions = positions
    else:
        k, v, kv_positions = kv_override  # cross-attention (whisper decoder)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        if kv_override is None:
            k = apply_rope(k, positions, cfg.rope_theta)
    out = flash_attention(q, k, v, q_positions=positions,
                          kv_positions=kv_positions, window=window,
                          causal=causal, block_kv=block_kv,
                          softcap=cfg.logit_softcap)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dtype))


def gqa_kv(params: Params, cfg: AttentionConfig, x: jax.Array,
           positions: jax.Array, dtype: Any) -> tuple[jax.Array, jax.Array]:
    """K/V projection only (cross-attention memo for enc-dec)."""
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dtype))
    if cfg.use_rope:
        k = apply_rope(k, positions, cfg.rope_theta)
    return k, v


# --- KV cache (ring buffer with absolute slot positions) -------------------

def gqa_cache_shape(cfg: AttentionConfig, batch: int, cache_len: int,
                    dtype: Any) -> dict:
    return {
        "k": jax.ShapeDtypeStruct((batch, cache_len, cfg.num_kv_heads,
                                   cfg.head_dim), dtype),
        "v": jax.ShapeDtypeStruct((batch, cache_len, cfg.num_kv_heads,
                                   cfg.head_dim), dtype),
        "pos": jax.ShapeDtypeStruct((batch, cache_len), jnp.int32),
    }


def gqa_cache_init(cfg: AttentionConfig, batch: int, cache_len: int,
                   dtype: Any) -> dict:
    return {
        "k": jnp.zeros((batch, cache_len, cfg.num_kv_heads, cfg.head_dim),
                       dtype),
        "v": jnp.zeros((batch, cache_len, cfg.num_kv_heads, cfg.head_dim),
                       dtype),
        "pos": jnp.full((batch, cache_len), -1, jnp.int32),
    }


def gqa_prefill_cache(params: Params, cfg: AttentionConfig, x: jax.Array,
                      positions: jax.Array, cache_len: int,
                      dtype: Any) -> dict:
    """Build a cache from a prompt of static length S (ring-rotated if S>len)."""
    B, S, _ = x.shape
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dtype))
    if cfg.use_rope:
        k = apply_rope(k, positions, cfg.rope_theta)
    pos = jnp.broadcast_to(jnp.asarray(positions), (B, S)).astype(jnp.int32)
    if S >= cache_len:
        k, v, pos = k[:, -cache_len:], v[:, -cache_len:], pos[:, -cache_len:]
        shift = S % cache_len
        k = jnp.roll(k, shift, axis=1)
        v = jnp.roll(v, shift, axis=1)
        pos = jnp.roll(pos, shift, axis=1)
        return {"k": k, "v": v, "pos": pos}
    cache = gqa_cache_init(cfg, B, cache_len, dtype)
    return {
        "k": jax.lax.dynamic_update_slice(cache["k"], k, (0, 0, 0, 0)),
        "v": jax.lax.dynamic_update_slice(cache["v"], v, (0, 0, 0, 0)),
        "pos": jax.lax.dynamic_update_slice(cache["pos"], pos, (0, 0)),
    }


def gqa_decode_qkv(params: Params, cfg: AttentionConfig, x: jax.Array,
                   cur_index: jax.Array, *, dtype: Any) -> tuple[jax.Array, dict]:
    """One decode token's query and cache entries. x: (B, 1, d);
    cur_index: scalar absolute position. Returns q (B, 1, H, D) and the
    entries k, v (B, 1, KV, D) and pos (B, 1) for its ring-buffer slot."""
    B = x.shape[0]
    pos = jnp.full((B, 1), cur_index, jnp.int32)
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dtype))
    if cfg.use_rope:
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    return q, {"k": k, "v": v, "pos": pos}


def gqa_decode_attend(params: Params, cfg: AttentionConfig, q: jax.Array,
                      cache: dict, cur_index: jax.Array, *,
                      window: jax.Array | int, dtype: Any) -> jax.Array:
    """One decode token's attention over its layer's ring buffer ``cache``
    (k, v: (B, L, KV, D); pos: (B, L)), holding the token's own entries."""
    B = q.shape[0]
    with scope("kv_cache"):
        k_all = cache["k"].astype(dtype)
        v_all = cache["v"].astype(dtype)
    out = attention_ref(q, k_all, v_all,
                        q_positions=jnp.full((B, 1), cur_index, jnp.int32),
                        kv_positions=cache["pos"], window=window,
                        causal=True, softcap=cfg.logit_softcap)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(dtype))


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention, DeepSeek-v2)
# ---------------------------------------------------------------------------

def _mla_q(params: Params, cfg: AttentionConfig, x: jax.Array, positions,
           dtype: Any) -> tuple[jax.Array, jax.Array]:
    from repro.models.layers import rmsnorm
    if cfg.q_lora_rank:
        cq = x @ params["w_dq"].astype(dtype)
        cq = rmsnorm({"scale": params["q_norm"]}, cq)
        q = jnp.einsum("bsr,rhk->bshk", cq, params["w_uq"].astype(dtype))
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dtype))
    q_nope = q[..., :cfg.qk_nope_dim]
    q_rope = apply_rope(q[..., cfg.qk_nope_dim:], positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_latent(params: Params, cfg: AttentionConfig, x: jax.Array, positions,
                dtype: Any) -> tuple[jax.Array, jax.Array]:
    from repro.models.layers import rmsnorm
    dkv = x @ params["w_dkv"].astype(dtype)
    ckv = rmsnorm({"scale": params["kv_norm"]}, dkv[..., :cfg.kv_lora_rank])
    k_rope = dkv[..., None, cfg.kv_lora_rank:]        # (B,S,1,rope)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)[..., 0, :]
    return ckv, k_rope


def mla_forward(params: Params, cfg: AttentionConfig, x: jax.Array,
                positions: jax.Array, *, dtype: Any,
                block_kv: int = 512) -> jax.Array:
    """Training/prefill MLA: decompress latent to per-head K/V, flash attend."""
    B, S, _ = x.shape
    q_nope, q_rope = _mla_q(params, cfg, x, positions, dtype)
    ckv, k_rope = _mla_latent(params, cfg, x, positions, dtype)
    k_nope = jnp.einsum("bsr,rhk->bshk", ckv, params["w_uk"].astype(dtype))
    v = jnp.einsum("bsr,rhv->bshv", ckv, params["w_uv"].astype(dtype))
    H = cfg.num_heads
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (B, S, H, cfg.qk_rope_dim))], axis=-1)
    # pad v to qk dim so flash kernel sees one head_dim; slice after
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    v_p = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, qk - cfg.v_head_dim)))
    out = flash_attention(q, k, v_p, q_positions=positions,
                          kv_positions=positions, window=0, causal=True,
                          block_kv=block_kv)
    out = out[..., :cfg.v_head_dim]
    return jnp.einsum("bshv,hvd->bsd", out, params["wo"].astype(dtype))


def mla_cache_init(cfg: AttentionConfig, batch: int, cache_len: int,
                   dtype: Any) -> dict:
    return {
        "ckv": jnp.zeros((batch, cache_len, cfg.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, cache_len, cfg.qk_rope_dim), dtype),
        "pos": jnp.full((batch, cache_len), -1, jnp.int32),
    }


def mla_prefill_cache(params: Params, cfg: AttentionConfig, x: jax.Array,
                      positions: jax.Array, cache_len: int,
                      dtype: Any) -> dict:
    B, S, _ = x.shape
    ckv, k_rope = _mla_latent(params, cfg, x, positions, dtype)
    pos = jnp.broadcast_to(jnp.asarray(positions), (B, S)).astype(jnp.int32)
    cache = mla_cache_init(cfg, B, cache_len, dtype)
    n = min(S, cache_len)
    return {
        "ckv": jax.lax.dynamic_update_slice(cache["ckv"], ckv[:, -n:],
                                            (0, 0, 0)),
        "k_rope": jax.lax.dynamic_update_slice(cache["k_rope"],
                                               k_rope[:, -n:], (0, 0, 0)),
        "pos": jax.lax.dynamic_update_slice(cache["pos"], pos[:, -n:], (0, 0)),
    }


def mla_decode_qkv(params: Params, cfg: AttentionConfig, x: jax.Array,
                   cur_index: jax.Array, *, dtype: Any) -> tuple[tuple, dict]:
    """One decode token's query parts (q_nope, q_rope: (B, 1, H, *)) and
    latent cache entries ckv (B, 1, r), k_rope (B, 1, rope), pos (B, 1)."""
    B = x.shape[0]
    pos = jnp.full((B, 1), cur_index, jnp.int32)
    q = _mla_q(params, cfg, x, pos, dtype)
    ckv, k_rope = _mla_latent(params, cfg, x, pos, dtype)
    return q, {"ckv": ckv, "k_rope": k_rope, "pos": pos}


def mla_decode_attend(params: Params, cfg: AttentionConfig, q: tuple,
                      cache: dict, cur_index: jax.Array, *,
                      dtype: Any) -> jax.Array:
    """Absorbed-weight decode: attend in the 512-d latent space directly —
    the compressed-KV insight of MLA; no per-head K/V is ever materialized.
    ``cache`` is the layer's latent ring buffer, holding this token's."""
    q_nope, q_rope = q
    with scope("kv_cache"):
        ckv = cache["ckv"].astype(dtype)
        k_rope = cache["k_rope"].astype(dtype)
    # absorb w_uk into the query: q_lat[b,1,h,r]
    q_lat = jnp.einsum("bqhk,rhk->bqhr", q_nope, params["w_uk"].astype(dtype))
    s = (jnp.einsum("bqhr,bsr->bhqs", q_lat, ckv,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bqhk,bsk->bhqs", q_rope, k_rope,
                      preferred_element_type=jnp.float32))
    s = s * ((cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5)
    kv_pos = cache["pos"][:, None, None, :]                  # (B,1,1,S)
    ok = (kv_pos >= 0) & (kv_pos <= cur_index)
    s = jnp.where(ok, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhqs,bsr->bqhr", p.astype(dtype), ckv)
    out = jnp.einsum("bqhr,rhv->bqhv", ctx, params["w_uv"].astype(dtype))
    return jnp.einsum("bqhv,hvd->bqd", out, params["wo"].astype(dtype))
