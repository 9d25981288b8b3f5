"""Mamba2 block via SSD (state-space duality, arXiv:2405.21060).

The chunked SSD algorithm: intra-chunk attention-like quadratic term +
inter-chunk linear state recurrence (log-depth via associative scan). This
pure-jnp implementation is also the oracle for the Pallas SSD kernel in
``repro.kernels.ssd``. Decode is the O(1)-per-token state recurrence.

Projections are kept as separate matrices (z/x/B/C/dt) instead of one fused
in_proj so each piece carries a clean sharding axis (inner dims TP-sharded
over ``model``, B/C groups replicated) — the TPU-native layout.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.config import SSMConfig
from repro.models.layers import rmsnorm
from repro.models.spec import ParamSpec

Params = Any


# ---------------------------------------------------------------------------
# specs
# ---------------------------------------------------------------------------

def mamba_specs(cfg: SSMConfig, d_model: int) -> dict:
    d_in = cfg.d_inner(d_model)
    H = cfg.num_ssm_heads(d_model)
    GN = cfg.n_groups * cfg.state_dim
    s = d_model ** -0.5
    w = cfg.conv_width
    return {
        "in_z": ParamSpec((d_model, d_in), ("embed", "ssm_inner"), stddev=s),
        "in_x": ParamSpec((d_model, d_in), ("embed", "ssm_inner"), stddev=s),
        "in_B": ParamSpec((d_model, GN), ("embed", None), stddev=s),
        "in_C": ParamSpec((d_model, GN), ("embed", None), stddev=s),
        "in_dt": ParamSpec((d_model, H), ("embed", "ssm_heads"), stddev=s),
        "conv_x": ParamSpec((w, d_in), (None, "ssm_inner"), stddev=w ** -0.5),
        "conv_x_b": ParamSpec((d_in,), ("ssm_inner",), init="zeros"),
        "conv_B": ParamSpec((w, GN), (None, None), stddev=w ** -0.5),
        "conv_B_b": ParamSpec((GN,), (None,), init="zeros"),
        "conv_C": ParamSpec((w, GN), (None, None), stddev=w ** -0.5),
        "conv_C_b": ParamSpec((GN,), (None,), init="zeros"),
        "A_log": ParamSpec((H,), ("ssm_heads",), init="a_log"),
        "dt_bias": ParamSpec((H,), ("ssm_heads",), init="zeros"),
        "D": ParamSpec((H,), ("ssm_heads",), init="ones"),
        "norm": ParamSpec((d_in,), ("ssm_inner",), init="ones"),
        "out": ParamSpec((d_in, d_model), ("ssm_inner", "embed"),
                         stddev=d_in ** -0.5),
    }


# ---------------------------------------------------------------------------
# causal depthwise conv (width-4: unrolled shifts — cheap and shardable)
# ---------------------------------------------------------------------------

def causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """x: (B, L, C); w: (W, C) -> (B, L, C), causal."""
    W = w.shape[0]
    out = x * w[-1]
    for i in range(1, W):
        shifted = jnp.pad(x, ((0, 0), (i, 0), (0, 0)))[:, :-i or None][:, :x.shape[1]]
        out = out + shifted * w[-1 - i]
    return out + b


def causal_conv_step(x_t: jax.Array, state: jax.Array, w: jax.Array,
                     b: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One-token conv. x_t: (B, C); state: (B, W-1, C) holds prior inputs."""
    full = jnp.concatenate([state, x_t[:, None, :]], axis=1)   # (B, W, C)
    out = jnp.einsum("bwc,wc->bc", full, w) + b
    return out, full[:, 1:]


# ---------------------------------------------------------------------------
# SSD core (chunked) — the jnp oracle
# ---------------------------------------------------------------------------

def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                C: jax.Array, *, chunk: int,
                initial_state: jax.Array | None = None,
                return_final_state: bool = False,
                head_block: int = 0):
    """SSD scan over chunks.

    x: (b, L, H, P); dt: (b, L, H) (already softplus'd, >=0);
    A: (H,) negative; B, C: (b, L, G, N). Returns y (b, L, H, P)
    [+ final state (b, H, P, N)]. L is padded to a chunk multiple internally.

    ``head_block`` > 0 processes group-aligned head blocks under vmap-of-map
    so the intra-chunk (cl, cl, Hb) decay tensors stay bounded — the jnp
    analogue of the Pallas kernel's per-head grid.
    """
    b, L, H, P = x.shape
    G, N0 = B.shape[-2:]
    rep0 = max(H // G, 1)
    if head_block and H > head_block:
        gb = max(head_block // rep0, 1)       # whole groups per block
        nb = G // gb
        if nb > 1 and G % gb == 0:
            # (b, L, nb, Hb/P...) blocked views; scan over nb blocks
            hb = gb * rep0                    # heads per block
            xb = x.reshape(b, L, nb, hb, P)
            dtb = dt.reshape(b, L, nb, hb)
            Ab = A.reshape(nb, hb)
            Bb = B.reshape(b, L, nb, gb, N0)
            Cb = C.reshape(b, L, nb, gb, N0)

            def one(i):
                return ssd_chunked(
                    xb[:, :, i], dtb[:, :, i], Ab[i], Bb[:, :, i],
                    Cb[:, :, i], chunk=chunk,
                    initial_state=(initial_state.reshape(
                        b, nb, hb, P, N0)[:, i]
                        if initial_state is not None else None),
                    return_final_state=True)

            ys, states = jax.lax.map(one, jnp.arange(nb))
            y = jnp.moveaxis(ys, 0, 2).reshape(b, L if L % chunk == 0 else L,
                                               H, P)
            y = y[:, :L]
            if return_final_state:
                state = jnp.moveaxis(states, 0, 1).reshape(b, H, P, N0)
                return y, state
            return y
    G, N = B.shape[-2:]
    rep = H // G
    cl = min(chunk, L)
    nc = -(-L // cl)
    pad = nc * cl - L
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))   # dt=0 -> no-op steps
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))

    f32 = jnp.float32
    xc = x.reshape(b, nc, cl, H, P)
    dtc = dt.reshape(b, nc, cl, H).astype(f32)
    Bc = B.reshape(b, nc, cl, G, N)
    Cc = C.reshape(b, nc, cl, G, N)

    dA = dtc * A.astype(f32)                           # (b,nc,cl,H), <= 0
    cum = jnp.cumsum(dA, axis=2)                       # within-chunk cumsum
    # intra-chunk: decay from step j to step i (i >= j). Mask INSIDE the
    # exp: above the diagonal seg > 0 can overflow, and where(tri, exp, 0)
    # would leak NaN through the backward pass (inf * 0 cotangent).
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]        # (b,nc,i,j,H)
    tri = jnp.tril(jnp.ones((cl, cl), bool))
    Lmat = jnp.exp(jnp.where(tri[None, None, :, :, None], seg, -jnp.inf))
    scores = jnp.einsum("bcign,bcjgn->bcijg", Cc.astype(f32), Bc.astype(f32))
    scores = jnp.repeat(scores, rep, axis=-1)                  # g -> h
    W = scores * Lmat * dtc[:, :, None, :, :]                  # (b,nc,i,j,H)
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", W, xc.astype(f32))

    # chunk-boundary states: (b, nc, H, P, N)
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)            # (b,nc,j,H)
    Bh = jnp.repeat(Bc, rep, axis=3).astype(f32)               # (b,nc,cl,H,N)
    S = jnp.einsum("bcjh,bcjhn,bcjhp->bchpn",
                   decay_to_end * dtc, Bh, xc.astype(f32))

    # inter-chunk recurrence T_n = a_n * T_{n-1} + S_n (assoc. scan)
    a = jnp.exp(cum[:, :, -1, :])                              # (b,nc,H)

    def combine(e1, e2):
        a1, s1 = e1
        a2, s2 = e2
        return a1 * a2, a2[..., None, None] * s1 + s2

    T_a, T_s = jax.lax.associative_scan(combine, (a, S), axis=1)
    if initial_state is not None:
        # fold the initial state through each prefix decay
        T_s = T_s + (T_a[..., None, None] * initial_state[:, None].astype(f32))
    # state entering chunk n = T_{n-1} (zeros/init for n=0)
    init = (initial_state[:, None].astype(f32) if initial_state is not None
            else jnp.zeros_like(T_s[:, :1]))
    R = jnp.concatenate([init, T_s[:, :-1]], axis=1)           # (b,nc,H,P,N)

    Ch = jnp.repeat(Cc, rep, axis=3).astype(f32)               # (b,nc,cl,H,N)
    y_inter = jnp.einsum("bcihn,bcih,bchpn->bcihp",
                         Ch, jnp.exp(cum), R)
    y = (y_intra + y_inter).reshape(b, nc * cl, H, P)[:, :L]
    y = y.astype(x.dtype)
    if return_final_state:
        return y, T_s[:, -1]                                   # (b,H,P,N)
    return y


def ssd_decode_step(state: jax.Array, x_t: jax.Array, dt_t: jax.Array,
                    A: jax.Array, B_t: jax.Array, C_t: jax.Array):
    """One-token SSD. state: (b,H,P,N); x_t: (b,H,P); dt_t: (b,H);
    B_t, C_t: (b,G,N). Returns (y_t (b,H,P), new_state)."""
    b, H, P, N = state.shape
    G = B_t.shape[1]
    rep = H // G
    f32 = jnp.float32
    Bh = jnp.repeat(B_t, rep, axis=1).astype(f32)              # (b,H,N)
    Ch = jnp.repeat(C_t, rep, axis=1).astype(f32)
    decay = jnp.exp(dt_t.astype(f32) * A.astype(f32))          # (b,H)
    upd = (dt_t.astype(f32)[..., None, None] * x_t.astype(f32)[..., None]
           * Bh[:, :, None, :])                                # (b,H,P,N)
    new_state = decay[..., None, None] * state.astype(f32) + upd
    y = jnp.einsum("bhpn,bhn->bhp", new_state, Ch)
    return y.astype(x_t.dtype), new_state


# ---------------------------------------------------------------------------
# full mamba2 block
# ---------------------------------------------------------------------------

def _project(params: Params, x: jax.Array, cfg: SSMConfig, d_model: int,
             dtype) -> tuple:
    z = x @ params["in_z"].astype(dtype)
    xi = x @ params["in_x"].astype(dtype)
    Bi = x @ params["in_B"].astype(dtype)
    Ci = x @ params["in_C"].astype(dtype)
    dt = x @ params["in_dt"].astype(dtype)
    return z, xi, Bi, Ci, dt


def mamba_forward(params: Params, cfg: SSMConfig, x: jax.Array, *,
                  d_model: int, dtype, norm_eps: float = 1e-5,
                  return_state: bool = False):
    """Full-sequence mamba2 block. x: (B, L, d_model)."""
    b, L, _ = x.shape
    H = cfg.num_ssm_heads(d_model)
    P = cfg.head_dim
    G, N = cfg.n_groups, cfg.state_dim
    z, xi, Bi, Ci, dt = _project(params, x, cfg, d_model, dtype)
    xi = jax.nn.silu(causal_conv(xi, params["conv_x"].astype(dtype),
                                 params["conv_x_b"].astype(dtype)))
    Bi = jax.nn.silu(causal_conv(Bi, params["conv_B"].astype(dtype),
                                 params["conv_B_b"].astype(dtype)))
    Ci = jax.nn.silu(causal_conv(Ci, params["conv_C"].astype(dtype),
                                 params["conv_C_b"].astype(dtype)))
    xh = xi.reshape(b, L, H, P)
    dt_sp = jax.nn.softplus(dt.astype(jnp.float32)
                            + params["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(params["A_log"].astype(jnp.float32))
    from repro.kernels import runtime
    if runtime.STATE.use_pallas:
        from repro.kernels.ssd import ssd as ssd_kernel
        y, final_state = ssd_kernel(xh, dt_sp, A, Bi.reshape(b, L, G, N),
                                    Ci.reshape(b, L, G, N),
                                    chunk=cfg.chunk_size)
        if not return_state:
            final_state = None
    else:
        out = ssd_chunked(xh, dt_sp, A, Bi.reshape(b, L, G, N),
                          Ci.reshape(b, L, G, N), chunk=cfg.chunk_size,
                          return_final_state=return_state,
                          head_block=cfg.head_block)
        y, final_state = out if return_state else (out, None)
    y = y + params["D"].astype(y.dtype)[None, None, :, None] * xh
    y = y.reshape(b, L, H * P)
    y = rmsnorm({"scale": params["norm"]}, y * jax.nn.silu(z), norm_eps)
    y = y @ params["out"].astype(dtype)
    if return_state:
        # conv tail states: the last (W-1) *pre-conv* channel inputs
        W = cfg.conv_width
        def tail(v):
            return jnp.pad(v, ((0, 0), (max(W - 1 - L, 0), 0), (0, 0)))[:, -(W - 1):]
        _, xi_raw, Bi_raw, Ci_raw, _ = _project(params, x, cfg, d_model, dtype)
        cache = {
            "ssm": final_state,
            "conv_x": tail(xi_raw), "conv_B": tail(Bi_raw),
            "conv_C": tail(Ci_raw),
        }
        return y, cache
    return y


def mamba_cache_init(cfg: SSMConfig, batch: int, d_model: int, dtype) -> dict:
    H = cfg.num_ssm_heads(d_model)
    d_in = cfg.d_inner(d_model)
    GN = cfg.n_groups * cfg.state_dim
    W = cfg.conv_width
    return {
        "ssm": jnp.zeros((batch, H, cfg.head_dim, cfg.state_dim), jnp.float32),
        "conv_x": jnp.zeros((batch, W - 1, d_in), dtype),
        "conv_B": jnp.zeros((batch, W - 1, GN), dtype),
        "conv_C": jnp.zeros((batch, W - 1, GN), dtype),
    }


def mamba_decode(params: Params, cfg: SSMConfig, x: jax.Array, cache: dict, *,
                 d_model: int, dtype, norm_eps: float = 1e-5):
    """One-token decode. x: (B, 1, d_model)."""
    b = x.shape[0]
    H = cfg.num_ssm_heads(d_model)
    P = cfg.head_dim
    G, N = cfg.n_groups, cfg.state_dim
    z, xi, Bi, Ci, dt = _project(params, x[:, 0], cfg, d_model, dtype)
    xi, conv_x = causal_conv_step(xi, cache["conv_x"],
                                  params["conv_x"].astype(dtype),
                                  params["conv_x_b"].astype(dtype))
    Bi, conv_B = causal_conv_step(Bi, cache["conv_B"],
                                  params["conv_B"].astype(dtype),
                                  params["conv_B_b"].astype(dtype))
    Ci, conv_C = causal_conv_step(Ci, cache["conv_C"],
                                  params["conv_C"].astype(dtype),
                                  params["conv_C_b"].astype(dtype))
    xi, Bi, Ci = jax.nn.silu(xi), jax.nn.silu(Bi), jax.nn.silu(Ci)
    dt_sp = jax.nn.softplus(dt.astype(jnp.float32)
                            + params["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(params["A_log"].astype(jnp.float32))
    y, new_state = ssd_decode_step(cache["ssm"], xi.reshape(b, H, P), dt_sp,
                                   A, Bi.reshape(b, G, N), Ci.reshape(b, G, N))
    y = y + params["D"].astype(y.dtype)[None, :, None] * xi.reshape(b, H, P)
    y = y.reshape(b, 1, H * P)
    y = rmsnorm({"scale": params["norm"]}, y * jax.nn.silu(z[:, None, :]),
                norm_eps)
    y = y @ params["out"].astype(dtype)
    new_cache = {"ssm": new_state, "conv_x": conv_x, "conv_B": conv_B,
                 "conv_C": conv_C}
    return y, new_cache
