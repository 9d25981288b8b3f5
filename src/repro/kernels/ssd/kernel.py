"""Pallas TPU kernel for the Mamba2 SSD chunked scan (arXiv:2405.21060).

TPU adaptation of the SSD algorithm: per (batch, head) the sequence is cut
into chunks; each chunk does an intra-chunk quadratic "attention-like" pass
(two MXU matmuls over (chunk x chunk) tiles) plus an inter-chunk rank-1
state recurrence. The chunk axis is the innermost grid dimension with
sequential ("arbitrary") semantics so the (P, N) state lives in VMEM scratch
across chunk visits — the TPU analogue of the CUDA kernel's persistent
shared-memory accumulator.

Inputs follow the oracle's layout (repro.models.mamba.ssd_chunked):
  x  (B, L, H, P)    dt (B, L, H)  [already softplus'd]
  A  (H,) negative   Bm/Cm (B, L, G, N), heads grouped H % G == 0
``ssd_pallas`` moves heads in front of the sequence before the call (x as
(B, H, L, P), dt as (B, H, 1, L), A as (H, 1, 1)), so no block puts a
size-1 heads slice in its last two dims. Grid: (B, H, L // chunk).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret

#: RPL202 streaming allowance (see flash_attention.kernel): operand
#: positions deliberately re-fetched across grid axes their index_map
#: ignores.
STREAMING_OPERANDS = {
    2: "A is a per-head scalar re-read per batch (4-byte block)",
    3: "B blocks re-streamed for each of the H//G heads sharing a group",
    4: "C streamed with B (same head-group sharing)",
}


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_ref, s_scr, *,
            num_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    x = x_ref[0, 0].astype(jnp.float32)                # (cl, P)
    dt = dt_ref[0, 0].astype(jnp.float32)              # (1, cl) row
    A = a_ref[0].astype(jnp.float32)                   # (1, 1)
    Bm = b_ref[0, 0].astype(jnp.float32)               # (cl, N)
    Cm = c_ref[0, 0].astype(jnp.float32)               # (cl, N)

    cl = x.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, (cl, cl), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (cl, cl), 1)
    tri, eye = j <= i, j == i
    # cumulative decay as masked reductions: the column cum_i, its row
    # copy cum_j, and dt as a column (no cumsum or transposes in the body)
    dA = dt * A                                        # (1, cl), <= 0
    cum = jnp.sum(jnp.where(tri, dA, 0.0), axis=1, keepdims=True)     # (cl, 1)
    cum_row = jnp.sum(jnp.where(eye, cum, 0.0), axis=0, keepdims=True)
    dt_col = jnp.sum(jnp.where(eye, dt, 0.0), axis=1, keepdims=True)

    # intra-chunk: y_i += sum_{j<=i} exp(cum_i - cum_j) dt_j (C_i.B_j) x_j
    # mask inside exp: keeps the (interpret-mode) backward pass NaN-free
    decay = jnp.exp(jnp.where(tri, cum - cum_row, -jnp.inf))
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    W = scores * decay * dt                            # (i, j)
    y = jax.lax.dot(W, x, preferred_element_type=jnp.float32)

    # inter-chunk: y_i += exp(cum_i) C_i . S_prev
    S_prev = s_scr[...]                                # (P, N) fp32
    y = y + jnp.exp(cum) * jax.lax.dot_general(
        Cm, S_prev, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update: S = exp(cum_end) S_prev + sum_j e^{cum_end-cum_j} dt_j x_j B_j^T
    cum_end = cum[cl - 1:, :]                          # (1, 1)
    w_state = jnp.exp(cum_end - cum) * dt_col          # (cl, 1)
    S_new = jnp.exp(cum_end) * S_prev + jax.lax.dot_general(
        x * w_state, Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    s_scr[...] = S_new

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == num_chunks - 1)
    def _final():
        state_ref[0, 0] = S_new.astype(state_ref.dtype)


def ssd_pallas(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
               Cm: jax.Array, *, chunk: int = 128,
               interpret: Optional[bool] = None
               ) -> tuple[jax.Array, jax.Array]:
    """Returns (y (B, L, H, P), final_state (B, H, P, N)). L % chunk == 0.
    ``interpret=None`` follows the platform."""
    interpret = resolve_interpret(interpret)
    B, L, H, P = x.shape
    G, N = Bm.shape[-2:]
    assert L % chunk == 0 and H % G == 0
    rep = H // G
    nc = L // chunk
    grid = (B, H, nc)

    # heads (and groups) move in front of the sequence so every block's
    # last two dims are (chunk, feature) tiles or whole dims
    x_spec = pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0))
    dt_spec = pl.BlockSpec((1, 1, 1, chunk), lambda b, h, c: (b, h, 0, c))
    a_spec = pl.BlockSpec((1, 1, 1), lambda b, h, c: (h, 0, 0))
    bc_spec = pl.BlockSpec((1, 1, chunk, N),
                           lambda b, h, c: (b, h // rep, c, 0))
    st_spec = pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0))

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    y, state = pl.pallas_call(
        functools.partial(_kernel, num_chunks=nc),
        grid=grid,
        in_specs=[x_spec, dt_spec, a_spec, bc_spec, bc_spec],
        out_specs=[x_spec, st_spec],
        out_shape=[jax.ShapeDtypeStruct((B, H, L, P), x.dtype),
                   jax.ShapeDtypeStruct((B, H, P, N), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
        **kwargs,
    )(jnp.swapaxes(x, 1, 2), jnp.swapaxes(dt, 1, 2)[:, :, None, :],
      A.reshape(H, 1, 1), jnp.swapaxes(Bm, 1, 2), jnp.swapaxes(Cm, 1, 2))
    return jnp.swapaxes(y, 1, 2), state
