"""Pallas TPU RMSNorm kernel.

Memory-bound op: each row is read once, normalized in fp32, scaled, written
once. Tiled as (block_rows, d) VMEM blocks — d stays whole (the reduction
axis must be resident), rows are the grid. For d_model up to 8192 a
128-row fp32 block is 4 MiB, comfortably inside VMEM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.runtime import resolve_interpret

#: RPL202 streaming allowance (see flash_attention.kernel): empty — every
#: operand here is fetched exactly once (scale's index_map is constant, so
#: its block stays resident across the whole row walk).
STREAMING_OPERANDS: dict[int, str] = {}


def _kernel(x_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                   # (rows, d)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * scale_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_pallas(x: jax.Array, scale: jax.Array, *, eps: float = 1e-5,
                   block_rows: int = 128,
                   interpret: Optional[bool] = None) -> jax.Array:
    """x: (rows, d) with rows % block_rows == 0; scale: (d,).
    ``interpret=None`` follows the platform."""
    interpret = resolve_interpret(interpret)
    rows, d = x.shape
    assert rows % block_rows == 0
    grid = (rows // block_rows,)
    return pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=interpret,
    )(x, scale)
