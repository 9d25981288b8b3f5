"""jit'd RMSNorm wrapper: flattens leading dims, pads rows to the block."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.rmsnorm.kernel import rmsnorm_pallas
from repro.utils import round_up


@partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x: jax.Array, scale: jax.Array, *, eps: float = 1e-5,
            block_rows: int = 128,
            interpret: Optional[bool] = None) -> jax.Array:
    shape = x.shape
    d = shape[-1]
    rows = 1
    for s in shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    br = min(block_rows, round_up(rows, 8))
    rows_p = round_up(rows, br)
    if rows_p != rows:
        x2 = jnp.pad(x2, ((0, rows_p - rows), (0, 0)))
    out = rmsnorm_pallas(x2, scale, eps=eps, block_rows=br,
                         interpret=interpret)
    return out[:rows].reshape(shape)
