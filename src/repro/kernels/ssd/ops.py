"""jit'd SSD wrapper: pads L to a chunk multiple (dt=0 rows are no-ops)."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.ssd.kernel import ssd_pallas
from repro.utils import round_up


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
        Cm: jax.Array, *, chunk: int = 128,
        interpret: Optional[bool] = None) -> tuple[jax.Array, jax.Array]:
    B, L, H, P = x.shape
    cl = min(chunk, round_up(L, 8))
    L_p = round_up(L, cl)
    if L_p != L:
        pad = L_p - L
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))     # dt=0 -> identity
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    y, state = ssd_pallas(x, dt, A, Bm, Cm, chunk=cl, interpret=interpret)
    return y[:, :L], state
