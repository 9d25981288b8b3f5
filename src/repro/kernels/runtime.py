"""Kernel dispatch: whether the model calls the Pallas kernels, and how
they run.

``pallas_enabled()`` routes the model's attention and SSD scan through the
Pallas kernels; the pure-jnp implementations stay the default (and the
oracles) — the kernels have no backward pass yet. How a kernel runs follows
the platform: compiled to Mosaic on a TPU, interpret mode (the kernel body
executed op by op, bit-accurate) anywhere else. Interpret mode is refused
on a TPU, where it would run the slow interpreter in place of the kernel.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional

import jax


@dataclasses.dataclass
class _State:
    use_pallas: bool = False


STATE = _State()


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` follows the platform; ``True`` on a TPU is an error."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("Pallas interpret mode was requested on a TPU "
                         "backend; kernels compile there")
    return interpret


@contextlib.contextmanager
def pallas_enabled():
    prev = STATE.use_pallas
    STATE.use_pallas = True
    try:
        yield
    finally:
        STATE.use_pallas = prev
