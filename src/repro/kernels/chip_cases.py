"""The main path's kernels at the widths of the configs that use them.

``tests/test_tpu_compile.py`` compiles these cases for a described TPU v5e
and ``chip_smoke.py`` runs them on the chip against their oracles, so both
always cover the same shapes. Widths come from the registered configs;
batch and sequence are one prefill's worth.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.config import get_arch
from repro.kernels.flash_attention import flash_attention, flash_attention_ref
from repro.kernels.ssd import ssd, ssd_ref


@dataclasses.dataclass(frozen=True)
class KernelCase:
    name: str
    kernel: Callable[..., jax.Array]      # kernel(*args, interpret=...)
    ref: Callable[..., jax.Array]         # pure-jnp oracle, same args
    shapes: tuple[jax.ShapeDtypeStruct, ...]
    make_args: Callable[[jax.Array], tuple]


def flash_case(arch: str, batch: int = 2, seq: int = 2048) -> KernelCase:
    a = get_arch(arch).attention
    H, KV, D = a.num_heads, a.num_kv_heads, a.head_dim
    bf16 = jnp.bfloat16
    shapes = (jax.ShapeDtypeStruct((batch, seq, H, D), bf16),
              jax.ShapeDtypeStruct((batch, seq, KV, D), bf16),
              jax.ShapeDtypeStruct((batch, seq, KV, D), bf16),
              jax.ShapeDtypeStruct((batch, seq), jnp.int32),
              jax.ShapeDtypeStruct((batch, seq), jnp.int32))

    def kernel(q, k, v, qp, kp, interpret=None):
        return flash_attention(q, k, v, qp, kp, causal=True,
                               interpret=interpret)

    def ref(q, k, v, qp, kp):
        t = lambda x: jnp.swapaxes(x, 1, 2)
        return t(flash_attention_ref(t(q), t(k), t(v), qp, kp, causal=True))

    def make_args(key):
        ks = jax.random.split(key, 3)
        pos = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), (batch, seq))
        return tuple(jax.random.normal(k, s.shape, s.dtype)
                     for k, s in zip(ks, shapes)) + (pos, pos)

    return KernelCase(f"flash_attention[{arch}]", kernel, ref, shapes,
                      make_args)


def ssd_case(arch: str, batch: int = 2, seq: int = 2048) -> KernelCase:
    cfg = get_arch(arch)
    s = cfg.ssm
    H, P, G, N = s.num_ssm_heads(cfg.d_model), s.head_dim, s.n_groups, s.state_dim
    chunk = s.chunk_size
    bf16, f32 = jnp.bfloat16, jnp.float32
    shapes = (jax.ShapeDtypeStruct((batch, seq, H, P), bf16),
              jax.ShapeDtypeStruct((batch, seq, H), f32),
              jax.ShapeDtypeStruct((H,), f32),
              jax.ShapeDtypeStruct((batch, seq, G, N), bf16),
              jax.ShapeDtypeStruct((batch, seq, G, N), bf16))

    def kernel(x, dt, A, Bm, Cm, interpret=None):
        return ssd(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)[0]

    def ref(x, dt, A, Bm, Cm):
        up = lambda a: a.astype(f32)
        return ssd_ref(up(x), dt, A, up(Bm), up(Cm), chunk=chunk)[0]

    def make_args(key):
        ks = jax.random.split(key, 5)
        x = jax.random.normal(ks[0], shapes[0].shape, bf16)
        dt = jax.nn.softplus(jax.random.normal(ks[1], shapes[1].shape, f32))
        A = -jnp.exp(0.5 * jax.random.normal(ks[2], (H,), f32))
        Bm = (0.5 * jax.random.normal(ks[3], shapes[3].shape, f32)).astype(bf16)
        Cm = (0.5 * jax.random.normal(ks[4], shapes[4].shape, f32)).astype(bf16)
        return x, dt, A, Bm, Cm

    return KernelCase(f"ssd[{arch}]", kernel, ref, shapes, make_args)


def chip_cases() -> list[KernelCase]:
    return [flash_case("smollm-360m"), flash_case("internlm-7b"),
            ssd_case("mamba2-1.3b")]
