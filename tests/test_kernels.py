"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracles,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention, flash_attention_ref
from repro.kernels.rmsnorm import rmsnorm, rmsnorm_ref
from repro.kernels.ssd import ssd, ssd_ref

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("B,Sq,Skv,H,KV,D", [
    (2, 64, 64, 4, 2, 32),
    (1, 96, 96, 8, 8, 16),
    (2, 33, 128, 4, 1, 64),     # ragged Sq, MQA
    (1, 128, 48, 6, 3, 24),     # ragged Skv
])
@pytest.mark.parametrize("causal,window,softcap", [
    (True, 0, 0.0), (True, 24, 0.0), (False, 0, 0.0), (True, 0, 30.0),
])
def test_flash_attention_matches_ref(B, Sq, Skv, H, KV, D, causal, window,
                                     softcap):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Skv, KV, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Skv, KV, D), jnp.float32)
    # causal: align q to the TAIL of kv when the prompt is longer, else
    # plain positions (q beyond kv attends to everything available)
    off = max(Skv - Sq, 0)
    qp = jnp.arange(off, off + Sq, dtype=jnp.int32)
    kp = jnp.arange(Skv, dtype=jnp.int32)
    out = flash_attention(q, k, v, qp, kp, causal=causal, window=window,
                          softcap=softcap, block_q=32, block_kv=32)
    ref = flash_attention_ref(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        jnp.broadcast_to(qp, (B, Sq)), jnp.broadcast_to(kp, (B, Skv)),
        causal=causal, window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.swapaxes(ref, 1, 2)),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)])
def test_flash_attention_dtypes(dtype, tol):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 64, 4, 32), dtype)
    k = jax.random.normal(ks[1], (1, 64, 2, 32), dtype)
    v = jax.random.normal(ks[2], (1, 64, 2, 32), dtype)
    pos = jnp.arange(64, dtype=jnp.int32)
    out = flash_attention(q, k, v, pos, pos, block_q=32, block_kv=32)
    ref = flash_attention_ref(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                              jnp.swapaxes(v, 1, 2),
                              pos[None], pos[None])
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(jnp.swapaxes(ref, 1, 2), np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("shape", [(4, 17, 96), (2, 100), (3, 5, 7, 32)])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)])
def test_rmsnorm_matches_ref(shape, dtype, tol):
    x = jax.random.normal(KEY, shape, dtype)
    s = jax.random.normal(jax.random.fold_in(KEY, 1), shape[-1:], jnp.float32)
    out = rmsnorm(x, s)
    ref = rmsnorm_ref(x, s)
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("B,L,H,P,G,N,chunk", [
    (2, 64, 4, 16, 2, 32, 16),
    (1, 100, 2, 8, 1, 16, 32),   # ragged L
    (2, 128, 8, 32, 8, 64, 64),  # G == H
])
def test_ssd_matches_ref(B, L, H, P, G, N, chunk):
    ks = jax.random.split(jax.random.fold_in(KEY, L), 5)
    x = jax.random.normal(ks[0], (B, L, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (H,), jnp.float32) * 0.5)
    Bm = jax.random.normal(ks[3], (B, L, G, N), jnp.float32) * 0.5
    Cm = jax.random.normal(ks[4], (B, L, G, N), jnp.float32) * 0.5
    y, st = ssd(x, dt, A, Bm, Cm, chunk=chunk)
    yr, sr = ssd_ref(x, dt, A, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(sr),
                               rtol=3e-4, atol=3e-4)


def test_ssd_head_blocked_equals_unblocked():
    from repro.models.mamba import ssd_chunked
    ks = jax.random.split(KEY, 5)
    B, L, H, P, G, N = 2, 64, 32, 4, 8, 8
    x = jax.random.normal(ks[0], (B, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, L, G, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, L, G, N)) * 0.5
    y0 = ssd_chunked(x, dt, A, Bm, Cm, chunk=16)
    for hb in (2, 4, 8, 16):
        y1 = ssd_chunked(x, dt, A, Bm, Cm, chunk=16, head_block=hb)
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                                   rtol=1e-5, atol=1e-5)


def test_interpret_mode_follows_the_platform(monkeypatch):
    """Interpret off the TPU, compiled on it; interpret on a TPU is refused."""
    from repro.kernels import runtime
    assert runtime.resolve_interpret(None) is True
    assert runtime.resolve_interpret(False) is False
    monkeypatch.setattr(runtime.jax, "default_backend", lambda: "tpu")
    assert runtime.resolve_interpret(None) is False
    with pytest.raises(ValueError, match="interpret"):
        runtime.resolve_interpret(True)


def test_pallas_attention_refuses_a_traced_window():
    from repro.kernels import runtime
    from repro.models import attention
    x = jnp.zeros((1, 8, 2, 16), jnp.float32)
    pos = jnp.arange(8, dtype=jnp.int32)
    with runtime.pallas_enabled(), pytest.raises(TypeError, match="window"):
        attention.flash_attention(x, x, x, q_positions=pos, kv_positions=pos,
                                  window=jnp.int32(4))


@pytest.mark.parametrize("arch", ["tiny", "mamba2-1.3b"])
def test_model_forward_through_kernels_matches_jnp(tiny_cfg, arch):
    """pallas_enabled() routes attention / the SSD scan through the
    kernels (interpret mode here) without changing the model's output."""
    import dataclasses
    from repro.config import get_smoke
    from repro.kernels import runtime
    from repro.models import Model
    cfg = tiny_cfg if arch == "tiny" else get_smoke(arch)
    model = Model(dataclasses.replace(cfg, dtype="float32"))
    params = model.init(KEY)
    toks = jax.random.randint(KEY, (2, 64), 0, cfg.vocab_size, jnp.int32)
    ref = model.forward_logits(params, {"tokens": toks})
    with runtime.pallas_enabled():
        out = model.forward_logits(params, {"tokens": toks})
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
