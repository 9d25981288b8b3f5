"""Prefill + decode must reproduce full-forward (teacher-forced) logits —
the strongest cache-correctness check, run per attention family."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import (AttentionConfig, ModelConfig, MoEConfig,
                          ParallelConfig, SSMConfig)
from repro.models import Model
from repro.serve import make_serve_step


def _decode_fn(model: Model, jitted: bool):
    """The model's decode step as called: eagerly, or jitted with its
    caches donated, as ``ServeSession`` runs it."""
    if jitted:
        return jax.jit(make_serve_step(model), donate_argnums=(1,))
    return model.decode_step


def _decode_parity(cfg: ModelConfig, atol: float = 1e-4,
                   jitted: bool = False):
    """prefill(prompt[:k]) + decode steps == forward(prompt) logits.

    Run in fp32: the full-forward (blockwise flash) and decode
    (cache-attention) paths are then numerically equivalent to ~1e-6;
    bf16 accumulation-order noise would need sloppy tolerances."""
    cfg = dataclasses.replace(cfg, dtype="float32")
    parallel = ParallelConfig(remat="none", moe_impl="dense",
                              decode_moe_impl="dense")
    model = Model(cfg, parallel)
    decode = _decode_fn(model, jitted)
    params = model.init(jax.random.PRNGKey(0))
    B, S, k = 2, 24, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size, dtype=jnp.int32)
    batch = {"tokens": toks}
    if cfg.frontend == "audio_stub":
        batch["frames"] = 0.02 * jax.random.normal(
            jax.random.PRNGKey(2), (B, cfg.encoder_seq, cfg.d_model))
    full = model.forward_logits(params, batch)          # (B, S, V)
    pre_batch = dict(batch, tokens=toks[:, :k])
    logits, caches = model.prefill(params, pre_batch)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(full[:, k - 1]),
                               rtol=atol, atol=atol)
    for t in range(k, S):
        logits, caches = decode(params, caches, toks[:, t], jnp.int32(t))
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full[:, t]),
            rtol=atol, atol=atol,
            err_msg=f"{cfg.name}: decode step {t}")


def _swa(tiny: ModelConfig) -> ModelConfig:
    return dataclasses.replace(
        tiny, name="swa",
        attention=dataclasses.replace(tiny.attention, sliding_window=8))


def _local_global(tiny: ModelConfig) -> ModelConfig:
    return dataclasses.replace(
        tiny, name="lg", num_layers=4,
        attention=dataclasses.replace(tiny.attention, global_every=2,
                                      local_window=8))


def _mla(_) -> ModelConfig:
    return ModelConfig(
        name="mla", num_layers=2, d_model=64, d_ff=128, vocab_size=256,
        max_seq_len=128, vocab_pad_multiple=64,
        attention=AttentionConfig(kind="mla", num_heads=4, num_kv_heads=4,
                                  kv_lora_rank=32, qk_nope_dim=16,
                                  qk_rope_dim=8, v_head_dim=16))


def _ssm(_) -> ModelConfig:
    return ModelConfig(
        name="ssm", family="ssm", num_layers=2, d_model=64, d_ff=0,
        vocab_size=256, max_seq_len=128, vocab_pad_multiple=64,
        ssm=SSMConfig(state_dim=16, head_dim=16, n_groups=1, chunk_size=8))


def _hybrid_moe(_) -> ModelConfig:
    return ModelConfig(
        name="hy", family="hybrid", num_layers=4, d_model=64, d_ff=128,
        vocab_size=256, max_seq_len=128, vocab_pad_multiple=64,
        attn_every=4, attn_index=1,
        attention=AttentionConfig(num_heads=4, num_kv_heads=2, head_dim=16),
        ssm=SSMConfig(state_dim=16, head_dim=16, n_groups=1, chunk_size=8),
        moe=MoEConfig(num_experts=4, top_k=2, expert_ff=64, moe_every=2,
                      moe_offset=1))


def _encdec(_) -> ModelConfig:
    return ModelConfig(
        name="ed", family="audio", num_layers=2, d_model=64, d_ff=128,
        vocab_size=256, max_seq_len=64, vocab_pad_multiple=64,
        encoder_layers=2, encoder_seq=12, frontend="audio_stub",
        mlp_act="gelu",
        attention=AttentionConfig(num_heads=4, num_kv_heads=4, head_dim=16,
                                  use_rope=False))


# family -> (config from the tiny GQA config, tolerance)
FAMILIES = {
    "gqa": (lambda tiny: tiny, 1e-4),
    "swa": (_swa, 1e-4),
    "local_global": (_local_global, 1e-4),
    "mla": (_mla, 1e-4),
    "ssm": (_ssm, 1e-3),
    "hybrid_moe": (_hybrid_moe, 1e-3),
    "encdec": (_encdec, 1e-4),
}


def test_decode_parity_gqa(tiny_cfg):
    _decode_parity(tiny_cfg)


def test_decode_parity_swa(tiny_cfg):
    _decode_parity(_swa(tiny_cfg))


def test_decode_parity_local_global(tiny_cfg):
    _decode_parity(_local_global(tiny_cfg))


def test_decode_parity_mla():
    _decode_parity(_mla(None))


def test_decode_parity_ssm():
    _decode_parity(_ssm(None), atol=1e-3)


def test_decode_parity_hybrid_moe():
    _decode_parity(_hybrid_moe(None), atol=1e-3)


def test_decode_parity_encdec():
    _decode_parity(_encdec(None))


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_decode_parity_jitted_donated(tiny_cfg, family):
    """The same parity through the jitted step with donated caches: the
    program the server runs, which updates the caches in place."""
    make, atol = FAMILIES[family]
    _decode_parity(make(tiny_cfg), atol=atol, jitted=True)


def _ring_roll(tiny_cfg: ModelConfig, jitted: bool):
    cfg = dataclasses.replace(
        tiny_cfg, name="roll", max_seq_len=8, dtype="float32",
        attention=dataclasses.replace(tiny_cfg.attention, sliding_window=8))
    parallel = ParallelConfig(remat="none")
    model = Model(cfg, parallel)
    decode = _decode_fn(model, jitted)
    params = model.init(jax.random.PRNGKey(0))
    S = 24    # 3x the window/cache
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, S), 0,
                              cfg.vocab_size, dtype=jnp.int32)
    full = model.forward_logits(params, {"tokens": toks})
    logits, caches = model.prefill(params, {"tokens": toks[:, :8]})
    for t in range(8, S):
        logits, caches = decode(params, caches, toks[:, t], jnp.int32(t))
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[:, t]),
                                   rtol=1e-4, atol=1e-4)


def test_ring_buffer_rolls_past_window(tiny_cfg):
    """Decoding far past the SWA window must equal the windowed forward."""
    _ring_roll(tiny_cfg, jitted=False)


def test_ring_buffer_rolls_past_window_jitted_donated(tiny_cfg):
    _ring_roll(tiny_cfg, jitted=True)


def test_decode_step_updates_the_cache_in_place():
    """The jitted, donated decode step needs no second copy of the cache:
    from one cache length to another its temporary bytes grow by less
    than a quarter of what the cache grows (the CPU's working copies of
    one layer's K and V are an eighth of it here), where slicing each
    layer's cache out of the stack and restacking it made them grow by
    more than the cache.

    In float32: the CPU backend computes bfloat16 elementwise work in
    float32 and hoists a float32 copy of a bfloat16 cache out of the layer
    loop, which says nothing of the program a TPU runs."""
    cfg = ModelConfig(
        name="deep", num_layers=8, d_model=64, d_ff=128, vocab_size=256,
        max_seq_len=256, vocab_pad_multiple=64, dtype="float32",
        attention=AttentionConfig(num_heads=4, num_kv_heads=2, head_dim=16))
    model = Model(cfg, ParallelConfig(remat="none"))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    step = jax.jit(make_serve_step(model), donate_argnums=(1,))
    B = 8

    def cache_and_temp_bytes(cache_len: int) -> tuple[int, int]:
        caches = jax.eval_shape(lambda: model.init_caches(B, cache_len))
        compiled = step.lower(params, caches,
                              jax.ShapeDtypeStruct((B,), jnp.int32),
                              jax.ShapeDtypeStruct((), jnp.int32)).compile()
        cache = sum(x.size * x.dtype.itemsize
                    for x in jax.tree_util.tree_leaves(caches))
        return cache, compiled.memory_analysis().temp_size_in_bytes

    (cache0, temp0), (cache1, temp1) = (cache_and_temp_bytes(64),
                                        cache_and_temp_bytes(256))
    assert temp1 - temp0 < (cache1 - cache0) / 4, (temp0, temp1,
                                                   cache0, cache1)


@pytest.mark.parametrize("cache_len,slot,block", [
    (1280, 0, 128), (1280, 127, 128), (1280, 128, 128), (1280, 1279, 128),
    (256, 200, 128), (96, 70, 32), (64, 5, 64)])
def test_block_slot_write_equals_one_slot_write(cache_len, slot, block):
    """A lane-aligned block write of one decode step's entries changes the
    stacked ring buffer exactly as the one-slot write does."""
    from repro.models.model import _write_slot

    shape = (3, 2, cache_len, 5, 4)            # (layers, B, L, KV, D)
    x = jax.random.normal(jax.random.PRNGKey(0), shape)
    u = jax.random.normal(jax.random.PRNGKey(1), shape[:2] + (1,) + shape[3:])
    write = jax.jit(_write_slot, static_argnums=3)
    got = write(x, u, jnp.int32(slot), block)
    want = np.asarray(x).copy()
    want[:, :, slot] = np.asarray(u)[:, :, 0]
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(
        np.asarray(write(x, u, jnp.int32(slot), 1)), want)


def test_serve_session_stream_is_contiguous():
    """ServeSession emits a non-overlapping greedy token stream: two
    chained decode_step calls must equal one generate of the same total."""
    from repro.launch.serve import ServeSession

    sess = ServeSession("smollm-360m", smoke=True)
    batch = sess.make_batch(2, 8, seed=3)
    gen, tp, td = sess.generate(batch, 6)
    assert gen.shape == (2, 6)
    assert (tp.phase, tp.batch, tp.tokens) == ("prefill", 2, 16)
    assert (td.phase, td.batch, td.tokens) == ("decode", 2, 12)
    assert tp.seconds >= 0.0 and td.tokens_per_s > 0.0

    sess2 = ServeSession("smollm-360m", smoke=True)
    sess2.prefill(batch)
    a, _ = sess2.decode_step(2)
    b, _ = sess2.decode_step(4)
    chained = np.concatenate([np.asarray(a), np.asarray(b)], axis=1)
    np.testing.assert_array_equal(chained, np.asarray(gen))
