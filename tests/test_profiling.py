"""The program's names for its own work (repro.utils.profiling): scopes in
the compiled programs' operation metadata, and the launchers' host spans
with their counts, as the JAX profiler records them."""
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.config import ParallelConfig, get_smoke
from repro.core.ft.supervisor import JobContext
from repro.launch.serve import ServeSession
from repro.launch.train import build_job
from repro.models import Model
from repro.utils.profiling import SCOPES, SPANS, scope, span

OP_NAME = re.compile(r'op_name="([^"]*)"')
# a transform around a name-stack entry: jvp(attn), transpose(jvp(layers))
TRANSFORM = re.compile(r"[\w.-]*\(|\)")


def op_names(compiled_text: str) -> list[str]:
    return OP_NAME.findall(compiled_text)


def scopes_in(names) -> set[str]:
    """The program scopes that some operation's path passes through."""
    return {TRANSFORM.sub("", part) for name in names
            for part in name.split("/")} & set(SCOPES)


@pytest.fixture(scope="module")
def smoke_programs(tmp_path_factory):
    """Compiled text of the smoke smollm's prefill and decode step
    (ServeSession) and train step (the launcher's job: remat on)."""
    devices = jax.devices()[:1]
    sess = ServeSession("smollm-360m", smoke=True, max_len=24,
                        devices=devices)
    prompt = {"tokens": jnp.zeros((2, 16), jnp.int32)}
    prefill = sess._prefill_fn.lower(sess.params, prompt).compile()
    _, caches = sess._prefill_fn(sess.params, prompt)
    decode = sess._step_fn.lower(sess.params, caches,
                                 jnp.zeros((2,), jnp.int32),
                                 jnp.int32(16)).compile()
    trainer, _, _ = build_job(get_smoke("smollm-360m"), global_batch=2,
                              seq_len=16, steps=1, ckpt_every=1,
                              ckpt_dir=str(tmp_path_factory.mktemp("ckpt")),
                              devices=devices)
    state = trainer.init_state()
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
             "labels": jnp.zeros((2, 16), jnp.int32),
             "weights": jnp.ones((2, 16), jnp.float32)}
    train = trainer.step_fn.lower(state.params, state.opt, batch).compile()
    return {"prefill": op_names(prefill.as_text()),
            "decode": op_names(decode.as_text()),
            "train": op_names(train.as_text())}


def test_the_compile_cache_keeps_the_scopes():
    assert jax.config.jax_compilation_cache_include_metadata_in_key


def test_names_are_listed_once_and_others_refused():
    assert len(set(SCOPES)) == len(SCOPES)
    assert len(set(SPANS)) == len(SPANS)
    with pytest.raises(ValueError, match="unknown scope"):
        scope("attention")
    with pytest.raises(ValueError, match="unknown span"):
        span("serve.other")


@pytest.mark.parametrize("program, present, absent", [
    ("prefill", {"embed", "layers", "norm", "attn", "kv_cache", "mlp",
                 "lm_head"}, {"loss", "optimizer"}),
    ("decode", {"embed", "layers", "norm", "attn", "kv_cache", "mlp",
                "lm_head"}, {"loss", "optimizer"}),
    ("train", {"embed", "layers", "norm", "attn", "mlp", "lm_head", "loss",
               "optimizer"}, {"kv_cache"}),
])
def test_compiled_programs_carry_their_scopes(smoke_programs, program,
                                              present, absent):
    found = scopes_in(smoke_programs[program])
    assert present <= found, present - found
    assert not absent & found


def test_scopes_reach_the_backward_pass(smoke_programs):
    names = smoke_programs["train"]
    assert any(n.startswith("jit(step)/transpose(jvp(layers))/")
               and "/attn/" in n for n in names)
    assert any("transpose(jvp(loss))" in n for n in names)


def test_decode_cache_writes_are_kv_cache_inside_attn(smoke_programs):
    writes = [n for n in smoke_programs["decode"]
              if n.endswith("dynamic_update_slice") and "/attn/" in n]
    assert writes and all("/attn/kv_cache/" in n for n in writes)


@pytest.mark.parametrize("arch, present", [
    ("deepseek-v2-lite-16b", {"attn", "kv_cache", "moe", "mlp"}),  # MLA
    ("jamba-1.5-large-398b", {"attn", "kv_cache", "ssm", "moe"}),
    ("whisper-large-v3", {"attn", "kv_cache", "mlp"}),      # cross-attn
])
def test_decode_scopes_of_other_families(arch, present):
    cfg = get_smoke(arch)
    model = Model(cfg, ParallelConfig(remat="none", moe_impl="dense"))
    params = model.init(jax.random.PRNGKey(0))
    caches = model.init_caches(2, 8)
    text = jax.jit(model.decode_step).lower(
        params, caches, jnp.zeros((2,), jnp.int32),
        jnp.int32(7)).compile().as_text()
    found = scopes_in(op_names(text))
    assert present <= found, present - found


def test_launchers_record_every_span_with_its_counts(tmp_path):
    devices = jax.devices()[:1]
    trainer, _, _ = build_job(get_smoke("smollm-360m"), global_batch=2,
                              seq_len=16, steps=2, ckpt_every=2,
                              ckpt_dir=str(tmp_path / "ckpt"),
                              devices=devices)
    sess = ServeSession("smollm-360m", smoke=True, max_len=20,
                        devices=devices)
    with jax.profiler.trace(str(tmp_path / "trace")):
        trainer.job(JobContext(start_step=0, attempt=0, skip_ranges=[],
                               healthy_nodes=1, resume_extra={}))
        trainer.ckpt.wait()
        sess.prefill({"tokens": jnp.zeros((3, 16), jnp.int32)})
        sess.decode_step(4)
        sess.restart()
    path, = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                      recursive=True)
    seen: dict = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in SPANS:
                    seen.setdefault(e.name, []).append(
                        {k: v for k, v in e.stats if not k.startswith("_")})
    assert set(seen) == set(SPANS)
    assert seen["trainer.step"] == [
        {"step_num": 0, "batch": 2, "seq_len": 16},
        {"step_num": 1, "batch": 2, "seq_len": 16}]
    for name in ("trainer.data", "trainer.sync", "trainer.host"):
        assert seen[name] == [{}, {}], name
    assert seen["trainer.ckpt"] == [{}]       # step 2 of ckpt_every 2
    assert seen["serve.prefill"] == [{"batch": 3, "prompt_len": 16}]
    assert seen["serve.decode"] == [{"batch": 3, "start": 16, "steps": 4}]
    assert seen["serve.restart"] == [{}]
