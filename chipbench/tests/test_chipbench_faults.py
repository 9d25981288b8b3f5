"""Each fault a cell can have, planted in the program under a run that
skips only the look for a chip: ``correct`` comes out false. The same run
unbroken comes out true."""
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_testlib as tl  # noqa: E402
import common  # noqa: E402

common.use_program()
import jax.numpy as jnp  # noqa: E402
from repro.launch import serve as serve_mod  # noqa: E402
from repro.launch import train as train_mod  # noqa: E402


def alter_decoded_token(monkeypatch):
    orig = serve_mod.ServeSession.decode_step

    def decode_step(self, n_steps=1):
        gen, t = orig(self, n_steps)
        col = n_steps // 2
        return gen.at[:, col].set((gen[:, col] + 1) % self.cfg.vocab_size), t
    monkeypatch.setattr(serve_mod.ServeSession, "decode_step", decode_step)


def alter_scored_token(monkeypatch):
    orig = serve_mod.ServeSession.prefill

    def prefill(self, batch):
        out = orig(self, batch)
        self.logits = jnp.roll(self.logits, 1, axis=-1)
        return out
    monkeypatch.setattr(serve_mod.ServeSession, "prefill", prefill)


def serve_half_the_batch(monkeypatch):
    orig = serve_mod.ServeSession.prefill

    def prefill(self, batch):
        tok = batch["tokens"]
        half = tok.shape[0] // 2
        return orig(self, dict(batch, tokens=jnp.concatenate(
            [tok[:half], tok[:half]])))
    monkeypatch.setattr(serve_mod.ServeSession, "prefill", prefill)


def broken_train_step(monkeypatch, how):
    orig = train_mod.compile_train_step

    def compile_train_step(*a, **kw):
        fn, *rest = orig(*a, **kw)

        def step(params, opt, batch):
            if how == "unchanged":
                _, _, metrics = fn(params, opt, batch)
                return params, opt, metrics
            half = batch["tokens"].shape[0] // 2
            return fn(params, opt, {k: v[:half] for k, v in batch.items()})
        return (step, *rest)
    monkeypatch.setattr(train_mod, "compile_train_step", compile_train_step)


EVAL_FAULTS = {"none": None, "token_altered": alter_decoded_token,
               "half_batch": serve_half_the_batch}


@pytest.mark.parametrize("fault", sorted(EVAL_FAULTS))
def test_eval_gen_faults(monkeypatch, fault):
    _, conf = tl.test_model(monkeypatch)
    mix = dict(tl.EVAL_GEN_MIX, sample_rows=8)
    if EVAL_FAULTS[fault]:
        EVAL_FAULTS[fault](monkeypatch)
    out = tl.run_test_cell(monkeypatch, conf, mix, tl.TEST_EVAL_LIMITS)
    assert out["correct"] is (fault == "none"), out["checks"]


@pytest.mark.parametrize("fault", ["none", "token_altered"])
def test_eval_score_faults(monkeypatch, fault):
    _, conf = tl.test_model(monkeypatch)
    mix = dict(tl.SCORE_MIX, prompt_len=32, sample_rows=16)
    if fault == "token_altered":
        alter_scored_token(monkeypatch)
    out = tl.run_test_cell(monkeypatch, conf, mix, tl.TEST_EVAL_LIMITS)
    assert out["correct"] is (fault == "none"), out["checks"]


@pytest.mark.parametrize("fault", ["none", "unchanged", "half_batch"])
def test_train_faults(monkeypatch, fault):
    tl.use_smoke(monkeypatch, "smollm-360m")
    conf = tl.smoke_conf("smollm-360m")
    if fault != "none":
        broken_train_step(monkeypatch, fault)
    out = tl.run_test_cell(monkeypatch, conf, tl.TRAIN_MIX,
                           tl.TEST_TRAIN_LIMITS, seconds=0.1)
    assert out["correct"] is (fault == "none"), out["checks"]


FOUR_CHIPS = r"""
import os, sys, json
sys.path.insert(0, {tests!r})
import jax
import chipbench_testlib as tl, common

class MP:
    def setattr(self, obj, name, value):
        setattr(obj, name, value)

_, conf = tl.test_model(MP())
conf.update(model_axis=4)
if {fault!r} == "exchange_left_out":
    # what one chip holds of the MLP, its partial sum never exchanged
    from repro.models import layers
    orig = layers.mlp
    def mlp(params, x, act, dtype):
        quarter = params["w1"].shape[-1] // 4
        return orig({{k: (v[..., :quarter] if k != "w2" else v[:quarter])
                     for k, v in params.items()}}, x, act, dtype)
    layers.mlp = mlp
out = tl.run_test_cell(MP(), conf, tl.EVAL_GEN_MIX, tl.TEST_EVAL_LIMITS,
                       devices=jax.devices()[:4])
print(json.dumps({{"correct": out["correct"], "checks": out["checks"]}}))
"""


@pytest.mark.parametrize("fault", ["none", "exchange_left_out"])
def test_four_chip_eval_faults(fault):
    """Tensor parallel over four (virtual CPU) devices, model_axis 4."""
    import json
    tests = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=common.SRC)
    proc = subprocess.run(
        [sys.executable, "-c", FOUR_CHIPS.format(tests=tests, fault=fault)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is (fault == "none"), out["checks"]


@pytest.mark.parametrize("kind", ["train", "eval"])
def test_the_program_init_is_its_own_after_a_run(monkeypatch, kind):
    """The benchmark's weights stand in for ``Model.init`` only while a
    kind builds its weights: after a run, every other user of the program
    gets the program's own."""
    from repro.models import Model
    own = Model.init
    if kind == "train":
        tl.use_smoke(monkeypatch, "smollm-360m")
        conf, mix = tl.smoke_conf("smollm-360m"), tl.TRAIN_MIX
        limits = tl.TEST_TRAIN_LIMITS
    else:
        _, conf = tl.test_model(monkeypatch)
        mix, limits = tl.SCORE_MIX, tl.TEST_EVAL_LIMITS
    tl.run_test_cell(monkeypatch, conf, mix, limits, seconds=0.1)
    assert Model.init is own
