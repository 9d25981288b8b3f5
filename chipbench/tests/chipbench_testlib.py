"""Helpers for the benchmark's own tests: its kinds driven on the CPU at a
small size, with the program's registered small (smoke) configurations."""
from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
import common  # noqa: E402

common.use_program()


def smoke_conf(arch: str) -> dict:
    """The configuration file's keys for the program's smoke config."""
    from repro.config import get_smoke
    cfg = get_smoke(arch)
    a = cfg.attention
    return {"arch": arch, "reference": "dense", "model_axis": 1,
            "num_hidden_layers": cfg.num_layers, "hidden_size": cfg.d_model,
            "intermediate_size": cfg.d_ff, "num_attention_heads": a.num_heads,
            "num_key_value_heads": a.num_kv_heads, "head_dim": a.head_dim,
            "vocab_size": cfg.vocab_size,
            "tie_word_embeddings": cfg.tie_embeddings,
            "rope_theta": a.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "bias": False, "hidden_act": "silu",
            "embed_scale_sqrt_hidden": True, "param_dtype": "float32",
            "compute_dtype": cfg.dtype}


def use_smoke(monkeypatch, arch: str) -> None:
    from repro.config import get_smoke
    monkeypatch.setattr(common, "program_config",
                        lambda conf: get_smoke(arch))


TRAIN_MIX = {"kind": "train", "batch": 2, "seq_len": 32, "warmup_calls": 4,
             "optimizer": {"learning_rate": 3e-4, "warmup_steps": 1,
                           "total_steps": 10, "weight_decay": 0.1,
                           "grad_clip": 1.0, "adam_b1": 0.9, "adam_b2": 0.95,
                           "adam_eps": 1e-8},
             "z_loss": 1e-4}
GEN_MIX = {"kind": "eval", "batch": 4, "prompt_len": 16, "gen_len": 8,
           "sample_rows": 3}
SCORE_MIX = {"kind": "eval", "batch": 4, "prompt_len": 16, "gen_len": 0,
             "sample_rows": 6}


class Ctx:
    """A kind's context without the harness's look for a chip."""

    def __init__(self, conf, mix, seed=3, seconds=0.5, tracer=None):
        import jax
        self.conf, self.mix, self.seed, self.seconds = conf, mix, seed, seconds
        self.devices = jax.devices()[:1]
        self.tracer = tracer
        self.cell = {"chips": 1}
        self.t_window = None

    def mark(self, name):
        pass

    def window_started(self, t):
        self.t_window = t


def test_model(monkeypatch, arch: str = "internlm-7b", vocab: int = 2048,
               layers: int = 0):
    """(ModelConfig, configuration dict) of a small model the program runs
    on the CPU. The untied internlm-7b smoke model with a 2048-token
    vocabulary has near-ties among its top logits, so a precision too low
    for its widths shows in the served tokens."""
    import dataclasses
    from repro.config import get_smoke
    cfg = dataclasses.replace(get_smoke(arch), vocab_size=vocab,
                              num_layers=layers or get_smoke(arch).num_layers)
    monkeypatch.setattr(common, "program_config", lambda conf: cfg)
    conf = smoke_conf(arch)
    conf.update(vocab_size=vocab, num_hidden_layers=cfg.num_layers)
    return cfg, conf


# limits at the test size, set like the cells' own (limits/<cell>.json) from
# CPU readings of the program (lower) and of the fp8 control (upper) on
# seeds 5-8: eval widest logit gap 0-0.019 against 0.014-0.28 and last
# logits' error 0.0063-0.0085 against 0.064-0.086; training at smollm-360m
# smoke size, losses 0.005-0.006 against 0.012-0.021, first gradient's
# norms 0.0014-0.0015 against 0.018-0.022 and its difference 0.0118-0.0122
# against 0.148-0.160 (half the batch: 0.76-0.79)
TEST_EVAL_LIMITS = {"widest_logit_gap": 0.04, "logit_err": 0.025}
TEST_TRAIN_LIMITS = {"loss_gap": 0.009, "grad_norm_gap": 0.006,
                     "grad_diff": 0.04, "change_norm_gap": 0.003}
EVAL_GEN_MIX = dict(GEN_MIX, prompt_len=32, gen_len=16, sample_rows=4)


def run_test_cell(monkeypatch, conf: dict, mix: dict, limits: dict,
                  seed: int = 4, seconds: float = 0.2, devices=None) -> dict:
    """run.run_cell on the CPU for a cell given as data here."""
    import jax
    devices = devices or jax.devices()[:1]
    cell = {"name": "test.cell", "config": "test", "traffic": "test",
            "chips": len(devices), "why": "test"}
    monkeypatch.setattr(common, "cell_files",
                        lambda bench, workload: (cell, conf, mix, limits))
    bench = {"end_to_end": [{"name": "setup_s", "unit": "s"}],
             "per_layer": []}
    run = common.load_module("", "run")
    return run.run_cell(bench, "test.cell", seed, seconds, 0, devices, {})
