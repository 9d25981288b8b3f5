"""The control, the plain reference computed in float8 in the program's
place, comes out as not correct; the program, on the same seeds, as correct.
At a size a test run holds, with limits set the way the cells' are."""
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_testlib as tl  # noqa: E402
import common  # noqa: E402

calibrate = common.load_module("", "calibrate")


def test_eval_control_fails_the_program_passes(monkeypatch):
    _, conf = tl.test_model(monkeypatch)
    rows = list(calibrate.eval_rows(conf, tl.EVAL_GEN_MIX, [5, 6, 7],
                                    jax.devices()[:1]))
    for row in rows:
        assert row["served_tokens"] == 4 * 16
        assert common.judge(row["program"], tl.TEST_EVAL_LIMITS)[0], row
        assert not common.judge(row["control"], tl.TEST_EVAL_LIMITS)[0], row


def test_train_control_fails_the_program_passes(monkeypatch):
    tl.use_smoke(monkeypatch, "smollm-360m")
    conf = tl.smoke_conf("smollm-360m")
    rows = list(calibrate.train_rows(conf, tl.TRAIN_MIX, [5, 6, 7],
                                     jax.devices()[:1]))
    for row in rows:
        assert common.judge(row["program"], tl.TEST_TRAIN_LIMITS)[0], row
        assert not common.judge(row["control"], tl.TEST_TRAIN_LIMITS)[0], row
        assert not common.judge(row["fault_half_batch"],
                                tl.TEST_TRAIN_LIMITS)[0], row


def test_reference_logits_agree_with_its_training_loss(monkeypatch):
    """The reference's two paths (layer by layer for serving, scanned for
    training) compute one model."""
    import numpy as np
    _, conf = tl.test_model(monkeypatch, vocab=512)
    ref = common.load_module("reference", "dense")
    tokens = np.arange(2 * 12, dtype=np.int32).reshape(2, 12) * 37 % 512
    logits = ref.logits_at(9, conf, tokens, 0, rows_per_block=1)
    lse = jax.nn.logsumexp(logits[:, :-1], axis=-1)
    ll = np.take_along_axis(logits[:, :-1], tokens[:, 1:, None], -1)[..., 0]
    want = float(np.mean(lse - ll + 1e-4 * lse * lse))
    got = float(ref.loss(conf, ref.all_weights(9, conf), tokens[:, :-1],
                         tokens[:, 1:], 1e-4))
    assert got == pytest.approx(want, rel=1e-5)
