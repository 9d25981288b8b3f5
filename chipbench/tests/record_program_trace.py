#!/usr/bin/env python3
"""Record the small chip trace that test_chipbench_scopes.py reads.

    python3 chipbench/tests/record_program_trace.py chipbench/tests/data

On one TPU chip, with the program's smoke-size smollm configuration and
its own scopes and spans: one training step through ``Trainer.job`` (from
a checkpoint, and saving one), then one ``ServeSession`` prefill and a
decode of ``DECODE_STEPS`` tokens, traced; everything is compiled first.
The ``.xplane.pb`` is written gzipped to the given directory as
``program_1chip.xplane.pb.gz``.
"""
from __future__ import annotations

import glob
import gzip
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import common  # noqa: E402

FILE = "program_1chip.xplane.pb.gz"
TRAIN_BATCH, SEQ_LEN = 2, 32
SERVE_BATCH, PROMPT_LEN, DECODE_STEPS = 4, 32, 2


def train_job(ckpt_dir: str):
    """A smoke job that checkpoints every step, and a call that runs its
    next step: step 1 from fresh weights, each later one from the last
    checkpoint (a restore, which compiles nothing)."""
    import jax
    from repro.config import get_smoke
    from repro.core.ft.supervisor import JobContext
    from repro.launch.train import build_job
    trainer, _, _ = build_job(get_smoke("smollm-360m"),
                              global_batch=TRAIN_BATCH, seq_len=SEQ_LEN,
                              steps=10, ckpt_every=1, ckpt_dir=ckpt_dir,
                              devices=jax.devices()[:1])

    def next_step() -> None:
        start = len(trainer.history)
        trainer.total_steps = start + 1
        trainer.job(JobContext(start_step=start, attempt=0, skip_ranges=[],
                               healthy_nodes=1, resume_extra={}))
        trainer.ckpt.wait()
    return next_step


def main(out_dir: str) -> int:
    common.use_program()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.serve import ServeSession
    common.check_devices(1)
    ckpt_dir = tempfile.mkdtemp(prefix="chipbench_record_ckpt_")
    train_step = train_job(ckpt_dir)
    sess = ServeSession("smollm-360m", smoke=True,
                        max_len=PROMPT_LEN + DECODE_STEPS,
                        devices=jax.devices()[:1])
    prompt = {"tokens": jnp.asarray(
        np.arange(SERVE_BATCH * PROMPT_LEN, dtype=np.int32)
        .reshape(SERVE_BATCH, PROMPT_LEN) % 512)}
    for _ in range(2):            # compile outside the trace
        train_step()
        sess.prefill(prompt)
        np.asarray(sess.decode_step(DECODE_STEPS)[0])
    tmp = tempfile.mkdtemp(prefix="chipbench_record_")
    jax.profiler.start_trace(tmp)
    train_step()
    sess.prefill(prompt)
    np.asarray(sess.decode_step(DECODE_STEPS)[0])
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(out_dir, exist_ok=True)
    with open(src, "rb") as f, gzip.open(os.path.join(out_dir, FILE),
                                         "wb") as g:
        g.write(f.read())
    shutil.rmtree(tmp)
    shutil.rmtree(ckpt_dir)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
