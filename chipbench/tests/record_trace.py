#!/usr/bin/env python3
"""Record the small chip trace that test_chipbench_trace.py reads.

    python3 chipbench/tests/record_trace.py chipbench/tests/data

On one TPU chip: the program's smoke-size smollm session runs one prefill
and two decode steps under the benchmark's host spans, traced; the
``.xplane.pb`` is written gzipped to the given directory.
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


def main(out_dir: str) -> int:
    common.use_program()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import TraceAnnotation
    from repro.launch.serve import ServeSession
    common.check_devices(1)
    sess = ServeSession("smollm-360m", smoke=True, max_len=40,
                        devices=jax.devices()[:1])
    prompt = {"tokens": jnp.asarray(np.arange(4 * 32, dtype=np.int32)
                                    .reshape(4, 32) % 512)}
    for _ in range(2):            # compile outside the trace
        sess.prefill(prompt)
        sess.decode_step(2)
    tmp = tempfile.mkdtemp(prefix="chipbench_record_")
    jax.profiler.start_trace(tmp)
    with TraceAnnotation("eval.prefill"):
        sess.prefill(prompt)
    with TraceAnnotation("eval.decode"):
        gen, _ = sess.decode_step(2)
    with TraceAnnotation("eval.handoff"):
        np.asarray(gen)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(out_dir, exist_ok=True)
    with open(src, "rb") as f, gzip.open(
            os.path.join(out_dir, "eval_1chip.xplane.pb.gz"), "wb") as g:
        g.write(f.read())
    shutil.rmtree(tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
