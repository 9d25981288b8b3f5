#!/usr/bin/env python3
"""Readings that the limits in ``limits/<cell>.json`` are set from.

    python3 chipbench/calibrate.py --workload smollm-360m.eval-gen \
        --seeds 11,12,13

For each seed, in one process and at the cell's own sizes: the number(s)
that a run of the cell compares, read from the program (the lower
readings), from the control (the plain reference computed in float8, the
precision below the configuration's bf16: the upper readings) and, for a
training cell, from the reference with half of every batch left out. One
JSON line per seed on standard output. The benchmark's own runs never run
this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import common  # noqa: E402


def eval_rows(conf, mix, seeds, devices):
    kind = common.load_module("kinds", "eval")
    sess = kind.build(conf, mix, seeds[0], devices)
    for i, seed in enumerate(seeds):
        if i:
            sess.params = None
            gc.collect()
            sess.params = common.program_weights(seed, conf)(sess.model)
        traffic = kind.EvalTraffic(seed, conf["vocab_size"], mix["batch"],
                                   mix["prompt_len"])
        served, _, _ = kind.window(sess, traffic, mix["gen_len"], 0.0,
                                   kind.min_batches(mix), warm=i == 0)
        full, judged, last = kind.requests(served, traffic,
                                           kind.sample(served, mix, seed))
        del served
        ref = kind.reference_logits(seed, conf, full, mix["prompt_len"],
                                    device=devices[0])
        low = kind.reference_logits(seed, conf, full, mix["prompt_len"],
                                    prec="fp8", device=devices[0])
        yield {"seed": seed, "served_tokens": int(judged.size),
               "program": kind.readings(ref, judged, last),
               "control": kind.control_readings(ref, low, judged.shape[1])}


def train_rows(conf, mix, seeds, devices):
    kind = common.load_module("kinds", "train")
    for seed in seeds:
        with tempfile.TemporaryDirectory(prefix="chipbench_ckpt_") as d:
            trainer = kind.build(conf, mix, seed, devices, d)
            hook = kind.StepHook(trainer, trainer.step_fn, mix, 0.0)
            kind.run_job(trainer, hook, seed, conf)
            hook.finish()
            prog = kind.program_readings(hook)
            del trainer, hook
            gc.collect()
        ref = kind.reference_readings(seed, conf, mix)
        low = kind.reference_readings(seed, conf, mix, prec="fp8")
        half = kind.reference_readings(seed, conf, mix,
                                       rows=mix["batch"] // 2)
        yield {"seed": seed, "program": kind.readings(prog, ref),
               "control": kind.readings(low, ref),
               "fault_half_batch": kind.readings(half, ref),
               "reference_losses": ref["losses"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    args = ap.parse_args(argv)
    cell, conf, mix, _ = common.cell_files(common.manifest(), args.workload)
    common.use_program()
    common.enable_compile_cache()
    devices = common.check_devices(cell["chips"])
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = train_rows if mix["kind"] == "train" else eval_rows
    for row in rows(conf, mix, seeds, devices):
        print(json.dumps(dict(row, workload=args.workload)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
