"""Pretraining steps through the program's ``Trainer.job``.

Set-up builds the launcher's job (``launch/train.build_job``) once, with the
benchmark's weights and token batches, and the job's own loop drives it: its
first ``warmup_calls`` steps are set-up, the steps after them the window,
which ends with the first step that finishes after ``--seconds``. No
checkpoint and no injected fault falls in the window.

Traffic: ``batch`` rows of ``seq_len + 1`` token ids, uniform over the
vocabulary, drawn from the seed and the step; every row differs.

Correctness: the reference follows the first three steps from the same
seed. Compared, each by its worst leaf: the norm of each leaf of the first
gradient as AdamW received it, read back from its first moment; the norm
of that gradient's difference from the reference's; and the norm of each
leaf's change over the three steps. Each step's loss is read beside them.
"""
from __future__ import annotations

import gc
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

import common

NEVER = 1 << 40
CHECKED_STEPS = 3
# leaves whose reference gradient is under this share of the median leaf's
# move by round-off alone under Adam: their change is not compared
STILL_LEAF = 1e-3


class TrainTraffic:
    """The DataLoader's dataset: batch(step) from the seed, pure."""

    def __init__(self, seed: int, vocab: int, batch: int, seq_len: int):
        self.seed, self.vocab = seed, vocab
        self.shape = (batch, seq_len + 1)

    def batch(self, step: int) -> dict:
        with TraceAnnotation("train.data"):
            rng = np.random.default_rng([self.seed, 0, step])
            toks = rng.integers(0, self.vocab, self.shape, dtype=np.int32)
            return {"tokens": toks[:, :-1], "labels": toks[:, 1:],
                    "weights": np.ones((self.shape[0], self.shape[1] - 1),
                                       np.float32)}


def leaf_norms(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    norm = jax.jit(lambda x: jnp.linalg.norm(x.astype(jnp.float32)))
    return {common.leaf_name(p): float(norm(x)) for p, x in flat}


def host_leaves(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {common.leaf_name(p): np.asarray(jax.device_get(x))
            for p, x in flat}


class StepHook:
    """Stands in for the job's ``step_fn``: calls it, and around it reads
    what the check needs, starts the window and ends it."""

    def __init__(self, trainer, step_fn, mix: dict, seconds: float,
                 tracer=None):
        self.trainer, self.step_fn = trainer, step_fn
        self.warmup = mix["warmup_calls"]
        self.b1 = mix["optimizer"]["adam_b1"]
        self.shape = (mix["batch"], mix["seq_len"])
        self.seconds, self.tracer = seconds, tracer
        self.calls = 0
        self.losses: list[float] = []
        self.grad_norms: dict = {}
        self.grad: dict = {}
        self.start_params = self.checked_params = None
        self.t_start = self.t_end = None
        self.last = None

    def __call__(self, params, opt, batch):
        self.calls += 1
        k = self.calls
        if k == 1:
            self.start_params = host_leaves(params)
        with TraceAnnotation("train.step"):
            out = self.step_fn(params, opt, batch)
        self.last = out
        if k <= CHECKED_STEPS:
            self.losses.append(float(out[2]["loss"]))
        if k == 1:
            # AdamW's first moment after one step is (1 - b1) * gradient
            self.grad_norms = {n: v / (1 - self.b1)
                               for n, v in leaf_norms(out[1].m).items()}
            self.grad = {n: x / np.float32(1 - self.b1)
                         for n, x in host_leaves(out[1].m).items()}
        if k == CHECKED_STEPS:
            self.checked_params = host_leaves(out[0])
        if k == self.warmup:
            jax.block_until_ready(out)
            self.t_start = time.perf_counter()
            if self.tracer is not None:
                self.tracer.start()
        elif k > self.warmup:
            if self.tracer is not None and self.tracer.active:
                self.tracer.unit_done(train=self.shape, block=out)
            if time.perf_counter() - self.t_start >= self.seconds:
                self.trainer.total_steps = k
        return out

    def finish(self) -> float:
        jax.block_until_ready(self.last)
        self.t_end = time.perf_counter()
        if self.tracer is not None:
            self.tracer.stop()
        return self.t_end - self.t_start


def build(conf: dict, mix: dict, seed: int, devices: list, ckpt_dir: str):
    """The launcher's job with the benchmark's weights and batches."""
    common.use_program()
    from repro.launch.train import build_job
    cfg = common.program_config(conf)
    common.check_program_config(cfg, conf)
    opt = mix["optimizer"]
    trainer, _, _ = build_job(
        cfg, global_batch=mix["batch"], seq_len=mix["seq_len"],
        steps=opt["total_steps"], ckpt_every=NEVER, ckpt_dir=ckpt_dir,
        model_axis=conf["model_axis"], devices=devices, log_every=NEVER)
    tcfg = trainer.tcfg
    bad = {k: (getattr(tcfg, k), v) for k, v in opt.items()
           if getattr(tcfg, k) != v}
    if bad:
        raise common.BenchError(f"the launcher's optimizer differs from the "
                                f"traffic file (launcher, file): {bad}")
    trainer.dataset = TrainTraffic(seed, conf["vocab_size"], mix["batch"],
                                   mix["seq_len"])
    trainer.total_steps = NEVER
    return trainer


def run_job(trainer, hook, seed: int, conf: dict) -> None:
    """The job's own loop, its weights made from ``seed``."""
    common.use_program()
    from repro.core.ft.supervisor import JobContext
    trainer.step_fn = hook
    with common.seeded_init(seed, conf):
        trainer.job(JobContext(start_step=0, attempt=0, skip_ranges=[],
                               healthy_nodes=1, resume_extra={}))


def gap(prog: dict, ref: dict, names=None) -> float:
    """Worst leaf: |program norm - reference norm| over the larger of the
    reference's norm of that leaf and of the median leaf."""
    names = list(ref) if names is None else names
    median = float(np.median([ref[n] for n in ref]))
    return max(abs(prog[n] - ref[n]) / max(ref[n], median) for n in names)


def diff_gap(prog: dict, ref: dict, ref_norms: dict) -> float:
    """Worst leaf: ||program - reference|| over the larger of the
    reference's norm of that leaf and of the median leaf."""
    median = float(np.median(list(ref_norms.values())))
    return max(float(np.linalg.norm(prog[n].astype(np.float64) - ref[n]))
               / max(ref_norms[n], median) for n in ref)


def readings(prog: dict, ref: dict) -> dict:
    """The numbers read, from the program's and the reference's losses,
    first gradients and change norms."""
    median_g = float(np.median(list(ref["grad_norms"].values())))
    moving = [n for n, g in ref["grad_norms"].items()
              if g >= STILL_LEAF * median_g]
    return {
        "loss_gap": max(abs(a - b) for a, b in
                        zip(prog["losses"], ref["losses"])),
        "grad_norm_gap": gap(prog["grad_norms"], ref["grad_norms"]),
        "grad_diff": diff_gap(prog["grad"], ref["grad"], ref["grad_norms"]),
        "change_norm_gap": gap(prog["change_norms"], ref["change_norms"],
                               moving),
    }


def program_readings(hook) -> dict:
    change = {n: float(np.linalg.norm(hook.checked_params[n].astype(
        np.float64) - hook.start_params[n]))
        for n in hook.start_params}
    return {"losses": hook.losses, "grad_norms": hook.grad_norms,
            "grad": hook.grad, "change_norms": change}


def reference_readings(seed: int, conf: dict, mix: dict, prec: str = "f32",
                       rows: int = 0) -> dict:
    """The reference's first steps; ``rows`` > 0 keeps only the first rows
    of each batch (a planted fault: part of the batch left out)."""
    ref = common.family(conf["reference"])
    traffic = TrainTraffic(seed, conf["vocab_size"], mix["batch"],
                           mix["seq_len"])
    keep = slice(0, rows or mix["batch"])
    batches = [(b["tokens"][keep], b["labels"][keep]) for b in
               (traffic.batch(s) for s in range(CHECKED_STEPS))]
    return ref.train_steps(seed, conf, batches, mix["optimizer"],
                           mix["z_loss"], prec=prec)


def run(ctx) -> dict:
    conf, mix, seed = ctx.conf, ctx.mix, ctx.seed
    with tempfile.TemporaryDirectory(prefix="chipbench_ckpt_") as ckpt_dir:
        trainer = build(conf, mix, seed, ctx.devices, ckpt_dir)
        ctx.mark("built")
        hook = StepHook(trainer, trainer.step_fn, mix, ctx.seconds,
                            tracer=ctx.tracer)
        run_job(trainer, hook, seed, conf)
        wall = hook.finish()
        ctx.window_started(hook.t_start)
        steps = hook.calls - hook.warmup
        history = [loss for _, loss in trainer.history[hook.warmup:]]
        device = common.device_info(ctx.devices)
        prog = program_readings(hook)
        hook.last = hook.start_params = hook.checked_params = None
        hook.grad = None
        del trainer
        gc.collect()
    ref = reference_readings(seed, conf, mix)
    tokens = steps * mix["batch"] * mix["seq_len"]
    return {"attempted": steps,
            "failed": int(sum(not np.isfinite(x) for x in history)),
            "end_to_end": {"train_tokens_per_s": tokens / wall},
            "device": device, "readings": readings(prog, ref)}
