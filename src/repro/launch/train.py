"""End-to-end fault-tolerant training driver.

The full §6.1 stack around a real JAX training loop: sharded train step,
deterministic resumable data pipeline, asynchronous checkpointing, loss-spike
detection with rollback + data-skip, failure diagnosis and the auto-restart
supervisor. Scales from the CPU example (reduced config) to the production
mesh (same code path — only the mesh/config change).

  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --smoke \
      --steps 120 --ckpt-every 20 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import tempfile
import time
from typing import Optional

import jax
import numpy as np

from repro.config import (ParallelConfig, TrainConfig, get_arch, get_smoke)
from repro.core.ft.checkpoint import CheckpointManager
from repro.core.ft.diagnosis import FailureDiagnosisSystem
from repro.core.ft.detection import SimulatedFleet, StragglerMonitor
from repro.core.ft.spike import SpikeDetector
from repro.core.ft.supervisor import (JobContext, JobFailure, SpikeInterrupt,
                                      Supervisor)
from repro.data import DataConfig, DataLoader, SyntheticLM
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import Model
from repro.sharding import make_rules
from repro.train.optimizer import adamw_abstract, adamw_init
from repro.train.train_step import compile_train_step
from repro.utils import logger
from repro.utils.profiling import span


@dataclasses.dataclass
class TrainerState:
    params: object
    opt: object
    loader: DataLoader


class Trainer:
    """Restartable training job body driven by the Supervisor."""

    def __init__(self, model: Model, tcfg: TrainConfig, mesh, parallel,
                 ckpt: CheckpointManager, *, total_steps: int,
                 ckpt_every: int = 20,
                 fault_schedule: Optional[dict] = None,
                 spike_schedule: Optional[dict] = None,
                 log_every: int = 10,
                 fleet: Optional[SimulatedFleet] = None,
                 host_time_fn=None):
        self.model, self.tcfg = model, tcfg
        self.mesh, self.parallel = mesh, parallel
        self.ckpt = ckpt
        self.total_steps = total_steps
        self.ckpt_every = ckpt_every
        self.fault_schedule = dict(fault_schedule or {})  # step -> FailureType
        self.spike_schedule = dict(spike_schedule or {})  # step -> delta loss
        self.log_every = log_every
        self.detector = SpikeDetector(min_history=8, patience=3,
                                      z_threshold=6.0)
        # straggler mitigation: per-host step times feed the same cordon
        # list the detection kit uses; persistently slow hosts are removed
        # at the next elastic restart. host_time_fn(step) -> {host: seconds}
        # supplies the measurements (real deployments read them from the
        # multihost heartbeat; tests/sims inject them).
        self.fleet = fleet
        self.host_time_fn = host_time_fn
        self.straggler = StragglerMonitor(
            range(fleet.num_nodes) if fleet else [])
        self.history: list[tuple[int, float]] = []
        self.step_fn, self.p_sh, self.o_sh, _ = compile_train_step(
            model, tcfg, mesh, parallel, donate=False)
        data_cfg = DataConfig(vocab_size=model.cfg.vocab_size,
                              seq_len=tcfg.seq_len,
                              global_batch=tcfg.global_batch,
                              seed=tcfg.seed)
        self.dataset = SyntheticLM(data_cfg)
        self._fired: set[int] = set()

    def init_state(self) -> TrainerState:
        params = self.model.init(jax.random.PRNGKey(self.tcfg.seed))
        opt = jax.jit(adamw_init, out_shardings=self.o_sh)(params)
        return TrainerState(params, opt, DataLoader(self.dataset))

    def _restore(self, step: int, skip_ranges) -> TrainerState:
        abstract = self.model.abstract()
        (params, opt), extra = self.ckpt.restore(
            step, (abstract, adamw_abstract(abstract)),
            shardings=(self.p_sh, self.o_sh))
        loader = DataLoader(self.dataset,
                            start_step=int(extra.get("data_step", step)),
                            skip_ranges=[tuple(r) for r in
                                         extra.get("skip_ranges", [])])
        for lo, hi in skip_ranges:
            loader.skip(lo, hi)
        return TrainerState(params, opt, loader)

    def job(self, ctx: JobContext) -> int:
        if ctx.start_step == 0 and self.ckpt.latest_restorable() is None:
            state = self.init_state()
        else:
            state = self._restore(ctx.start_step, ctx.skip_ranges)
        self.detector.reset_after_rollback(ctx.start_step)
        step = ctx.start_step
        while step < self.total_steps:
            with span("trainer.data"):
                data_step, batch = state.loader.next()
                batch = {k: jax.numpy.asarray(v) for k, v in batch.items()}
            b, s = batch["tokens"].shape
            with span("trainer.step", step_num=step, batch=b, seq_len=s):
                state.params, state.opt, metrics = self.step_fn(
                    state.params, state.opt, batch)
            with span("trainer.sync"):
                loss = float(metrics["loss"])
            with span("trainer.host"):
                # injected anomalies (benchmarks / example demos)
                if (data_step in self.spike_schedule
                        and not self._skipped(state, data_step)):
                    loss += self.spike_schedule[data_step]
                step += 1
                self.history.append((step, loss))
                if step % self.log_every == 0:
                    logger.info("step %d loss %.4f lr %.2e", step, loss,
                                float(metrics["lr"]))
                ev = self.detector.update(step, loss,
                                          self.ckpt.available_steps() or
                                          list(self.ckpt.ram_cache))
                if ev is not None:
                    raise SpikeInterrupt(ev)
                if step % self.ckpt_every == 0:
                    with span("trainer.ckpt"):
                        self.ckpt.save_async(
                            step, (state.params, state.opt),
                            extra={"data_step": state.loader.step,
                                   "skip_ranges": state.loader.skip_ranges})
                if self.host_time_fn is not None and self.fleet is not None:
                    for host, t in self.host_time_fn(step).items():
                        self.straggler.record(host, t)
                    slow = [h for h in self.straggler.stragglers()
                            if h not in self.fleet.cordoned]
                    if slow:
                        self.fleet.cordon(slow)
                        logger.info("stragglers cordoned at step %d: %s",
                                    step, slow)
                if step in self.fault_schedule and step not in self._fired:
                    self._fired.add(step)
                    from repro.core.ft.events import generate_log
                    ft = self.fault_schedule[step]
                    raise JobFailure(step, generate_log(ft, seed=step),
                                     truth=ft.name)
        return step

    def _skipped(self, state: TrainerState, data_step: int) -> bool:
        return any(lo <= data_step < hi for lo, hi in state.loader.skip_ranges)


def build_job(cfg, *, global_batch: int, seq_len: int, steps: int,
              ckpt_every: int, ckpt_dir: str, model_axis: int = 1,
              devices=None, **trainer_kw
              ) -> tuple[Trainer, Supervisor, CheckpointManager]:
    """The launcher's job: a Trainer on a host mesh, under a Supervisor."""
    mesh = make_host_mesh(model_axis, devices)
    parallel = ParallelConfig(moe_impl="dense",
                              shard_model_axes=model_axis > 1)
    tcfg = TrainConfig(global_batch=global_batch, seq_len=seq_len,
                       total_steps=steps, warmup_steps=steps // 10)
    model = Model(cfg, parallel, make_rules(mesh, parallel))
    ckpt = CheckpointManager(ckpt_dir, keep=4)
    trainer = Trainer(model, tcfg, mesh, parallel, ckpt, total_steps=steps,
                      ckpt_every=ckpt_every, **trainer_kw)
    sup = Supervisor(ckpt, FailureDiagnosisSystem(), SimulatedFleet(8))
    return trainer, sup, ckpt


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU scale)")
    ap.add_argument("--steps", type=int, default=100)
    # 4 x 1024 with "dots" remat fits one 16 GB v5e chip at smollm-360m
    ap.add_argument("--global-batch", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=1024)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--ckpt-dir",
                    default=os.path.join(tempfile.gettempdir(), "repro_ckpt"))
    ap.add_argument("--model-axis", type=int, default=1)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke(args.arch) if args.smoke else get_arch(args.arch)
    trainer, sup, ckpt = build_job(
        cfg, global_batch=args.global_batch, seq_len=args.seq_len,
        steps=args.steps, ckpt_every=args.ckpt_every,
        ckpt_dir=args.ckpt_dir, model_axis=args.model_axis)
    t0 = time.time()
    report = sup.run(trainer.job)
    ckpt.wait()
    losses = [l for _, l in trainer.history]
    logger.info("done: completed=%s final_step=%d attempts=%d "
                "loss %.3f -> %.3f (%.1fs)", report.completed,
                report.final_step, report.attempts, losses[0], losses[-1],
                time.time() - t0)


if __name__ == "__main__":
    main()
