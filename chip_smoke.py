#!/usr/bin/env python3
"""Run the trainer and the serving session once on a TPU, at full width.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: model-parallel serving

One chip:
  * the main path's Pallas kernels (``repro.kernels.chip_cases``), compiled
    on the chip, against their pure-jnp oracles;
  * smollm-360m trained through ``Trainer`` under ``Supervisor`` with one
    injected failure after the first checkpoint: the supervisor restores
    and finishes (the paper's §6.1 loop), and the replayed steps repeat the
    losses they had before the failure;
  * a ``ServeSession`` prefill plus greedy decode, whose logits at every
    step match a teacher-forced forward pass over the same tokens.

Four chips: internlm-7b served through ``ServeSession(model_axis=4)``, and
a 2-layer cut of it compared between the 4-chip mesh and one chip.

Weights are random, made from a seed. Every phase checks its result; any
failure exits non-zero. Without a TPU the script fails: it never falls
back to the CPU. The times it prints are informational, not a benchmark.
The last line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

# relative error bounds, max|out - ref| / max|ref|: bf16 compute against a
# reference that sees the same inputs (a wrong position or cache shows up
# as 0.2-0.5 at these widths)
KERNEL_TOL = 2e-2
SERVE_TOL = 3e-2
LOSS_TOL = 1e-3


class CheckFailed(Exception):
    pass


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)
    log(f"ok: {what}")


def rel_err(out, ref) -> float:
    import numpy as np
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.abs(out - ref).max() / max(np.abs(ref).max(), 1e-30))


def all_finite(x) -> bool:
    import numpy as np
    return bool(np.isfinite(np.asarray(x, np.float32)).all())


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def kernel_phase(cases=None) -> None:
    """Each kernel compiled on the device against its f32 oracle."""
    import jax
    from repro.kernels.chip_cases import chip_cases
    for i, case in enumerate(cases if cases is not None else chip_cases()):
        args = case.make_args(jax.random.PRNGKey(i))
        t0 = time.perf_counter()
        out = jax.block_until_ready(jax.jit(case.kernel)(*args))
        t1 = time.perf_counter()
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(case.ref)(*args)
        err = rel_err(out, ref)
        log(f"{case.name}: first call (compile + run) {t1 - t0:.2f}s "
            "[informational, not a benchmark]")
        check(out.shape == ref.shape and all_finite(out) and err <= KERNEL_TOL,
              f"{case.name} {tuple(out.shape)} matches its oracle: "
              f"rel err {err:.3g} <= {KERNEL_TOL}")


def train_phase(cfg=None, *, batch: int = 4, seq: int = 1024,
                steps: int = 8, ckpt_every: int = 4, fault_step: int = 6,
                devices=None) -> None:
    """Train under the Supervisor; one failure after the first checkpoint."""
    import jax
    import numpy as np
    from repro.config import get_arch
    from repro.core.ft.events import BY_NAME
    from repro.launch.train import build_job

    cfg = cfg or get_arch("smollm-360m")
    devices = devices or jax.devices()[:1]
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt_dir:
        trainer, sup, ckpt = build_job(
            cfg, global_batch=batch, seq_len=seq, steps=steps,
            ckpt_every=ckpt_every, ckpt_dir=ckpt_dir, devices=devices,
            fault_schedule={fault_step: BY_NAME["ECCError"]}, log_every=1)
        step_fn, seconds = trainer.step_fn, []

        def timed_step(*a):
            t0 = time.perf_counter()
            out = jax.block_until_ready(step_fn(*a))
            seconds.append(time.perf_counter() - t0)
            return out

        trainer.step_fn = timed_step
        report = sup.run(trainer.job)
        ckpt.wait()
        ckpt.ram_cache.clear()
    log(f"{cfg.name} train {batch}x{seq} (remat {trainer.parallel.remat}): "
        f"first step (compile + run) {seconds[0]:.2f}s, later steps median "
        f"{float(np.median(seconds[1:])):.3f}s [informational, not a "
        "benchmark]")
    history = trainer.history
    log("losses (step, loss): " + ", ".join(f"({s}, {l:.4f})"
                                            for s, l in history))
    failures = [e for e in report.events if e.kind == "failure"]
    check(report.completed and report.final_step == steps,
          f"supervisor completed {steps} steps "
          f"(attempts {report.attempts})")
    check(len(failures) == 1 and failures[0].resumed_from == ckpt_every,
          f"one injected failure, resumed from the step-{ckpt_every} "
          "checkpoint")
    check(all(np.isfinite(l) for _, l in history), "all losses finite")
    first = {}
    replayed = []
    for s, l in history:
        if s in first:
            replayed.append((s, first[s], l))
        else:
            first[s] = l
    diffs = [abs(a - b) for _, a, b in replayed]
    check(len(replayed) == fault_step - ckpt_every
          and max(diffs) <= LOSS_TOL,
          f"replayed steps {[s for s, _, _ in replayed]} repeat their "
          f"pre-failure losses: max |diff| {max(diffs, default=0):.3g} "
          f"<= {LOSS_TOL}")


def decode_and_reference(sess, batch, n_steps: int):
    """Prefill + ``n_steps`` greedy decode steps; returns the session's
    logits at each step, the teacher-forced forward's logits at the same
    positions, and the generated tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    prompt = batch["tokens"]
    S = int(prompt.shape[1])
    tp = sess.prefill(batch)
    logits, toks = [np.asarray(sess.logits, np.float32)], []
    td_seconds = 0.0
    for _ in range(n_steps):
        gen, td = sess.decode_step(1)
        td_seconds += td.seconds
        toks.append(np.asarray(gen))
        logits.append(np.asarray(sess.logits, np.float32))
    tokens = np.concatenate(toks, axis=1)
    full = jax.jit(sess.model.forward_logits)(
        sess.params, {"tokens": jnp.concatenate([prompt, tokens], axis=1)})
    ref = np.asarray(full[:, S - 1:S + n_steps], np.float32)
    log(f"{sess.cfg.name} prefill {tuple(prompt.shape)} {tp.seconds:.2f}s, "
        f"{n_steps} decode steps {td_seconds:.2f}s (both include their "
        "first compile) [informational, not a benchmark]")
    return np.stack(logits, axis=1), ref, tokens


def serve_phase(arch="smollm-360m", *, batch: int = 4, prompt: int = 512,
                steps: int = 16, devices=None) -> None:
    """Prefill + greedy decode against a teacher-forced forward pass."""
    import jax
    from repro.launch.serve import ServeSession
    sess = ServeSession(arch, max_len=prompt + steps,
                        devices=devices or jax.devices()[:1])
    got, ref, tokens = decode_and_reference(
        sess, sess.make_batch(batch, prompt, seed=0), steps)
    err = rel_err(got, ref)
    check(got.shape == ref.shape and tokens.shape == (batch, steps)
          and all_finite(got) and err <= SERVE_TOL,
          f"{sess.cfg.name} prefill + {steps} decode steps match the "
          f"teacher-forced forward: logits {got.shape}, rel err {err:.3g} "
          f"<= {SERVE_TOL}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def serve_four_chips_phase(arch="internlm-7b", *, batch: int = 4,
                           prompt: int = 2048, steps: int = 4) -> None:
    """A model no single chip holds, served tensor-parallel over four."""
    import numpy as np
    from repro.launch.serve import ServeSession
    sess = ServeSession(arch, model_axis=4, max_len=prompt + steps)
    tp = sess.prefill(sess.make_batch(batch, prompt, seed=0))
    gen, td = sess.decode_step(steps)
    logits = np.asarray(sess.logits, np.float32)
    log(f"{sess.cfg.name} on mesh {dict(sess.mesh.shape)}: prefill "
        f"{tp.seconds:.2f}s, {steps} decode steps {td.seconds:.2f}s (both "
        "include their first compile) [informational, not a benchmark]")
    check(gen.shape == (batch, steps) and logits.shape[0] == batch
          and all_finite(logits),
          f"{sess.cfg.name} model_axis=4: prefill {batch}x{prompt} + "
          f"{steps} decode steps, finite logits {logits.shape}")


def cut_compare_phase(arch="internlm-7b", *, layers: int = 2,
                      batch: int = 4, prompt: int = 2048,
                      steps: int = 4) -> None:
    """The same cut served on four chips and on one: equal logits."""
    import jax
    import numpy as np
    from repro.config import get_arch
    from repro.launch.serve import ServeSession
    base = get_arch(arch) if isinstance(arch, str) else arch
    cut = dataclasses.replace(base, num_layers=layers)
    runs = {}
    prompt_batch = None
    for name, kw in (("4 chips", dict(model_axis=4)),
                     ("1 chip", dict(devices=jax.devices()[:1]))):
        sess = ServeSession(cut, max_len=prompt + steps, **kw)
        if prompt_batch is None:
            prompt_batch = sess.make_batch(batch, prompt, seed=0)
        runs[name] = decode_and_reference(sess, prompt_batch, steps)
        del sess
    (l4, r4, t4), (l1, _, t1) = runs["4 chips"], runs["1 chip"]
    err4 = rel_err(l4, r4)
    check(all_finite(l4) and err4 <= SERVE_TOL,
          f"{layers}-layer {base.name} on 4 chips matches its teacher-forced "
          f"forward: rel err {err4:.3g} <= {SERVE_TOL}")
    err = rel_err(l4[:, 0], l1[:, 0])
    check(err <= SERVE_TOL,
          f"{layers}-layer {base.name} prefill logits, 4 chips vs 1 chip: "
          f"rel err {err:.3g} <= {SERVE_TOL}")
    # decode step i is comparable for a row while both meshes fed it the
    # same greedy tokens (a bf16 near-tie may pick different tokens)
    same = np.cumprod(t4 == t1, axis=1).astype(bool)
    errs = [rel_err(l4[b, i + 1], l1[b, i + 1])
            for b, i in zip(*np.nonzero(same))]
    check(2 * len(errs) >= same.size and max(errs) <= SERVE_TOL,
          f"{layers}-layer {base.name} decode logits, 4 chips vs 1 chip: "
          f"{len(errs)}/{same.size} (row, step) pairs fed equal tokens, "
          f"max rel err {max(errs, default=0):.3g} <= {SERVE_TOL}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip model-parallel phases")
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro sources at {src}", file=sys.stderr)
        return 2
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.launch.cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    cache = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    devices = jax.devices()
    dev = devices[0]
    need = 4 if args.four_chips else 1
    if dev.platform != "tpu" or len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chip(s); JAX found "
              f"{len(devices)} {dev.platform} device(s)", file=sys.stderr)
        return 1
    log(f"devices: {devices}")
    log(f"device_kind: {dev.device_kind}, count {len(devices)}")
    log(f"compile cache: {cache_dir}")

    phases = ([serve_four_chips_phase, cut_compare_phase] if args.four_chips
              else [kernel_phase, train_phase, serve_phase])
    t_all = time.perf_counter()
    try:
        for phase in phases:
            t0 = time.perf_counter()
            log(f"--- {phase.__name__}")
            phase()
            log(f"{phase.__name__} done in {time.perf_counter() - t0:.1f}s")
    except Exception:  # noqa: BLE001 - any failure fails the smoke run
        traceback.print_exc()
        log("FAILED")
        return 1
    log(f"compile cache hits {cache['hits']}, misses {cache['misses']}; "
        f"total {time.perf_counter() - t_all:.1f}s")
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
