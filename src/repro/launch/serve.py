"""Batched serving driver: prefill + greedy decode with sharded KV caches.

The importable surface is :class:`ServeSession` — build the model, mesh
and parameters once, then drive `prefill()` / `decode_step()` (or the
convenience `generate()`) as many times as needed; each call returns a
structured :class:`ServeTimings`. These two phases are exactly the ones
the cost model prices for the serving replay (``SHAPES['prefill_32k']``
and ``SHAPES['decode_32k']`` in ``launch/cost_model.py``), so a
calibrated dry-run of this driver and ``cluster/serve_replay.py``'s
analytic fallback describe the same work.

CLI (thin argparse wrapper over ServeSession):

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \
      --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig, ParallelConfig, get_arch, get_smoke
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import Model
from repro.serve import make_prefill, make_serve_step
from repro.sharding import make_rules
from repro.utils import logger
from repro.utils.profiling import span


@dataclasses.dataclass(frozen=True, slots=True)
class ServeTimings:
    """Wall-clock accounting for one serving phase.

    ``seconds`` includes compile on the first call of each jitted
    function; ``tokens`` is the number of tokens the phase produced
    (batch * prompt for prefill, batch * steps for decode)."""
    phase: str
    seconds: float
    batch: int
    tokens: int

    @property
    def tokens_per_s(self) -> float:
        return self.tokens / max(self.seconds, 1e-9)


class ServeSession:
    """One resident serving instance: model + mesh + params built once.

    ``prefill(batch)`` runs the prompt pass and retains the KV caches and
    last-step logits (``logits``) as session state; ``decode_step()``
    appends one greedy token per sequence. ``generate(prompt, n)`` chains
    the two.

    ``arch`` names a registered config or is a ``ModelConfig`` itself;
    ``max_len`` is the decode horizon the KV ring buffers hold (0: the
    model's whole context); ``devices`` limits the mesh to those devices
    (default: all of them).
    """

    def __init__(self, arch: Union[str, ModelConfig] = "smollm-360m", *,
                 smoke: bool = False, model_axis: int = 1, seed: int = 0,
                 max_len: int = 0,
                 devices: Optional[Sequence] = None) -> None:
        if isinstance(arch, ModelConfig):
            self.cfg = arch
        else:
            self.cfg = get_smoke(arch) if smoke else get_arch(arch)
        self.mesh = make_host_mesh(model_axis, devices)
        self.parallel = ParallelConfig(moe_impl="dense",
                                       shard_model_axes=model_axis > 1)
        self.model = Model(self.cfg, self.parallel,
                           make_rules(self.mesh, self.parallel))
        self._seed = seed
        self.params = self.model.init(jax.random.PRNGKey(seed))
        self._prefill_fn = jax.jit(make_prefill(self.model, max_len))
        # the step consumes its caches: one KV copy in HBM, not two
        self._step_fn = jax.jit(make_serve_step(self.model),
                                donate_argnums=(1,))
        self._caches = None
        self._tok = None
        self._pos = 0
        self.logits = None

    def make_batch(self, batch: int, prompt_len: int,
                   seed: int = 0) -> dict:
        """Random token batch shaped for this arch (stub frontends too)."""
        cfg = self.cfg
        rng = np.random.default_rng(seed)
        prompt = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                          (batch, prompt_len),
                                          dtype=np.int32))
        out = {"tokens": prompt}
        if cfg.frontend == "patch_stub":
            out["patches"] = jnp.zeros((batch, cfg.num_patches,
                                        cfg.d_model), jnp.float32)
        if cfg.frontend == "audio_stub":
            out["frames"] = jnp.zeros((batch, cfg.encoder_seq,
                                       cfg.d_model), jnp.float32)
        return out

    def prefill(self, batch: dict) -> ServeTimings:
        """Prompt pass; stores caches + first greedy token on the session."""
        tokens = batch["tokens"]
        t0 = time.time()
        with span("serve.prefill", batch=int(tokens.shape[0]),
                  prompt_len=int(tokens.shape[1])):
            logits, caches = self._prefill_fn(self.params, batch)
            logits.block_until_ready()
            dt = time.time() - t0
            self._caches = caches
            self.logits = logits
            self._tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            self._pos = int(tokens.shape[1])
        return ServeTimings("prefill", dt, int(tokens.shape[0]),
                            int(tokens.shape[0] * tokens.shape[1]))

    def decode_step(self, n_steps: int = 1) -> tuple[jnp.ndarray,
                                                     ServeTimings]:
        """Greedy-decode ``n_steps`` tokens per sequence.

        Returns the generated tokens ``[batch, n_steps]`` and the phase
        timings. The session always holds one generated-but-unreturned
        token (prefill's argmax at first), so consecutive calls emit a
        contiguous, non-overlapping token stream."""
        if self._caches is None:
            raise RuntimeError("decode_step before prefill")
        tok, logits = self._tok, self.logits
        out = []
        t0 = time.time()
        with span("serve.decode", batch=int(tok.shape[0]), start=self._pos,
                  steps=n_steps):
            for t in range(self._pos, self._pos + n_steps):
                out.append(tok)
                logits, self._caches = self._step_fn(
                    self.params, self._caches, tok, jnp.int32(t))
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            jax.block_until_ready(tok)
            dt = time.time() - t0
            self._pos += n_steps
            self._tok = tok
            self.logits = logits
            gen = jnp.stack(out, axis=1)
        return gen, ServeTimings("decode", dt, int(tok.shape[0]),
                                 int(tok.shape[0] * n_steps))

    def generate(self, batch: dict, n_tokens: int
                 ) -> tuple[jnp.ndarray, ServeTimings, ServeTimings]:
        """Prefill then greedy-decode ``n_tokens``; returns
        (tokens ``[batch, n_tokens]``, prefill timings, decode timings)."""
        tp = self.prefill(batch)
        gen, td = self.decode_step(n_tokens)
        return gen, tp, td

    def restart(self) -> ServeTimings:
        """In-place restart: the recovery primitive the serving replay's
        transient-infra verdict models (``cluster/serve_replay.py``). All
        session state an instance failure would destroy — KV caches, the
        pending greedy token, the position cursor — is dropped and the
        parameters are re-initialized from the session seed; resident
        requests must re-enter through :meth:`prefill` (the replay's
        recompute pass). Returns the restart's wall-clock timings so
        dry-runs can calibrate the taxonomy's ``restart_overhead_min``."""
        self._caches = None
        self._tok = None
        self._pos = 0
        self.logits = None
        t0 = time.time()
        with span("serve.restart"):
            self.params = self.model.init(jax.random.PRNGKey(self._seed))
            jax.block_until_ready(self.params)
        dt = time.time() - t0
        return ServeTimings("restart", dt, 0, 0)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--restarts", type=int, default=0,
                    help="in-place restarts between generations (exercises "
                         "the fault-recovery primitive the serving replay "
                         "models for transient-infra verdicts)")
    args = ap.parse_args()

    enable_compile_cache()
    sess = ServeSession(args.arch, smoke=args.smoke,
                        model_axis=args.model_axis,
                        max_len=args.prompt_len + args.gen)
    for i in range(args.restarts + 1):
        gen, tp, td = sess.generate(
            sess.make_batch(args.batch, args.prompt_len), args.gen)
        logger.info("prefill %.2fs; decode %d x %d tokens in %.2fs "
                    "(%.1f tok/s incl. first-step compile)",
                    tp.seconds, td.batch, args.gen, td.seconds,
                    td.tokens_per_s)
        if i < args.restarts:
            tr = sess.restart()
            logger.info("in-place restart %d/%d: %.2fs (KV + session state "
                        "dropped)", i + 1, args.restarts, tr.seconds)
    logger.info("sample generation: %s", np.asarray(gen[0][:16]))


if __name__ == "__main__":
    main()
