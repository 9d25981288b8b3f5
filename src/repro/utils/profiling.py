"""The names the program gives its own work in a profiler trace.

Two kinds, both recorded by the JAX profiler (``jax.profiler.trace``) and so
on one clock with the device's operations:

- ``SCOPES``: ``jax.named_scope`` names inside the jitted programs. They are
  compile-time metadata (each HLO operation's ``op_name``, which a device
  trace shows as its ``tf_op``) and cost nothing at run time. An operation
  belongs to the innermost of them in its path; one the compiler made
  itself (a layout ``copy``, a ``convert``) carries none.
- ``SPANS``: host spans (``jax.profiler.TraceAnnotation``) around the
  launchers' calls, with the work's counts as arguments.

``scope`` and ``span`` refuse a name that is not listed here, so the list
stays the whole vocabulary that readers of a trace can rely on.

JAX's persistent compilation cache keys a program by its code without
this metadata, so by default a program compiled before its scopes moved
(or from a checkout without them) is served with its old names. Importing
this module keys the cache by the metadata too, so that a trace always
shows the scopes of the source that ran.
"""
from __future__ import annotations

import jax
from jax.profiler import StepTraceAnnotation, TraceAnnotation

jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

SCOPES = (
    "embed",        # token embedding (and a frontend's inputs)
    "layers",       # the layer stack: per-layer slicing of weights, the
                    # train step's saved activations and prefill's caches,
                    # their restacking; residual adds. Decode's caches are
                    # read in place, not sliced out
    "norm",         # RMSNorm calls of the model
    "attn",         # attention sublayer: projections and core
    "kv_cache",     # prefill's cache build; decode's write of every
                    # layer's slot after the stack (a lane-aligned block
                    # of slots for narrow entries), and the read's select
                    # and cast (fused into the attention's dots on a TPU)
    "mlp",
    "moe",
    "ssm",
    "lm_head",
    "loss",         # chunked softmax cross-entropy
    "optimizer",    # gradient clipping and the AdamW update
)

SPANS = (
    "trainer.step",     # Trainer.job's step_fn call: step_num, batch, seq_len
    "trainer.data",     # the next batch from the loader, put on the device
    "trainer.sync",     # the step's loss read back to the host
    "trainer.host",     # spike detector, logging, stragglers, fault schedule
    "trainer.ckpt",     # CheckpointManager.save_async's blocking part
    "serve.prefill",    # ServeSession.prefill: batch, prompt_len
    "serve.decode",     # ServeSession.decode_step: batch, start, steps
    "serve.restart",    # ServeSession.restart
)


def scope(name: str):
    """``jax.named_scope(name)`` for a name in ``SCOPES``."""
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r}; known: {SCOPES}")
    return jax.named_scope(name)


def span(name: str, step_num: int | None = None, **counts: int):
    """A host span for a name in ``SPANS``; ``counts`` become its arguments.
    With ``step_num`` it is a ``StepTraceAnnotation``, which the profiler's
    tools read as one training step."""
    if name not in SPANS:
        raise ValueError(f"unknown span {name!r}; known: {SPANS}")
    if step_num is not None:
        return StepTraceAnnotation(name, step_num=step_num, **counts)
    return TraceAnnotation(name, **counts)
