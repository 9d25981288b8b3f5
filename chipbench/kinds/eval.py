"""Offline batched evaluation through the program's ``ServeSession``.

A closed loop: one static batch of prompts after another, each prefilled
and, for generative mixes, greedily decoded for ``gen_len`` tokens. No
batch starts after the window's length has passed, and the batch in flight
finishes, so the window holds whole batches.

Traffic: prompts of ``prompt_len`` token ids, uniform over the vocabulary,
drawn from the seed and the batch's index. Every seed gives the same shapes
and the same number of tokens; only the ids differ.

Correctness: once the window has closed and the program's state is freed,
``sample_rows`` requests drawn from the seed among the finished ones are run
through the plain reference over prompt plus served tokens. Two numbers are
compared: the widest gap by which a served token's reference logit lies
below the reference's best at that position, and the error of the logits
the program left after each batch (the prefill's for a scored batch, the
last decode step's for a generative one) relative to the largest reference
logit of the sample.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

import common

NEVER = 1 << 40


class EvalTraffic:
    """Prompt batches from the seed: batch(i) -> (batch, prompt_len) ids."""

    def __init__(self, seed: int, vocab: int, batch: int, prompt_len: int):
        self.seed, self.vocab = seed, vocab
        self.shape = (batch, prompt_len)

    def batch(self, index: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, 1, index])
        return rng.integers(0, self.vocab, self.shape, dtype=np.int32)


class Served:
    """What the window produced: per batch, its index, served tokens and
    its times."""

    def __init__(self):
        self.batches: list[int] = []
        self.tokens: list[np.ndarray] = []
        self.ttft_s: list[float] = []
        self.decode_s: list[float] = []
        self.logits: list = []       # the session's last logits, on device


def build(conf: dict, mix: dict, seed: int, devices: list):
    """The program's session with the benchmark's weights from ``seed``."""
    common.use_program()
    from repro.launch.serve import ServeSession
    cfg = common.program_config(conf)
    common.check_program_config(cfg, conf)
    with common.seeded_init(seed, conf):
        return ServeSession(cfg, model_axis=conf["model_axis"],
                            max_len=mix["prompt_len"] + max(mix["gen_len"], 1),
                            devices=devices)


def serve_batch(sess, argmax, prompt: np.ndarray, gen_len: int):
    """One request batch; returns (served tokens, ttft_s, decode_s).

    The first served token is the argmax of the prefill's logits; a
    generative batch then decodes ``gen_len`` greedy steps, of which the
    session returns the tokens of the first ``gen_len``."""
    t0 = time.perf_counter()
    with TraceAnnotation("eval.prefill"):
        sess.prefill({"tokens": jnp.asarray(prompt)})
    if gen_len == 0:
        with TraceAnnotation("eval.handoff"):
            first = np.asarray(argmax(sess.logits))[:, None]
        return first, time.perf_counter() - t0, 0.0
    t1 = time.perf_counter()
    with TraceAnnotation("eval.decode"):
        gen, _ = sess.decode_step(gen_len)
    with TraceAnnotation("eval.handoff"):
        served = np.asarray(gen)
    t2 = time.perf_counter()
    return served, t1 - t0, t2 - t1


def window(sess, traffic: EvalTraffic, gen_len: int, seconds: float,
           min_batches: int = 1, tracer=None,
           warm: bool = True) -> tuple[Served, float, float]:
    """Serve whole batches until ``seconds`` have passed, and at least
    ``min_batches``; returns what was served, the window's start on the
    host clock and its wall time."""
    argmax = jax.jit(lambda x: jnp.argmax(x, axis=-1).astype(jnp.int32))
    if warm:    # every shape the window uses, on a batch it never serves
        serve_batch(sess, argmax, traffic.batch(NEVER), gen_len)
    out = Served()
    t_start = time.perf_counter()
    if tracer is not None:
        tracer.start()
    i = 0
    while i < min_batches or time.perf_counter() - t_start < seconds:
        prompt = traffic.batch(i)
        tokens, ttft, dec = serve_batch(sess, argmax, prompt, gen_len)
        out.batches.append(i)
        out.tokens.append(tokens)
        out.ttft_s.append(ttft)
        out.decode_s.append(dec)
        out.logits.append(sess.logits)
        if tracer is not None:
            tracer.unit_done(prefill=(prompt.shape[0], prompt.shape[1]),
                             decode=[(prompt.shape[0], prompt.shape[1] + j)
                                     for j in range(gen_len)])
        i += 1
    wall = time.perf_counter() - t_start
    if tracer is not None:
        tracer.stop()
    return out, t_start, wall


def min_batches(mix: dict) -> int:
    """Enough batches that the check's sample is always full-size."""
    return -(-mix["sample_rows"] // mix["batch"])


def end_to_end(served: Served, wall: float, mix: dict) -> dict:
    B, S, G = mix["batch"], mix["prompt_len"], mix["gen_len"]
    n = len(served.batches)
    out = {"eval_tokens_per_s": n * B * (S + G) / wall}
    if G:
        out["tpot_ms"] = 1e3 * sum(served.decode_s) / (n * G)
    ttft = np.repeat(np.asarray(served.ttft_s), B)
    out["ttft_ms_p95"] = 1e3 * float(np.percentile(ttft, 95))
    return out


def sample(served: Served, mix: dict, seed: int) -> list[tuple[int, int]]:
    """(batch position in the window, row) pairs drawn from the seed."""
    B = mix["batch"]
    total = len(served.batches) * B
    k = min(mix["sample_rows"], total)
    rng = np.random.default_rng([seed, 2])
    picks = rng.choice(total, size=k, replace=False)
    return sorted((int(p) // B, int(p) % B) for p in picks)


def requests(served: Served, traffic: EvalTraffic, picks) -> tuple:
    """The picked rows' prompt plus served tokens, the reference's input
    (R, S + G'), where G' is the served tokens' count less the one a
    scored request serves; their served tokens (R, G'') and the program's
    last logits (R, V)."""
    full, judged, last = [], [], []
    for b, r in picks:
        prompt = traffic.batch(served.batches[b])[r]
        toks = served.tokens[b][r]
        full.append(np.concatenate([prompt, toks]) if len(toks) > 1
                    else prompt)
        judged.append(toks)
        last.append(np.asarray(served.logits[b][r], np.float32))
    return np.stack(full), np.stack(judged), np.stack(last)


def readings(ref_logits: np.ndarray, judged: np.ndarray,
             last: np.ndarray) -> dict:
    """The numbers compared: served tokens against the reference's logits
    at their positions, and the program's last logits against the
    reference's at the last position."""
    gap = widest_gap(ref_logits[:, :judged.shape[1]], judged)
    ref_last = ref_logits[:, -1].astype(np.float64)
    err = np.abs(last - ref_last).max() / np.abs(ref_last).max()
    return {"widest_logit_gap": gap, "logit_err": float(err)}


def widest_gap(ref_logits: np.ndarray, tokens: np.ndarray) -> float:
    """max over positions of (best reference logit - served token's)."""
    ref_logits = ref_logits.astype(np.float64)
    best = ref_logits.max(axis=-1)
    got = np.take_along_axis(ref_logits, tokens[..., None], axis=-1)[..., 0]
    return float((best - got).max())


def control_readings(ref_logits: np.ndarray, low_logits: np.ndarray,
                     served: int) -> dict:
    """The control's readings: at each position, the gap of the token the
    lower precision puts first, and its last logits' error."""
    return readings(ref_logits, low_logits[:, :served].argmax(axis=-1),
                    low_logits[:, -1])


def reference_logits(seed: int, conf: dict, full: np.ndarray, prompt_len: int,
                     prec: str = "f32", device=None) -> np.ndarray:
    ref = common.family(conf["reference"])
    # rows per block: their fp32 attention scores in about 1 GB
    seq = full.shape[1]
    rows = int(1e9 // (conf["num_attention_heads"] * seq * seq * 4))
    return ref.logits_at(seed, conf, full, prompt_len - 1, prec=prec,
                         rows_per_block=max(1, min(rows, 32)), device=device)


def run(ctx) -> dict:
    conf, mix, seed = ctx.conf, ctx.mix, ctx.seed
    sess = build(conf, mix, seed, ctx.devices)
    ctx.mark("built")
    traffic = EvalTraffic(seed, conf["vocab_size"], mix["batch"],
                          mix["prompt_len"])
    served, t_start, wall = window(sess, traffic, mix["gen_len"],
                                   ctx.seconds, min_batches(mix),
                                   tracer=ctx.tracer)
    ctx.window_started(t_start)
    device = common.device_info(ctx.devices)
    del sess
    gc.collect()
    picks = sample(served, mix, seed)
    full, judged, last = requests(served, traffic, picks)
    served.logits = None
    ref = reference_logits(seed, conf, full, mix["prompt_len"],
                           device=ctx.devices[0])
    tokens = np.concatenate(served.tokens)
    failed = int(((tokens < 0) | (tokens >= conf["vocab_size"])).any(axis=1)
                 .sum())
    return {"attempted": len(served.batches) * mix["batch"],
            "failed": failed, "end_to_end": end_to_end(served, wall, mix),
            "device": device, "readings": readings(ref, judged, last)}
