"""The dense decoder family: RMSNorm, GQA with RoPE, SwiGLU.

Its plain reference, and what the benchmark knows of the family besides
(``common.family``): the keys of its configuration files as the program
states them (``program_keys``), its weights' scales (``WEIGHT_SCALES``) and
the operations and bytes its work needs (the counts that ``flops.py``
dispatches to).

Straight ``jax.numpy`` in float32 at the highest matmul precision, written
from the published Llama-style description and the configuration file
alone, with the departures from it that the file states (its
``departures``: the embedding scaled by sqrt(hidden_size) where
``embed_scale_sqrt_hidden`` says so). It imports nothing of the program
and takes nothing it made: its weights come from the seed through
``common.leaf_value``, one layer at a time, so a model larger than one
chip fits.

``prec="fp8"`` is the control: every matmul operand rounded to float8
(e4m3, one scale per tensor) before a float32 multiply, and in training
every gradient flowing back through one rounded to e5m2 with its own scale
(the usual fp8 training recipe): the precision one step below the bf16 the
configurations state.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

import common
import flops

HIGHEST = jax.lax.Precision.HIGHEST


def _round(x, dtype):
    top = float(jnp.finfo(dtype).max)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def _q8(x):
    return _round(x, jnp.float8_e4m3fn)


def _q8_fwd(x):
    return _q8(x), None


def _q8_bwd(_, g):
    return (_round(g, jnp.float8_e5m2),)


_q8.defvjp(_q8_fwd, _q8_bwd)


def mm(spec: str, a, b, prec: str):
    if prec == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# the program's configuration against the file
# ---------------------------------------------------------------------------

def program_keys(cfg) -> dict:
    """The file's keys as the program's ModelConfig states them; its
    ``reference`` is this family's only where the program's architecture is
    a dense GQA decoder with SwiGLU and RoPE."""
    a = cfg.attention
    if (cfg.family != "dense" or a is None or a.kind != "gqa"
            or a.sliding_window or a.global_every or a.logit_softcap
            or not a.use_rope or cfg.mlp_act != "silu_glu"):
        return {"reference": f"{cfg.family}, not dense GQA, SwiGLU, RoPE"}
    return {
        "reference": "dense",
        "num_hidden_layers": cfg.num_layers,
        "hidden_size": cfg.d_model,
        "intermediate_size": cfg.d_ff,
        "num_attention_heads": a.num_heads,
        "num_key_value_heads": a.num_kv_heads,
        "head_dim": a.head_dim,
        "vocab_size": cfg.vocab_size,
        "tie_word_embeddings": cfg.tie_embeddings,
        "rope_theta": a.rope_theta,
        "rms_norm_eps": cfg.norm_eps,
        "hidden_act": cfg.mlp_act.split("_")[0],
        "compute_dtype": cfg.dtype,
        # the program's layers have no bias; a bias leaf would be refused
        # where the weights are made (``common.leaf_value``)
        "bias": False,
        "embed_scale_sqrt_hidden": abs(
            common.program_embedding_scale(cfg.d_model)
            - cfg.d_model ** 0.5) < 1e-3,
    }


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

# each weight's fan-in axes within one layer's leaf, by the end of its name
WEIGHT_SCALES = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
                 "w1": (0,), "w2": (0,), "w3": (0,), "w": (0,),
                 "tok": (1,), "scale": common.NORM}


def layer_shapes(conf: dict) -> dict:
    d, ff = conf["hidden_size"], conf["intermediate_size"]
    h, kv, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                 conf["head_dim"])
    return {"segments/ln1/scale": (d,), "segments/attn/wq": (d, h, hd),
            "segments/attn/wk": (d, kv, hd), "segments/attn/wv": (d, kv, hd),
            "segments/attn/wo": (h, hd, d), "segments/ln2/scale": (d,),
            "segments/mlp/w1": (d, ff), "segments/mlp/w3": (d, ff),
            "segments/mlp/w2": (ff, d)}


def outer_shapes(conf: dict) -> dict:
    d, v = conf["hidden_size"], conf["vocab_size"]
    out = {"embed/tok": (v, d), "final_norm/scale": (d,)}
    if not conf["tie_word_embeddings"]:
        out["lm_head/w"] = (d, v)
    return out


@partial(jax.jit, static_argnums=(1, 2))
def _layer_weights(seed_key, family_name, shapes_items, layer):
    return {name: common.leaf_value(common.leaf_key(seed_key, name, layer),
                                    name, shape, family_name)
            for name, shape in shapes_items}


def layer_weights(seed: int, conf: dict, layer: int) -> dict:
    items = tuple(sorted(layer_shapes(conf).items()))
    return _layer_weights(common.base_key(seed), conf["reference"], items,
                          jnp.int32(layer))


def outer_weights(seed: int, conf: dict) -> dict:
    items = tuple(sorted(outer_shapes(conf).items()))
    return _layer_weights(common.base_key(seed), conf["reference"], items,
                          jnp.int32(0))


def all_weights(seed: int, conf: dict) -> dict:
    """Every weight, the layers stacked: for models that fit one chip."""
    n = conf["num_hidden_layers"]
    tree = {name: jax.ShapeDtypeStruct((n,) + shape, jnp.float32)
            for name, shape in layer_shapes(conf).items()}
    tree.update({name: jax.ShapeDtypeStruct(shape, jnp.float32)
                 for name, shape in outer_shapes(conf).items()})
    nested: dict = {}
    for name, s in tree.items():
        node = nested
        *parents, leaf = name.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = s
    return flatten(common.make_weights(seed, conf, nested))


def flatten(nested: dict) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(nested)
    return {common.leaf_name(p): v for p, v in flat}


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def rope(x, positions, theta):
    """x: (B, S, heads, hd); rotates the two halves of each head."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions[:, None].astype(jnp.float32) * freqs       # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer(conf: dict, w: dict, h, prec: str):
    """One decoder layer over full causal sequences h: (B, S, d)."""
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    B, S, _ = h.shape
    kv, hd = conf["num_key_value_heads"], conf["head_dim"]
    g = conf["num_attention_heads"] // kv
    pos = jnp.arange(S)
    x = rmsnorm(h, w["segments/ln1/scale"], eps)
    q = rope(mm("bsd,dhk->bshk", x, w["segments/attn/wq"], prec), pos, theta)
    k = rope(mm("bsd,dhk->bshk", x, w["segments/attn/wk"], prec), pos, theta)
    v = mm("bsd,dhk->bshk", x, w["segments/attn/wv"], prec)
    q = q.reshape(B, S, kv, g, hd)
    s = mm("bqkgd,bskd->bkgqs", q, k, prec) * hd ** -0.5
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = mm("bkgqs,bskd->bqkgd", p, v, prec).reshape(B, S, kv * g, hd)
    h = h + mm("bshk,hkd->bsd", o, w["segments/attn/wo"], prec)
    x = rmsnorm(h, w["segments/ln2/scale"], eps)
    a = mm("bsd,df->bsf", x, w["segments/mlp/w1"], prec)
    b = mm("bsd,df->bsf", x, w["segments/mlp/w3"], prec)
    return h + mm("bsf,fd->bsd", jax.nn.silu(a) * b,
                  w["segments/mlp/w2"], prec)


def embed(conf: dict, outer: dict, tokens):
    scale = (conf["hidden_size"] ** 0.5 if conf["embed_scale_sqrt_hidden"]
             else 1.0)
    return outer["embed/tok"][tokens] * scale


def head(conf: dict, outer: dict, h, prec: str):
    h = rmsnorm(h, outer["final_norm/scale"], conf["rms_norm_eps"])
    if conf["tie_word_embeddings"]:
        return mm("bsd,vd->bsv", h, outer["embed/tok"], prec)
    return mm("bsd,dv->bsv", h, outer["lm_head/w"], prec)


# ---------------------------------------------------------------------------
# serving: logits at chosen positions, layer by layer, in blocks of rows
# ---------------------------------------------------------------------------

def logits_at(seed: int, conf: dict, tokens, first: int, prec: str = "f32",
              rows_per_block: int = 8, device=None):
    """Logits (R, S - first, V) at positions first..S-1 of tokens (R, S).

    Layer-outer so that each layer's weights are made once; rows go through
    a layer in blocks so the attention scores of a block fit."""
    import numpy as np
    device = device or jax.devices()[0]
    tokens = np.asarray(tokens)
    R = tokens.shape[0]
    blocks = [slice(i, min(i + rows_per_block, R))
              for i in range(0, R, rows_per_block)]
    with jax.default_device(device):
        outer = outer_weights(seed, conf)
        emb = jax.jit(partial(embed, conf))
        hs = [emb(outer, jnp.asarray(tokens[b])) for b in blocks]
        step = jax.jit(partial(layer, conf), static_argnums=(2,))
        for li in range(conf["num_hidden_layers"]):
            w = layer_weights(seed, conf, li)
            hs = [step(w, h, prec) for h in hs]
            del w
        top = jax.jit(lambda o, h: head(conf, o, h[:, first:], prec))
        out = [np.asarray(top(outer, h)) for h in hs]
    return np.concatenate(out, axis=0)


# ---------------------------------------------------------------------------
# training: loss, gradients and AdamW as the traffic file states them
# ---------------------------------------------------------------------------

def loss(conf: dict, params: dict, tokens, labels, z_loss: float,
         prec: str = "f32"):
    """Mean token cross-entropy plus z-loss, every layer rematerialized."""
    layer_names = list(layer_shapes(conf))
    stacked = {n: params[n] for n in layer_names}

    @jax.checkpoint
    def body(h, w):
        return layer(conf, w, h, prec), None

    h = embed(conf, params, tokens)
    h, _ = jax.lax.scan(body, h, stacked)
    logits = head(conf, params, h, prec)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll + z_loss * lse * lse)


def adamw_lr(opt: dict, step: int) -> float:
    """Linear warmup, then cosine to a tenth of the peak (flat after)."""
    import math
    lr, warm, total = (opt["learning_rate"], opt["warmup_steps"],
                       opt["total_steps"])
    if step < warm:
        return lr * step / max(warm, 1)
    frac = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return lr * (0.1 + 0.45 * (1 + math.cos(math.pi * frac)))


def train_steps(seed: int, conf: dict, batches: list, opt: dict,
                z_loss: float, prec: str = "f32") -> dict:
    """Follow the first len(batches) steps from the seed's weights.

    Returns per-step losses, the first (clipped) gradient on the host and
    its per-leaf norms, and per-leaf norms of the change of the weights
    over all the steps."""
    import numpy as np
    params = all_weights(seed, conf)
    start = {k: v for k, v in params.items()}

    @jax.jit
    def grad(p, tokens, labels):
        return jax.value_and_grad(partial(loss, conf, z_loss=z_loss,
                                          prec=prec))(p, tokens, labels)

    @jax.jit
    def update(p, g, m, v, lr, t):
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        g = jax.tree.map(
            lambda x: x * jnp.minimum(1.0, opt["grad_clip"]
                                      / jnp.maximum(gn, 1e-9)), g)
        b1, b2 = opt["adam_b1"], opt["adam_b2"]
        m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
        p = jax.tree.map(
            lambda p_, m_, v_: p_ - lr * (
                (m_ / bc1) / (jnp.sqrt(v_ / bc2) + opt["adam_eps"])
                + opt["weight_decay"] * p_), p, m, v)
        return p, g, m, v

    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first_grad, first_norms = [], None, None
    for t, (tokens, labels) in enumerate(batches, start=1):
        value, g = grad(params, jnp.asarray(tokens), jnp.asarray(labels))
        params, g, m, v = update(params, g, m, v,
                                 jnp.float32(adamw_lr(opt, t)),
                                 jnp.float32(t))
        losses.append(float(value))
        if first_grad is None:
            first_norms = {k: float(jnp.linalg.norm(x)) for k, x in g.items()}
            first_grad = {k: np.asarray(x) for k, x in g.items()}
        del g
    change = {k: float(jnp.linalg.norm(params[k] - start[k]))
              for k in params}
    return {"losses": losses, "grad_norms": first_norms, "grad": first_grad,
            "change_norms": change}


# ---------------------------------------------------------------------------
# counts: operations and bytes the work needs (``flops.py``)
# ---------------------------------------------------------------------------

def _dims(conf: dict) -> tuple[int, int, int, int, int, int, int]:
    return (conf["num_hidden_layers"], conf["hidden_size"],
            conf["intermediate_size"], conf["num_attention_heads"],
            conf["num_key_value_heads"], conf["head_dim"], conf["vocab_size"])


def layer_matmul_params(conf: dict) -> int:
    """Weights one decoder layer multiplies by: q, k, v, o, the gated MLP."""
    _, d, ff, h, kv, hd, _ = _dims(conf)
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff


def matmul_params(conf: dict) -> int:
    """Weights every token is multiplied by, the output head included."""
    n_layers, d, *_, vocab = _dims(conf)
    return n_layers * layer_matmul_params(conf) + d * vocab


def param_count(conf: dict) -> int:
    """All parameters: embedding, layers, norms and an untied head."""
    n_layers, d, *_, vocab = _dims(conf)
    norms = n_layers * 2 * d + d
    head = 0 if conf["tie_word_embeddings"] else d * vocab
    return vocab * d + n_layers * layer_matmul_params(conf) + norms + head


def forward_attention_flops(conf: dict, batch: int, seq: int) -> int:
    """QK^T and PV over the causal pairs, every layer."""
    n_layers, _, _, h, _, hd, _ = _dims(conf)
    return n_layers * batch * flops.attention_pairs(seq) * h * hd * 2 * 2


def attention_sublayer_flops(conf: dict, batch: int, seq: int) -> int:
    """The attention sublayer's forward over ``batch`` x ``seq``: the q, k, v
    and o projections on every token and QK^T and PV over the causal pairs,
    every layer."""
    n_layers, d, _, h, kv, hd, _ = _dims(conf)
    proj = 2 * d * h * hd + 2 * d * kv * hd
    return (2 * n_layers * proj * batch * seq
            + forward_attention_flops(conf, batch, seq))


def train_step_flops(conf: dict, batch: int, seq: int) -> int:
    """Forward and backward (3x the forward) of one step on batch x seq."""
    tokens = batch * seq
    return (6 * matmul_params(conf) * tokens
            + 3 * forward_attention_flops(conf, batch, seq))


def prefill_flops(conf: dict, batch: int, seq: int) -> int:
    """Forward pass over the prompt; the head on the last position only."""
    n_layers, d, *_, vocab = _dims(conf)
    return (2 * n_layers * layer_matmul_params(conf) * batch * seq
            + forward_attention_flops(conf, batch, seq)
            + 2 * batch * d * vocab)


def decode_step_bytes(conf: dict, batch: int, position: int) -> int:
    """HBM bytes one decode step must read and write at ``position``.

    Every weight once at the compute dtype (bf16), except that an untied
    embedding table is read only at the batch's rows; the K and V entries of
    the ``position + 1`` valid positions, read; the new entry, written.
    """
    n_layers, d, _, _, kv, hd, vocab = _dims(conf)
    weights = param_count(conf)
    if not conf["tie_word_embeddings"]:
        weights -= vocab * d - batch * d
    kv_entry = n_layers * 2 * kv * hd * flops.BF16_BYTES
    return (weights * flops.BF16_BYTES + batch * (position + 1) * kv_entry
            + batch * kv_entry)
