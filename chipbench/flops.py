"""Operations and bytes that the algorithm needs, from a configuration's shapes.

The counts depend only on the configuration file and the work's shapes, never
on how the program implements the work: recomputation (remat), padding,
replicated work across chips and the whole ring buffer that a decode step may
read are not counted. A share built on these counts is therefore at most 100%
when the time is the device time of the same work.

Attention is causal: a prompt of S tokens has S (S + 1) / 2 query-key pairs.
"""
from __future__ import annotations

BF16_BYTES = 2


def _dims(conf: dict) -> tuple[int, int, int, int, int, int, int]:
    return (conf["num_hidden_layers"], conf["hidden_size"],
            conf["intermediate_size"], conf["num_attention_heads"],
            conf["num_key_value_heads"], conf["head_dim"], conf["vocab_size"])


def layer_matmul_params(conf: dict) -> int:
    """Weights one decoder layer multiplies by: q, k, v, o and the gated MLP."""
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


def attention_pairs(seq: int) -> int:
    """Causal query-key pairs of one sequence of ``seq`` tokens."""
    return seq * (seq + 1) // 2


def forward_attention_flops(conf: dict, batch: int, seq: int) -> int:
    """QK^T and PV over the causal pairs, every layer."""
    n_layers, _, _, h, _, hd, _ = _dims(conf)
    return n_layers * batch * attention_pairs(seq) * h * hd * 2 * 2


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
    kv_entry = n_layers * 2 * kv * hd * BF16_BYTES
    return (weights * BF16_BYTES + batch * (position + 1) * kv_entry
            + batch * kv_entry)

