"""Operations and bytes that the algorithm needs, from a configuration's shapes.

The counts depend only on the configuration file and the work's shapes, never
on how the program implements the work: recomputation (remat), padding,
replicated work across chips and the whole ring buffer that a decode step may
read are not counted. A share built on these counts is therefore at most 100%
when the time is the device time of the same work.

Attention is causal: a prompt of S tokens has S (S + 1) / 2 query-key pairs.

Each count is the configuration's architecture family's
(``reference/<reference>.py``, ``common.family``); what this module holds
besides is shared by every family.
"""
from __future__ import annotations

import common

BF16_BYTES = 2


def attention_pairs(seq: int) -> int:
    """Causal query-key pairs of one sequence of ``seq`` tokens."""
    return seq * (seq + 1) // 2


def _family(conf: dict):
    return common.family(conf["reference"])


def layer_matmul_params(conf: dict) -> int:
    """Weights one decoder layer multiplies by."""
    return _family(conf).layer_matmul_params(conf)


def matmul_params(conf: dict) -> int:
    """Weights every token is multiplied by, the output head included."""
    return _family(conf).matmul_params(conf)


def param_count(conf: dict) -> int:
    """All parameters."""
    return _family(conf).param_count(conf)


def forward_attention_flops(conf: dict, batch: int, seq: int) -> int:
    """The attention core's forward over the causal pairs, every layer."""
    return _family(conf).forward_attention_flops(conf, batch, seq)


def attention_sublayer_flops(conf: dict, batch: int, seq: int) -> int:
    """The attention sublayer's forward over ``batch`` x ``seq``, every
    layer: its projections and its core."""
    return _family(conf).attention_sublayer_flops(conf, batch, seq)


def train_step_flops(conf: dict, batch: int, seq: int) -> int:
    """Forward and backward of one step on batch x seq."""
    return _family(conf).train_step_flops(conf, batch, seq)


def prefill_flops(conf: dict, batch: int, seq: int) -> int:
    """Forward pass over the prompt; the head on the last position only."""
    return _family(conf).prefill_flops(conf, batch, seq)


def decode_step_bytes(conf: dict, batch: int, position: int) -> int:
    """HBM bytes one decode step must read and write at ``position``."""
    return _family(conf).decode_step_bytes(conf, batch, position)
