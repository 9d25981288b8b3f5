"""flops.py against counts worked out by hand from the published widths."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import common  # noqa: E402
import flops  # noqa: E402

SMOLLM = common.load_json(os.path.join(common.HERE, "configs",
                                       "smollm-360m.json"))
INTERNLM = common.load_json(os.path.join(common.HERE, "configs",
                                         "internlm-7b.json"))


def test_smollm_counts_by_hand():
    # per layer: q 960*15*64, k and v 960*5*64 each, o 15*64*960,
    # gate/up/down 3*960*2560
    layer = 921_600 + 2 * 307_200 + 921_600 + 3 * 2_457_600
    assert layer == 9_830_400
    assert flops.layer_matmul_params(SMOLLM) == layer
    head = 960 * 49152
    assert flops.matmul_params(SMOLLM) == 32 * layer + head
    # tied: the embedding is the head; 2 norms per layer and the final one
    assert flops.param_count(SMOLLM) == (49152 * 960 + 32 * layer
                                         + 65 * 960)
    # training step, 4 x 1024: 6 N T plus 3 x causal attention forward
    # (2 matmuls x 2 flops x pairs x heads x head_dim x layers)
    attn = 32 * 4 * (1024 * 1025 // 2) * 15 * 64 * 4
    assert flops.train_step_flops(SMOLLM, 4, 1024) == (
        6 * (32 * layer + head) * 4096 + 3 * attn)
    assert 9.6e12 < flops.train_step_flops(SMOLLM, 4, 1024) < 9.8e12
    # prefill 32 x 512: layers on every token, the head on the last only
    assert flops.prefill_flops(SMOLLM, 32, 512) == (
        2 * 32 * layer * 32 * 512 + 32 * 32 * (512 * 513 // 2) * 15 * 64 * 4
        + 2 * 32 * 960 * 49152)


def test_internlm_counts_by_hand():
    layer = 4 * 4096 * 4096 + 3 * 4096 * 11008
    assert flops.layer_matmul_params(INTERNLM) == layer
    params = 103168 * 4096 * 2 + 32 * layer + 65 * 4096
    assert flops.param_count(INTERNLM) == params
    assert 7.3e9 < params < 7.35e9       # 29.3 GB in fp32
    # decode step at position 1100, batch 8: bf16 weights with the embedding
    # read at 8 rows, K and V of 1101 positions read, one position written
    kv = 32 * 2 * 32 * 128 * 2
    weights = params - 103168 * 4096 + 8 * 4096
    assert flops.decode_step_bytes(INTERNLM, 8, 1100) == (
        2 * weights + 8 * 1101 * kv + 8 * kv)


def test_decode_bytes_grow_with_position_only_by_kv():
    a = flops.decode_step_bytes(SMOLLM, 64, 1024)
    b = flops.decode_step_bytes(SMOLLM, 64, 1025)
    assert b - a == 64 * 32 * 2 * 5 * 64 * 2
    # tied: the whole table is read as the head
    assert a - 64 * 1025 * 32 * 2 * 5 * 64 * 2 - 64 * 32 * 2 * 5 * 64 * 2 \
        == 2 * flops.param_count(SMOLLM)


def test_smollm_attention_sublayer_by_hand():
    # 64 x 1024 tokens, 32 layers; 2 flops a multiply-add. Projections:
    # q and o 960 <-> 15 x 64, k and v 960 -> 5 x 64, on every token
    tokens = 64 * 1024
    q = o = 2 * 960 * 960 * tokens
    k = v = 2 * 960 * 320 * tokens
    # QK^T and PV: 15 heads of 64 over each row's 1024 * 1025 / 2 causal
    # query-key pairs
    qk = pv = 2 * 64 * (1024 * 1025 // 2) * 15 * 64
    assert flops.attention_sublayer_flops(SMOLLM, 64, 1024) == 32 * (
        q + k + v + o + qk + pv)
    assert 1.4e13 < flops.attention_sublayer_flops(SMOLLM, 64, 1024) < 1.5e13
