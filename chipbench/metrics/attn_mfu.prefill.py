"""The prefill attention sublayer's share of the chips' peak: the operations
that the traced prompts' attention needs (``serve.prefill``'s batch and
prompt length) over the prefill program's device time in the ``attn``
scope."""
import flops
import scopes


def attention_sublayer_flops(conf: dict, batch: int, seq: int) -> int:
    """The attention sublayer's forward over ``batch`` x ``seq``: the q, k, v
    and o projections on every token and QK^T and PV over the causal pairs,
    every layer."""
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    kv, hd = conf["num_key_value_heads"], conf["head_dim"]
    proj = 2 * d * h * hd + 2 * d * kv * hd
    return (2 * conf["num_hidden_layers"] * proj * batch * seq
            + flops.forward_attention_flops(conf, batch, seq))


def read(ctx):
    r = scopes.of_view(ctx)
    if not r:
        return None
    calls = r["program_spans"].get("serve.prefill", {}).get("calls")
    t = r["scopes"].get("prefill", {}).get("attn")
    if not calls or not t:
        return None
    need = sum(attention_sublayer_flops(ctx.conf, c["batch"], c["prompt_len"])
               for c in calls)
    return 100.0 * need / (t * ctx.chips * ctx.peaks["bf16_flops_per_s"])
