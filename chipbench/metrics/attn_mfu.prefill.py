"""The prefill attention sublayer's share of the chips' peak: the operations
that the traced prompts' attention needs (flops.attention_sublayer_flops of
``serve.prefill``'s batch and prompt length) over the prefill program's
device time in the ``attn`` scope."""
import flops
import scopes


def read(ctx):
    r = scopes.of_view(ctx)
    if not r:
        return None
    calls = r["program_spans"].get("serve.prefill", {}).get("calls")
    t = r["scopes"].get("prefill", {}).get("attn")
    if not calls or not t:
        return None
    need = sum(flops.attention_sublayer_flops(ctx.conf, c["batch"],
                                              c["prompt_len"]) for c in calls)
    return 100.0 * need / (t * ctx.chips * ctx.peaks["bf16_flops_per_s"])
