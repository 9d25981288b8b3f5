"""The prefill program's share of the chips' peak: the operations its
traced prompts need (flops.prefill_flops) over its device time."""
import flops


def read(ctx):
    prefill = ctx.trace["modules"].get("prefill")
    shapes = ctx.counters["prefill"]
    if not prefill or not shapes or not prefill["s"]:
        return None
    need = sum(flops.prefill_flops(ctx.conf, b, s) for b, s in shapes)
    return 100.0 * need / (prefill["s"] * ctx.chips
                           * ctx.peaks["bf16_flops_per_s"])
