"""The train step's share of the chips' peak: the operations its traced
steps need (flops.train_step_flops) over the step program's device time."""
import flops


def read(ctx):
    step = ctx.trace["modules"].get("step")
    shapes = ctx.counters["train"]
    if not step or not shapes or not step["s"]:
        return None
    need = sum(flops.train_step_flops(ctx.conf, b, s) for b, s in shapes)
    return 100.0 * need / (step["s"] * ctx.chips
                           * ctx.peaks["bf16_flops_per_s"])
