"""The decode program's share of the chips' HBM bandwidth: the bytes its
traced steps must move (flops.decode_step_bytes: bf16 weights and the valid
KV entries) over its device time."""
import flops


def read(ctx):
    step = ctx.trace["modules"].get("step")
    steps = ctx.counters["decode"]
    if not step or not steps or not step["s"]:
        return None
    need = sum(flops.decode_step_bytes(ctx.conf, b, pos) for b, pos in steps)
    return 100.0 * need / (step["s"] * ctx.chips
                           * ctx.peaks["hbm_bytes_per_s"])
