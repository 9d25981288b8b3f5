"""Device time in collectives inside the decode program's runs, over that
program's device time (both averaged over the chips)."""


def read(ctx):
    step = ctx.trace["modules"].get("step")
    if not step or not step["s"] or not ctx.counters["decode"]:
        return None
    return 100.0 * step["collective_s"] / step["s"]
