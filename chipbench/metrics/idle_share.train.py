"""Share of the traced training window in which no operation ran on the
device (averaged over its chips): the host holding the chip back."""


def read(ctx):
    t = ctx.trace
    if not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
