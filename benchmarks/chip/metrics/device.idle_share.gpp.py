"""Share of the traced stretch in which no operation ran on the device
(the busy union of the device's ops, averaged over the chips), in %."""


def read(ctx):
    t = ctx.trace
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
