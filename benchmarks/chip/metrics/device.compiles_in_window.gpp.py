"""Backend compilations JAX reported inside the window of GPP calls
(set-up compiled every program once; a dispatch that traces and compiles
its kernel again on every call shows here)."""


def read(ctx):
    return float(ctx.counters["compiles_in_window"])
