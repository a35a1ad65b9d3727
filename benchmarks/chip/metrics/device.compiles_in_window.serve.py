"""Backend compilations JAX reported from the start of the window to the
end of the drain; every shape is compiled in set-up, so this reads 0."""


def read(ctx):
    return float(ctx.counters["compiles_in_window"])
