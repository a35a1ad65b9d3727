"""Device time of one run of the decode program, from the trace: its
total device time over its number of runs, in ms."""


def read(ctx):
    prog = ctx.trace["programs"].get(ctx.counters.get("decode_program"))
    if not prog or not prog[0]:
        return None
    return prog[1] / prog[0] * 1e3
