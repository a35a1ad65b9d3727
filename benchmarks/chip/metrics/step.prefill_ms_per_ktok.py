"""Device time of the prefill programs over the prompt tokens admitted in
the traced stretch, in ms per 1000 prompt tokens."""


def read(ctx):
    prog = ctx.trace["programs"].get(ctx.counters.get("prefill_program"))
    toks = sum(sum(s.prefill_tokens) for s in ctx.counters["traced_steps"])
    if not prog or not toks:
        return None
    return prog[1] / toks * 1e6
