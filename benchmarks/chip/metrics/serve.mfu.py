"""Model FLOPs of the prefill and decode work done in the traced stretch
(flops.py) over the stretch's length times the chips' bf16 peak, in %."""
from benchmarks.chip import flops


def read(ctx):
    steps = ctx.counters["traced_steps"]
    if not steps:
        return None
    shape = flops.DenseShape.of(ctx.cfg)
    work = sum(flops.decode_step_flops(shape, s.decode_ctx)
               + sum(flops.prefill_flops(shape, n) for n in s.prefill_tokens)
               for s in steps)
    return 100.0 * work / (ctx.trace["window_s"] * ctx.chips
                           * ctx.peaks.bf16_flops)
