"""Share of its roofline that the decode step reaches, in %: the least
time the chips could take for the work a traced step needs (flops.py:
every weight and the live K/V read once, split over the chips) over the
decode program's measured device time per run."""
from benchmarks.chip import flops


def read(ctx):
    prog = ctx.trace["programs"].get(ctx.counters.get("decode_program"))
    steps = [s for s in ctx.counters["traced_steps"] if s.decode_ctx]
    if not prog or not prog[0] or not steps:
        return None
    shape = flops.DenseShape.of(ctx.cfg)
    p = ctx.peaks
    need = [flops.roofline_s(flops.decode_step_flops(shape, s.decode_ctx),
                             flops.decode_step_bytes(shape, s.decode_ctx),
                             chips=ctx.chips, peak_flops=p.bf16_flops,
                             hbm_bw=p.hbm_bw) for s in steps]
    return 100.0 * (sum(need) / len(need)) / (prog[1] / prog[0])
