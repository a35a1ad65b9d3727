"""FLOPs of the GPP calls that ran wholly in the traced stretch over the
stretch's length times the VPU f32 bound, in %."""
from benchmarks.chip import flops


def read(ctx):
    n = ctx.counters.get("traced_calls", 0)
    if not n:
        return None
    return 100.0 * n * flops.gpp_flops(ctx.counters["shape"]) / (
        ctx.trace["window_s"] * ctx.peaks.vpu_f32_flops)
