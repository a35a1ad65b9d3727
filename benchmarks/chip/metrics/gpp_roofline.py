"""Share of its roofline that the GPP kernel reaches, in %: the larger of
its FLOPs over the VPU f32 bound and its compulsory bytes over the HBM
bandwidth (flops.py), over the Pallas kernel's device time per call."""
from benchmarks.chip import flops


def read(ctx):
    k = ctx.trace["op_kinds"].get("tpu_custom_call")
    if not k or not k[0]:
        return None
    s, p = ctx.counters["shape"], ctx.peaks
    need = max(flops.gpp_flops(s) / p.vpu_f32_flops,
               flops.gpp_min_bytes(s) / p.hbm_bw)
    return 100.0 * need / (k[1] / k[0])
