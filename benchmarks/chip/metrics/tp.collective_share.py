"""Share of chip 0's busy time in the traced stretch that collective
operations (all-gather, all-reduce and the like) take, in %: the reduced
trace's `collective_s`, the union of chip 0's collective ops, over the
union of every op of the same chip in the same stretch. A collective that
overlaps compute counts in full."""
from benchmarks.chip import spans
from benchmarks.chip import trace_reduce as tr


def read(ctx):
    planes = spans.planes_of(ctx)
    if not planes:
        return None
    t0, t1 = tr.window(planes)
    devs = tr.device_planes(planes)
    ops0 = tr.clip(tr.line_events(devs[min(devs)], tr.OPS_LINE), t0, t1)
    busy0 = tr.busy_ns(ops0) * 1e-9
    if not busy0:
        return None
    return 100.0 * ctx.trace["collective_s"] / busy0
