"""Median duration of the `kernel.dispatch` spans that lie wholly inside
the traced stretch (config resolution, the kernel's host work and its
enqueue; this cell dispatches only gpp), in ms."""
import numpy as np

from benchmarks.chip import spans


def read(ctx):
    planes = spans.planes_of(ctx)
    calls = spans.whole_spans(planes, "kernel.dispatch") if planes else []
    if not calls:
        return None
    return float(np.median([d for _, _, d in calls])) * 1e-6
