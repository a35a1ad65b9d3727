"""95th percentile of the time requests waited in the engine's queue before
a slot took them (the engine's own per-request queue_wait_s), in ms."""
import numpy as np


def read(ctx):
    waits = ctx.counters.get("queue_wait_s") or []
    return float(np.percentile(waits, 95)) * 1e3 if waits else None
