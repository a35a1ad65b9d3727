"""Share of slot-steps that carried a request over the window: the
engine's slot_steps_active / (decode steps x max_batch), in %."""


def read(ctx):
    ws = ctx.counters.get("window_stats") or {}
    if not ws.get("decode_steps"):
        return None
    return 100.0 * ws["occupancy"]
