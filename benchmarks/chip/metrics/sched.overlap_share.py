"""Share of the window's decode steps that the engine enqueued while the
previous step's tokens were still on the device: the engine's
`rounds_overlapped` ÷ `decode_steps`, in %."""


def read(ctx):
    ws = ctx.counters.get("window_stats") or {}
    steps = ws.get("decode_steps")
    if "rounds_overlapped" not in ws or not steps:
        return None
    return 100.0 * ws["rounds_overlapped"] / steps
