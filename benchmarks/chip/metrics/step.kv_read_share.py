"""Share of the K/V cache lines that the decode steps read, over the
window: the engine's `kv_lines_read` (each active row's live lines,
rounded up to the attention kernel's block, where the kernel reads only
those; every line otherwise) ÷ `kv_lines_full` (max_batch × cache_len a
step), in %."""


def read(ctx):
    ws = ctx.counters.get("window_stats") or {}
    full = ws.get("kv_lines_full")
    if not full:
        return None
    return 100.0 * ws["kv_lines_read"] / full
