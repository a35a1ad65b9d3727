"""Share of the prefilled bucket tokens that were padding, over the
window: 1 - prompt tokens / padded bucket tokens, from the engine's
`prefill_tokens` and `prefill_bucket_tokens` counters, in %."""


def read(ctx):
    ws = ctx.counters.get("window_stats") or {}
    bucket = ws.get("prefill_bucket_tokens")
    if not bucket:
        return None
    return 100.0 * (1.0 - ws["prefill_tokens"] / bucket)
