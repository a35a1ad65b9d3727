"""Share of the traced stretch in which the device was idle while the
engine's `serve.sample` span (the sampler's enqueue and the tokens' pull
to the host) was the innermost program span open on the host, in %."""
from benchmarks.chip import spans


def read(ctx):
    planes = spans.planes_of(ctx)
    if not planes or not spans.program_spans(planes, "serve.sample"):
        return None
    idle = spans.idle_by_span(planes, spans.PROGRAM, ctx.chips)
    return 100.0 * idle.get("serve.sample", 0.0) / ctx.trace["window_s"]
