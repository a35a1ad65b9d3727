"""95th percentile of the duration of the engine's `serve.admit` spans
that lie wholly inside the traced stretch (one admission: the batch-1
prefill and the first token's pull to the host), in ms."""
from benchmarks.chip import common, spans


def read(ctx):
    planes = spans.planes_of(ctx)
    admits = spans.whole_spans(planes, "serve.admit") if planes else []
    if not admits:
        return None
    return common.percentile([d for _, _, d in admits], 95) * 1e-6
