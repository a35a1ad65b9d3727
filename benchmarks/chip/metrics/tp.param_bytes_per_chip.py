"""Weight bytes held on the fullest chip of the tensor-parallel mesh, in
GB (1e9 B): the largest `params_bytes` of the engine's per-device counters
(`finalize()["per_device"]`, measured from the committed weight shards)
at the end of the window. Engines without a mesh report no per-device
counters, and this reads nothing."""


def read(ctx):
    ws = ctx.counters.get("window_stats") or {}
    per_dev = ws.get("per_device") or []
    if not per_dev:
        return None
    return max(d["params_bytes"] for d in per_dev) / 1e9
