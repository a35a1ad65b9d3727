"""repro: a production-scale jax_pallas system grown from the paper's
single-kernel roofline study (8 Steps to 3.7 TFLOP/s, arXiv:2008.11326).

`import repro` is the documented entry point; the public surface is lazy
(nothing heavy imports until first attribute access). Every name below
carries a full docstring with a runnable example at its definition —
`help(repro.dispatch)` etc. resolves it:

    dispatch(name, *args, version=, config=, problem_key=)
        Run a registered kernel; config resolves from the tune cache.
    get_kernel(name) / list_kernels()
        The Kernel descriptor registry (docs/kernels.md).
    build_model(cfg)
        Config -> Model bundle: init/loss/prefill/decode/prefill_into_slot
        + the logical-axis metadata the sharding engine consumes.
    ServeEngine(cfg, params, max_batch=, cache_len=, mesh=) / Request
        Slot-level continuous-batching server; pass mesh= to serve
        tensor-parallel over a repro.dist mesh (docs/serving.md).
    PagedKVCache
        Paged K/V storage behind ServeEngine(kv_page_size=): block
        tables, FIFO free-list, refcounted prefix reuse, optional int8
        pages (docs/serving.md §Paged K/V cache).
    Router(cfg, params, replicas=, fault_plan=) / FaultPlan
        DP router over N replica engines with heartbeat failover,
        deterministic fault injection + recovery (FaultPlan.recover/
        flap), deadlines, and bounded-queue load shedding with retry
        backoff (docs/serving.md §router).
    OverloadConfig(window_ticks=, queue_high=, ttft_p99_high=)
        Windowed brown-out controller for the Router's admission path
        (docs/serving.md §Overload & recovery).
    generate_trace(TraceConfig(...))
        Seeded synthetic request traces: Poisson/bursty arrivals,
        heavy-tail length mixes.
    run_journey(size)
        The paper's Table I, v0-v10, on the modeled v5e roofline.
    tune_kernel(kernel, key)
        Model-then-measure autotuner; winners persist to the JSON cache.
    audit_registry(kernels=None)
        Static kernel auditor: jaxpr census + rule catalog over every
        registered (kernel, version, canonical shape) — no execution
        (docs/analysis.md; `python -m repro.analyze --strict` is the CI
        gate, `python -m repro.tune validate|prune` the cache hygiene).

    import repro
    repro.list_kernels()          # ['flash', 'gpp', 'paged_decode', 'ssm']
    ach, asx = repro.dispatch("gpp", inputs, version="v10")
    k = repro.get_kernel("flash")              # Kernel descriptor
    model = repro.build_model(cfg)
    engine = repro.ServeEngine(cfg, params)
    rows = repro.run_journey("si214")
"""

# public name -> defining module; resolved lazily on first access so that
# `import repro` stays cheap and optional layers never import eagerly
_EXPORTS = {
    "get_kernel": "repro.kernels.api",
    "dispatch": "repro.kernels.api",
    "list_kernels": "repro.kernels.api",
    "ServeEngine": "repro.serve.engine",
    "Request": "repro.serve.engine",
    "PagedKVCache": "repro.serve.kvcache",
    "Router": "repro.serve.router",
    "FaultPlan": "repro.serve.router",
    "OverloadConfig": "repro.serve.router",
    "TraceConfig": "repro.serve.trace",
    "generate_trace": "repro.serve.trace",
    "build_model": "repro.models.registry",
    "run_journey": "repro.core.journey",
    "tune_kernel": "repro.tune.tuner",
    "audit_registry": "repro.analyze.rules",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name: str):
    try:
        module = _EXPORTS[name]
    except KeyError:
        raise AttributeError(f"module 'repro' has no attribute {name!r}"
                             ) from None
    import importlib
    value = getattr(importlib.import_module(module), name)
    globals()[name] = value        # cache: subsequent access skips __getattr__
    return value


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))
