"""Benchmark harness — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run                   # all
    PYTHONPATH=src python -m benchmarks.run --only gpp_journey
    PYTHONPATH=src python -m benchmarks.run --only gpp_journey,gpp_tuner \
        --json runs/bench/BENCH_2.json                        # artifact

Prints `name,us_per_call,derived` CSV rows per the repo contract. With
--json PATH the same rows are also written as a BENCH_*.json artifact
(schema: benchmarks/report.py) so the perf trajectory persists PR-over-PR;
`python -m benchmarks.report --compare OLD NEW` diffs two artifacts and
flags >10% regressions (the CI gate).

Tables:
  table1_gpp_journey   — paper Table I: v0..v10 (CPU wall-clock at BENCH size
                         + modeled v5e TFLOP/s at Si-214/Si-510)
  fig_roofline_terms   — paper Figs 1/3/5/6: hierarchical terms per version
  fig8_locality        — paper Fig 8: HBM bytes per version (locality)
  v8_block_sweep       — the v8 tuning sweep (paper Sec. III-v8)
  gpp_tuner            — repro.tune winners per size (model-ranked; measured
                         where the size permits CPU timing)
  kernel_tuner         — tuned picks for the other registered kernels
                         (flash blk_q/blk_kv, ssm blk_c) via the same
                         generalized repro.tune flow
  model_cells          — the 40-cell dry-run roofline table (reads
                         runs/dryrun/*.json written by launch/dryrun.py)
  train_step_cpu       — measured wall-time of a reduced-config train step
                         per architecture (the CPU-executable signal)
  serve                — slot-scheduler serving stats on a reduced model
                         (decode steps / occupancy are deterministic;
                         latency/throughput fields are wall clock). Also
                         reachable via the --serve shortcut.
  router               — multi-replica DP router under a seeded bursty
                         trace: p50/p99 TTFT + time-per-output-token
                         (tick-denominated rows are deterministic and
                         gateable; _ms rows are wall clock), queue depth,
                         goodput-under-burst, per-replica rows. Shortcut:
                         --router [--replicas N] [--fault kill:R@T or
                         stall:R@T+D].
  kvcache              — paged K/V cache rows (serve/kvcache.py) under a
                         shared-system-prompt trace: prefix hit rate +
                         prefill tokens saved, measured bytes/slot vs the
                         static layout, paged-vs-static bit-exactness,
                         the int8 pool's pinned attention error, the
                         tuned paged_decode kernel pick, and the fleet
                         hit rate across router replicas. All token/page
                         counts are deterministic and gateable. Shortcut:
                         --kvcache (composable with --serve).
  spec                 — speculative decoding rows (serve/spec.py): the
                         acceptance rate and accepted-tokens-per-verify-
                         step of a layer-sliced draft, spec-vs-plain
                         bit-exactness at temperature 0, effective tok/s
                         for both engines (wall clock) and the tuned
                         multi-query paged_decode "verify" kernel pick.
                         Acceptance/parity rows are deterministic and
                         gateable. Shortcut: --spec (composable with
                         --serve/--kvcache).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import time

HERE = os.path.dirname(__file__)
RUNS = os.path.join(HERE, "..", "runs", "dryrun")

RESULTS = []          # rows emitted this run, for the --json artifact

# journey rows are expensive (jit + interpret-mode Pallas); compute them
# once per size and share across table1/roofline_terms/fig8. A cached row
# set without CPU timings is upgraded in place if a later table needs them.
_JOURNEY_CACHE = {}


def journey_rows(size: str, measure_cpu: bool = False):
    from repro.core.journey import run_journey
    rows = _JOURNEY_CACHE.get(size)
    if rows is None or (measure_cpu and rows[0].cpu_ms is None):
        rows = run_journey(size, measure_cpu=measure_cpu, verbose=False)
        _JOURNEY_CACHE[size] = rows
    return rows


def _csv(name, us, derived, kernel_config=None):
    """Emit one row. kernel_config (optional) records the selected kernel
    version + config and where it came from ({"kernel", "version",
    "config", "source"}) so report.py --compare can flag config churn —
    a tuned pick silently changing between artifacts — not just metric
    regressions."""
    print(f"{name},{us if us is not None else ''},{derived}")
    row = {"name": name, "us_per_call": us, "derived": derived}
    if kernel_config:
        row["kernel_config"] = kernel_config
    RESULTS.append(row)


def table1_gpp_journey():
    from repro.core.journey import FLOP_PEAK
    for size in ("si214", "si510"):
        rows = journey_rows(size, measure_cpu=(size == "si214"))
        for r in rows:
            us = r.cpu_ms * 1e3 if r.cpu_ms else None
            blocks = r.report.extra.get("block_config")
            kc = None
            if blocks:
                kc = {"kernel": "gpp", "version": r.version,
                      "config": {"blk_ig": blocks[0], "blk_igp": blocks[1],
                                 "blk_band": blocks[2]},
                      "source": "model" if r.version == "v10" else "static"}
            _csv(f"gpp_{size}_{r.version}", us,
                 f"modeled_tflops={r.modeled_tflops:.3f};"
                 f"pct_vpu_peak={r.modeled_tflops*1e12/FLOP_PEAK:.3f};"
                 f"step_s={r.report.modeled_step_s:.4f}",
                 kernel_config=kc)
        v0, vbest = rows[0], rows[-1]
        v8 = next(r for r in rows if r.version == "v8")
        _csv(f"gpp_{size}_speedup_v8_over_v0", None,
             f"{v0.report.modeled_step_s / v8.report.modeled_step_s:.3f}x"
             f" (paper: {'2.36x' if size == 'si214' else '3.27x'})")
        _csv(f"gpp_{size}_speedup_v10_over_v0", None,
             f"{v0.report.modeled_step_s / vbest.report.modeled_step_s:.3f}x"
             f" (beyond-paper steps)")


def fig_roofline_terms():
    for r in journey_rows("si214"):
        rep = r.report
        _csv(f"roofline_{r.version}", None,
             f"compute_s={rep.compute_s:.4f};memory_s={rep.memory_s:.5f};"
             f"dominant={rep.dominant}")


def fig8_locality():
    rows = journey_rows("si214")
    base = rows[0].report.bytes_per_chip
    for r in rows:
        rep = r.report
        _csv(f"hbm_bytes_{r.version}", None,
             f"gib={rep.bytes_per_chip/2**30:.2f};"
             f"vs_v0={rep.bytes_per_chip/base:.3f}")


def v8_block_sweep():
    from repro.core.journey import sweep_blocks
    for row in sweep_blocks("si214")[:8]:
        _csv(f"sweep_ig{row['blk_ig']}_igp{row['blk_igp']}_b{row['blk_band']}",
             None, f"modeled_s={row['modeled_s']:.4f};"
             f"tflops={row['tflops']:.3f};vmem_mib={row['vmem_mib']:.1f}")


def gpp_tuner():
    """The autotuner's pick per size. Model-only (measure_mode=False) so
    the artifact rows are deterministic — the regression gate must not
    depend on one noisy interpret-mode timing choosing among near-tied
    configs; the measured pass is exercised by tests/test_tune.py and the
    ops.gpp("v10") dispatch path."""
    import dataclasses

    from repro.kernels.gpp.problem import SIZES
    from repro.tune import tuner
    for name in ("tiny", "bench", "si214", "si510"):
        tc = tuner.tune(SIZES[name], use_cache=False, measure_mode=False)
        c = tc.config
        _csv(f"tuned_{name}", None,
             f"blk_ig={c.blk_ig};blk_igp={c.blk_igp};blk_band={c.blk_band};"
             f"modeled_s={tc.modeled_s:.4g};source={tc.source}",
             kernel_config={"kernel": "gpp", "version": "v10",
                            "config": dataclasses.asdict(c),
                            "source": tc.source})


def kernel_tuner():
    """The registry-wide generalization of gpp_tuner: every non-gpp kernel's
    tuned pick at representative sizes, through the same model-then-measure
    flow and (kernel, ProblemKey, backend, version) cache keying.
    Model-only for determinism (same rationale as gpp_tuner)."""
    import dataclasses

    from repro.kernels.flash.kernel_def import FlashKey
    from repro.kernels.ssm.kernel_def import SsmKey
    from repro.tune import tuner

    keys = [
        # (row name, kernel, key)
        ("flash_train_4k", "flash",
         FlashKey(b=8, h=16, kvh=4, sq=4096, skv=4096, hd=128)),
        ("flash_prefill_32k", "flash",
         FlashKey(b=1, h=16, kvh=4, sq=32768, skv=32768, hd=128)),
        ("flash_block_256", "flash",
         FlashKey(b=4, h=8, kvh=2, sq=256, skv=256, hd=64)),
        ("ssm_hymba_4k", "ssm", SsmKey(b=16, t=4096, c=6400, n=16)),
        ("ssm_small", "ssm", SsmKey(b=2, t=256, c=256, n=16)),
    ]
    for name, kernel, key in keys:
        tc = tuner.tune_kernel(kernel, key, use_cache=False,
                               measure_mode=False)
        cfg = dataclasses.asdict(tc.config)
        dims = ";".join(f"{k}={v}" for k, v in cfg.items() if k != "name")
        _csv(f"tuned_{name}", None,
             f"{dims};modeled_s={tc.modeled_s:.4g};source={tc.source}",
             kernel_config={"kernel": kernel, "version": tc.key.split("|")[-1],
                            "config": cfg, "source": tc.source})


def model_cells():
    files = sorted(glob.glob(os.path.join(RUNS, "*__single.json")))
    if not files:
        _csv("model_cells", None, "no dry-run artifacts (run launch.dryrun)")
        return
    for f in files:
        r = json.load(open(f))
        _csv(f"cell_{r['name'].replace('/', '_')}", None,
             f"step_s={r['step_s']:.4g};dominant={r['dominant']};"
             f"roofline={r['roofline_frac']:.3f};"
             f"mem_gib={r.get('hbm_adjusted_gib', 0):.2f};"
             f"fits={r['fits_hbm']}")


def train_step_cpu():
    import jax
    from repro.configs.base import ARCH_IDS, get_config, reduce_config
    from repro.models.registry import build_model
    for arch in ARCH_IDS:
        cfg = reduce_config(get_config(arch))
        model = build_model(cfg)
        rng = jax.random.PRNGKey(0)
        params = model.init_params(rng)
        batch = {"tokens": jax.random.randint(rng, (2, 64), 0, cfg.vocab_size),
                 "labels": jax.random.randint(rng, (2, 64), 0, cfg.vocab_size)}
        if cfg.family == "encdec":
            batch["frames"] = jax.numpy.zeros((2, cfg.enc_seq, cfg.d_model),
                                              jax.numpy.bfloat16)
        if cfg.family == "vlm":
            batch["vis"] = jax.numpy.zeros((2, cfg.n_vis_tokens, cfg.d_model),
                                           jax.numpy.bfloat16)
        fn = jax.jit(jax.grad(lambda p: model.loss_fn(p, batch)[0]))
        g = fn(params)
        jax.block_until_ready(g)
        t0 = time.perf_counter()
        g = fn(params)
        jax.block_until_ready(g)
        dt = time.perf_counter() - t0
        _csv(f"train_step_{arch}", dt * 1e6, "reduced-config fwd+bwd on CPU")


# --serve --mesh tp size (int), set by main() before jax imports so the
# forced host device count can take effect
SERVE_MESH = None


def _parse_mesh(spec: str) -> int:
    axis, sep, n = spec.partition("=")
    if axis != "tp" or not sep or not n.isdigit() or int(n) < 1:
        raise SystemExit(f"--mesh expects 'tp=N', got {spec!r}")
    return int(n)


def serve():
    """Slot-level continuous-batching stats: a mixed-length workload with
    more requests than slots on a reduced model. decode_steps / prefills /
    new_tokens / occupancy are deterministic (fixed workload, greedy or
    per-request keyed sampling); ttft/queue/tok_per_s are wall clock and
    therefore informational only (no gate-list metric names).

    With --mesh tp=N the engine serves tensor-parallel over an N-way
    `model` mesh axis (dist.sharding.serve_specs exact-TP layout) and one
    extra serve_device_<i> row per device records its occupancy / tok_per_s
    plus the measured local param/cache shard sizes."""
    import jax
    import numpy as np

    from repro.configs.base import get_config, reduce_config
    from repro.launch.mesh import make_mesh
    from repro.models.registry import build_model
    from repro.serve.engine import Request, ServeEngine

    mesh = None
    if SERVE_MESH is not None:
        tp = SERVE_MESH
        if jax.device_count() < tp:
            raise SystemExit(
                f"--mesh tp={tp} needs {tp} devices but jax sees "
                f"{jax.device_count()} (run.py forces the host platform "
                "count only when jax is not already initialized)")
        mesh = make_mesh((tp,), ("model",))
    # d_model=256 gives 8 heads / d_ff 768: dims an 8-way axis divides
    cfg = reduce_config(get_config("qwen2-1.5b"), layers=2,
                        d_model=(256 if mesh is not None else 64),
                        vocab=128)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_batch=4, cache_len=128, mesh=mesh)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 4 + i % 7),
                    max_new_tokens=(4 if i % 3 else 32),
                    temperature=(0.7 if i % 2 else 0.0))
            for i in range(12)]
    out, stats = eng.run(reqs, collect_stats=True)
    e = stats["engine"]
    _csv("serve_engine", e["wall_s"] * 1e6,
         f"decode_steps={e['decode_steps']};prefills={e['prefills']};"
         f"new_tokens={e['new_tokens']};occupancy={e['occupancy']:.3f};"
         f"tok_per_s={e['tok_per_s']:.1f};"
         f"mean_ttft_ms={e['mean_ttft_s'] * 1e3:.1f};"
         f"mean_queue_ms={e['mean_queue_wait_s'] * 1e3:.1f}")
    ttfts = [r.ttft_s for r in stats["requests"].values()]
    waits = [r.queue_wait_s for r in stats["requests"].values()]
    _csv("serve_latency", None,
         f"p50_ttft_ms={np.percentile(ttfts, 50) * 1e3:.1f};"
         f"p95_ttft_ms={np.percentile(ttfts, 95) * 1e3:.1f};"
         f"p50_queue_ms={np.percentile(waits, 50) * 1e3:.1f};"
         f"p95_queue_ms={np.percentile(waits, 95) * 1e3:.1f}")
    for d in e.get("per_device", []):
        _csv(f"serve_device_{d['device']}", None,
             f"occupancy={d['occupancy']:.3f};"
             f"tok_per_s={d['tok_per_s']:.1f};"
             f"params_mib={d['params_bytes'] / 2**20:.3f};"
             f"cache_mib={d['cache_bytes'] / 2**20:.3f}")


# --router knobs, set by main()
ROUTER_REPLICAS = 2
ROUTER_FAULT = None


def _parse_fault(spec: str):
    """'kill:R@T', 'stall:R@T+D', 'recover:R@T', or 'flap:R@T+D' (a
    kill at T + recover at T+D) -> FaultPlan (import-free parse check
    lives here so argparse errors stay legible)."""
    from repro.serve.router import FaultPlan
    plan = FaultPlan()
    for part in spec.split(","):
        kind, sep, rest = part.partition(":")
        try:
            if kind == "kill":
                rep, tick = rest.split("@")
                plan.kill(int(rep), at_tick=int(tick))
            elif kind == "stall":
                rep, rest2 = rest.split("@")
                tick, dur = rest2.split("+")
                plan.stall(int(rep), at_tick=int(tick), ticks=int(dur))
            elif kind == "recover":
                rep, tick = rest.split("@")
                plan.recover(int(rep), at_tick=int(tick))
            elif kind == "flap":
                rep, rest2 = rest.split("@")
                tick, down = rest2.split("+")
                plan.flap(int(rep), at_tick=int(tick),
                          down_ticks=int(down))
            else:
                raise ValueError(kind)
        except ValueError:
            raise SystemExit(
                f"--fault expects 'kill:R@T', 'stall:R@T+D', "
                f"'recover:R@T', or 'flap:R@T+D' "
                f"(comma-separated), got {part!r}")
    return plan


def router():
    """The serving-tier SLO table: a seeded bursty trace load-balanced
    across ROUTER_REPLICAS replica engines (optionally with a scripted
    fault). Tick-denominated tail-latency rows, queue depth, and
    goodput-under-burst counts are deterministic — the same trace seed
    schedules identically on every host, so report.py --compare can gate
    tail latency. The _ms mirrors and tok-per-wall-second rates are wall
    clock (informational; see report.WALLCLOCK). Two extra fixed
    scenarios ride along: router_overload (deadlines + bounded queue +
    retry backoff + brown-out controller under a hot burst) and
    router_recovery (goodput and fence->recover gap under a replica
    flap)."""
    import jax

    from repro.configs.base import get_config, reduce_config
    from repro.models.registry import build_model
    from repro.serve.router import OverloadConfig, Router
    from repro.serve.trace import TraceConfig, generate_trace

    cfg = reduce_config(get_config("qwen2-1.5b"), layers=2, d_model=64,
                        vocab=128)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    # bursty heavy-tail mix sized so bursts actually overlap the run:
    # ~0.5s of calm, then a 4x-rate burst window every second
    trace = generate_trace(TraceConfig(
        n_requests=24, arrival="bursty", rate_rps=16.0, burst_factor=4.0,
        burst_every_s=1.0, burst_len_s=0.5, prompt_median=6,
        prompt_sigma=0.6, prompt_max=24, out_median=8, out_sigma=0.8,
        out_max=32, temperatures=(0.0, 0.7), vocab=128, seed=0))
    plan = _parse_fault(ROUTER_FAULT) if ROUTER_FAULT else None
    rt = Router(cfg, params, replicas=ROUTER_REPLICAS, max_batch=4,
                cache_len=64, fault_plan=plan, stale_after_ticks=3)
    out, s = rt.run(trace, tick_s=0.05)
    fault_note = f";fault={ROUTER_FAULT}" if ROUTER_FAULT else ""
    _csv("router_engine", s["wall_s"] * 1e6,
         f"replicas={s['replicas']};completed={s['completed']};"
         f"requeued={s['requeued']};ticks={s['ticks']};"
         f"decode_steps={s['decode_steps']};prefills={s['prefills']};"
         f"goodput_toks={s['goodput_toks']};wasted_toks={s['wasted_toks']};"
         f"goodput_tok_per_s={s['goodput_tok_per_s']:.1f}{fault_note}")
    _csv("router_slo_ticks", None,
         f"p50_ttft_ticks={s['p50_ttft_ticks']:.2f};"
         f"p99_ttft_ticks={s['p99_ttft_ticks']:.2f};"
         f"p50_tpot_ticks={s['p50_tpot_ticks']:.3f};"
         f"p99_tpot_ticks={s['p99_tpot_ticks']:.3f}")
    _csv("router_slo_wall", None,
         f"p50_ttft_ms={s['p50_ttft_s'] * 1e3:.1f};"
         f"p99_ttft_ms={s['p99_ttft_s'] * 1e3:.1f};"
         f"p50_tpot_ms={s['p50_tpot_s'] * 1e3:.2f};"
         f"p99_tpot_ms={s['p99_tpot_s'] * 1e3:.2f}")
    _csv("router_queue", None,
         f"mean_queue_depth={s['mean_queue_depth']:.2f};"
         f"p99_queue_depth={s['p99_queue_depth']:.2f};"
         f"max_queue_depth={s['max_queue_depth']}")
    b = s.get("burst")
    if b:
        _csv("router_burst", None,
             f"burst_ticks={b['ticks']};burst_arrivals={b['arrivals']};"
             f"burst_new_tokens={b['new_tokens']};"
             f"burst_tok_per_tick={b['tok_per_tick']:.2f}")
    for pr in s["per_replica"]:
        _csv(f"router_replica_{pr['replica']}", None,
             f"decode_steps={pr['decode_steps']};"
             f"prefills={pr['prefills']};completed={pr['completed']};"
             f"evicted={pr['evicted']};stalled_ticks={pr['stalled_ticks']};"
             f"killed={pr['killed']};fenced={pr['fenced']}")

    # --- overload scenario: a hotter burst mix with per-request deadlines
    # pushed through a bounded queue, retry backoff, and the windowed
    # brown-out controller. Every rate below is tick-denominated and
    # deterministic per seed, so report.py --compare gates them exactly
    # (docs/serving.md §Overload & recovery).
    o_trace = generate_trace(TraceConfig(
        n_requests=24, arrival="bursty", rate_rps=32.0, burst_factor=6.0,
        burst_every_s=0.5, burst_len_s=0.25, prompt_median=6,
        prompt_sigma=0.6, prompt_max=24, out_median=8, out_sigma=0.8,
        out_max=32, temperatures=(0.0, 0.7), vocab=128, seed=0,
        deadline_median=24, deadline_sigma=0.8, deadline_max=96))
    ort = Router(cfg, params, replicas=ROUTER_REPLICAS, max_batch=4,
                 cache_len=64, stale_after_ticks=3, max_queue=4,
                 retry_budget=2, retry_backoff_base=1, retry_backoff_cap=8,
                 overload=OverloadConfig(window_ticks=2, queue_high=1,
                                         queue_low=0))
    _, so = ort.run(o_trace, tick_s=0.05)
    _csv("router_overload", None,
         f"completed={so['completed']};shed={so['shed']};"
         f"deadline_missed={so['deadline_missed']};"
         f"shed_rate={so['shed_rate']:.3f};"
         f"deadline_miss_rate={so['deadline_miss_rate']:.3f};"
         f"retries_per_request={so['retries_per_request']:.3f};"
         f"brownout_ticks={so['brownout_ticks']};"
         f"goodput_toks={so['goodput_toks']};"
         f"p99_ttft_ticks={so['p99_ttft_ticks']:.2f}")

    # --- recovery scenario: the base trace under a kill->recover flap of
    # replica 1; goodput-under-flap and the fence->recover gap gate the
    # recovery path (every completed output stays bit-exact vs an
    # undisturbed single-engine run — the chaos tier asserts that).
    from repro.serve.router import FaultPlan
    r_trace = generate_trace(TraceConfig(
        n_requests=24, arrival="bursty", rate_rps=16.0, burst_factor=4.0,
        burst_every_s=1.0, burst_len_s=0.5, prompt_median=6,
        prompt_sigma=0.6, prompt_max=24, out_median=8, out_sigma=0.8,
        out_max=32, temperatures=(0.0, 0.7), vocab=128, seed=0))
    rrt = Router(cfg, params, replicas=ROUTER_REPLICAS, max_batch=4,
                 cache_len=64, stale_after_ticks=3,
                 fault_plan=FaultPlan().flap(1, at_tick=6, down_ticks=6))
    _, sr = rrt.run(r_trace, tick_s=0.05)
    _csv("router_recovery", None,
         f"completed={sr['completed']};recoveries={sr['recoveries']};"
         f"mean_recovery_ticks={sr['mean_recovery_ticks']:.2f};"
         f"requeued={sr['requeued']};wasted_toks={sr['wasted_toks']};"
         f"goodput_toks={sr['goodput_toks']};ticks={sr['ticks']};"
         f"p99_ttft_ticks={sr['p99_ttft_ticks']:.2f}")


def kvcache():
    """Paged-K/V rows: one shared-prompt workload served three ways (cold
    static cache, paged bf16, paged int8) plus the registry-routed
    paged_decode kernel. Hit rates, token counts, page accounting, and
    the kernel error bounds are deterministic per seed; only the
    us_per_call column is wall clock."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import get_config, reduce_config
    from repro.kernels import api
    from repro.models.registry import build_model
    from repro.serve.engine import ServeEngine
    from repro.serve.router import Router
    from repro.serve.trace import TraceConfig, generate_trace
    from repro.tune import tuner

    cfg = reduce_config(get_config("qwen2-1.5b"), layers=2, d_model=64,
                        vocab=128)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    # 90% of requests share one of two 16-token system prompts: the
    # workload shape the prefix index exists for (greedy sampling so the
    # paged-vs-static compare is a bit-exactness check, not a similarity)
    trace = generate_trace(TraceConfig(
        n_requests=16, rate_rps=16.0, prompt_median=6, prompt_sigma=0.6,
        prompt_max=16, out_median=6, out_sigma=0.6, out_max=16,
        temperatures=(0.0,), vocab=128, seed=0,
        shared_prefix_frac=0.9, prefix_pool=2, prefix_len=16))
    reqs = trace.plain_requests()

    base = ServeEngine(cfg, params, max_batch=4, cache_len=64)
    out_base, _ = base.run(reqs, collect_stats=True)

    eng = ServeEngine(cfg, params, max_batch=4, cache_len=64,
                      kv_page_size=8)
    out_paged, stats = eng.run(reqs, collect_stats=True)
    eng.kv.check_conservation()
    kv = stats["engine"]["kvcache"]
    _csv("kvcache_engine", stats["engine"]["wall_s"] * 1e6,
         f"page_size={kv['page_size']};"
         f"prefix_hit_rate={kv['prefix_hit_rate']:.3f};"
         f"prefill_tokens_saved={kv['prefill_tokens_saved']};"
         f"peak_live_pages={kv['peak_live_pages']};"
         f"page_occupancy={kv['page_occupancy']:.3f}")
    _csv("kvcache_bytes", None,
         f"kv_bytes_per_slot={kv['kv_bytes_per_slot']:.0f};"
         f"static_bytes_per_slot={kv['static_bytes_per_slot']};"
         f"bytes_per_slot_reduction={kv['bytes_per_slot_reduction']:.3f}")
    exact = sum(np.array_equal(out_base[r], out_paged[r]) for r in out_base)
    _csv("kvcache_parity", None,
         f"bitexact_frac={exact / len(out_base):.3f};"
         f"requests={len(out_base)};"
         f"tokens={sum(len(t) for t in out_base.values())}")

    # int8 pool: token agreement vs the bf16 baseline (informational
    # similarity — int8 is lossy by design) + the pinned attention-level
    # error of the quantized kernel route at the canonical shape
    eng8 = ServeEngine(cfg, params, max_batch=4, cache_len=64,
                       kv_page_size=8, kv_dtype="int8")
    out8, _ = eng8.run(reqs, collect_stats=True)
    agree = sum(np.array_equal(out_base[r], out8[r]) for r in out_base)
    ks = api.get_kernel("paged_decode")
    key = ks.canonical_keys()[0]
    (q, kp, vp, tbl, cl), _kw = ks.make_example(key)
    ref = api.dispatch("paged_decode", q, kp, vp, tbl, cl, version="ref")
    i8 = api.dispatch("paged_decode", q, kp, vp, tbl, cl, version="int8")
    err8 = float(jnp.max(jnp.abs(ref.astype(jnp.float32)
                                 - i8.astype(jnp.float32))))
    _csv("kvcache_int8", None,
         f"token_agree_frac={agree / len(out_base):.3f};"
         f"attn_max_abs_err={err8:.4g}")

    # the registry route: tuned pages_per_block pick (model-ranked, same
    # determinism rationale as gpp_tuner) + gather-vs-oracle error
    tc = tuner.tune_kernel("paged_decode", key, use_cache=False,
                           measure_mode=False)
    gat = api.dispatch("paged_decode", q, kp, vp, tbl, cl,
                       version="gather", config=tc.config)
    errg = float(jnp.max(jnp.abs(ref.astype(jnp.float32)
                                 - gat.astype(jnp.float32))))
    _csv("kvcache_kernel", None,
         f"pages_per_block={tc.config.pages_per_block};"
         f"modeled_s={tc.modeled_s:.4g};gather_max_abs_err={errg:.4g};"
         f"source={tc.source}",
         kernel_config={"kernel": "paged_decode",
                        "version": tc.key.split("|")[-1],
                        "config": dataclasses.asdict(tc.config),
                        "source": tc.source})

    # fleet view: replica-local pools/indexes — the shared prompt
    # prefills once PER REPLICA, so the fleet hit rate sits below a
    # single engine's on the same trace (docs/serving.md §Paged K/V)
    rt = Router(cfg, params, replicas=2, max_batch=4, cache_len=64,
                kv_page_size=8)
    _, rs = rt.run(trace, tick_s=0.05)
    rkv = rs["kvcache"]
    _csv("kvcache_router", None,
         f"replicas=2;prefix_hit_rate={rkv['prefix_hit_rate']:.3f};"
         f"prefill_tokens_saved={rkv['prefill_tokens_saved']};"
         f"pages_allocated={rkv['pages_allocated']};"
         f"pages_freed={rkv['pages_freed']}")


def spec():
    """Speculative-decoding rows: one greedy trace served plain and with
    a layer-sliced draft (the target's own first layer — the zero-train
    draft that works because the residual stream is embedding-dominated).
    Acceptance, accounting, and parity rows are deterministic per seed;
    the tok/s rows are wall clock. The size is the smallest where the
    verify's shared weight traffic beats per-step dispatch overhead, so
    the speedup is a real effect, not noise."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import get_config, reduce_config
    from repro.kernels import api
    from repro.models.registry import build_model
    from repro.serve.engine import ServeEngine
    from repro.serve.trace import TraceConfig, generate_trace
    from repro.tune import tuner

    spec_k = 3
    cfg = reduce_config(get_config("qwen2-1.5b"), layers=6, d_model=384,
                        vocab=256)
    params = build_model(cfg).init_params(jax.random.PRNGKey(0))
    dcfg = dataclasses.replace(cfg, n_layers=1)
    dparams = dict(params)
    dparams["layers"] = jax.tree_util.tree_map(lambda x: x[:1],
                                               params["layers"])
    trace = generate_trace(TraceConfig(
        n_requests=12, rate_rps=16.0, prompt_median=6, prompt_sigma=0.6,
        prompt_max=16, out_median=8, out_sigma=0.5, out_max=12,
        temperatures=(0.0,), vocab=256, seed=0))
    reqs = trace.plain_requests()

    plain = ServeEngine(cfg, params, max_batch=4, cache_len=64)
    seng = ServeEngine(cfg, params, max_batch=4, cache_len=64,
                       draft_cfg=dcfg, draft_params=dparams, spec_k=spec_k)
    # first run jits; best-of-2 timed reps after
    out_plain, out_spec = plain.run(list(reqs)), seng.run(list(reqs))
    walls = {}
    for name, eng in (("plain", plain), ("spec", seng)):
        best = None
        for _ in range(2):
            t0 = time.perf_counter()
            out = eng.run(list(reqs))
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        walls[name] = best
    toks = sum(len(v) for v in out_plain.values())

    sp = seng.last_stats["spec"]
    _csv("spec_engine", walls["spec"] * 1e6,
         f"k={sp['k']};acceptance_rate={sp['acceptance_rate']:.3f};"
         f"accepted_tokens_per_step={sp['accepted_tokens_per_step']:.3f};"
         f"tokens_emitted={sp['tokens_emitted']};"
         f"verify_steps={sp['verify_steps']};"
         f"draft_steps={sp['draft_steps']}")
    exact = sum(np.array_equal(out_plain[r], out_spec[r]) for r in out_plain)
    _csv("spec_parity", None,
         f"bitexact_frac={exact / len(out_plain):.3f};"
         f"requests={len(out_plain)};tokens={toks}")
    # effective throughput: tokens per wall second, both routes — the
    # tok_per_s fields are wall clock (gate-excluded); the speedup ratio
    # is the headline the baseline artifact records
    _csv("spec_throughput", None,
         f"plain_tok_per_s={toks / walls['plain']:.1f};"
         f"effective_tok_per_s={toks / walls['spec']:.1f};"
         f"wall_speedup={walls['plain'] / walls['spec']:.3f}")

    # the multi-query kernel route: tuned pages_per_block for the verify
    # version at the qlen>1 canonical shape + its error vs the ref oracle
    ks = api.get_kernel("paged_decode")
    key = next(k for k in ks.canonical_keys() if k.qlen > 1)
    (q, kp, vp, tbl, cl), _kw = ks.make_example(key)
    tc = tuner.tune_kernel("paged_decode", key, version="verify",
                           use_cache=False, measure_mode=False)
    ref = api.dispatch("paged_decode", q, kp, vp, tbl, cl, version="ref")
    ver = api.dispatch("paged_decode", q, kp, vp, tbl, cl,
                       version="verify", config=tc.config)
    errv = float(jnp.max(jnp.abs(ref.astype(jnp.float32)
                                 - ver.astype(jnp.float32))))
    _csv("spec_kernel", None,
         f"qlen={key.qlen};pages_per_block={tc.config.pages_per_block};"
         f"modeled_s={tc.modeled_s:.4g};verify_max_abs_err={errv:.4g};"
         f"source={tc.source}",
         kernel_config={"kernel": "paged_decode",
                        "version": "verify",
                        "config": dataclasses.asdict(tc.config),
                        "source": tc.source})


TABLES = {
    "gpp_journey": table1_gpp_journey,
    "roofline_terms": fig_roofline_terms,
    "fig8_locality": fig8_locality,
    "v8_block_sweep": v8_block_sweep,
    "gpp_tuner": gpp_tuner,
    "kernel_tuner": kernel_tuner,
    "model_cells": model_cells,
    "train_step_cpu": train_step_cpu,
    "serve": serve,
    "router": router,
    "kvcache": kvcache,
    "spec": spec,
}

# the cheap, deterministic-model subset CI benchmarks and the committed
# baseline artifact are built from (no multi-minute train-step jits)
FAST_TABLES = ("gpp_journey", "roofline_terms", "fig8_locality",
               "v8_block_sweep", "gpp_tuner", "kernel_tuner")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default=None,
                    help="comma-separated table names (or 'fast' for the "
                         f"CI subset: {','.join(FAST_TABLES)})")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the rows as a BENCH_*.json artifact "
                         "(schema: benchmarks/report.py)")
    ap.add_argument("--serve", action="store_true",
                    help="shortcut for --only serve (slot-scheduler stats)")
    ap.add_argument("--mesh", default=None, metavar="tp=N",
                    help="with --serve: run the engine tensor-parallel "
                         "over an N-way model axis (forces N host devices "
                         "when jax is not yet initialized)")
    ap.add_argument("--router", action="store_true",
                    help="shortcut for --only router (multi-replica DP "
                         "router SLO rows)")
    ap.add_argument("--kvcache", action="store_true",
                    help="add the kvcache table (paged K/V cache rows; "
                         "alone it runs just that table, with --serve it "
                         "rides along)")
    ap.add_argument("--spec", action="store_true",
                    help="add the spec table (speculative decoding rows; "
                         "alone it runs just that table, composable with "
                         "--serve/--kvcache)")
    ap.add_argument("--replicas", type=int, default=2, metavar="N",
                    help="with --router: number of replica engines "
                         "(default 2)")
    ap.add_argument("--fault", default=None, metavar="SPEC",
                    help="with --router: scripted fault(s), "
                         "'kill:R@T' or 'stall:R@T+D' (comma-separated)")
    args = ap.parse_args()
    if args.router:
        todo = ["router"]
    elif args.serve:
        todo = ["serve"]
    elif args.only is None:
        if args.kvcache or args.spec:
            todo = (["kvcache"] if args.kvcache else []) \
                + (["spec"] if args.spec else [])
        else:
            todo = list(TABLES)
    elif args.only == "fast":
        todo = list(FAST_TABLES)
    else:
        todo = args.only.split(",")
        unknown = [t for t in todo if t not in TABLES]
        if unknown:
            ap.error(f"unknown tables {unknown}; choose from {list(TABLES)}")
    if args.mesh:
        if todo != ["serve"]:
            # a forced host device count would silently skew every other
            # table's wall-clock rows while the mesh itself went unused
            ap.error("--mesh only applies to the serve table "
                     "(use --serve or --only serve)")
        import sys as _sys
        tp = _parse_mesh(args.mesh)
        if "jax" not in _sys.modules:
            # must land before the first jax import; harmless off-CPU
            # (the flag only affects the host platform)
            flags = os.environ.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (
                    f"{flags} --xla_force_host_platform_device_count={tp}"
                ).strip()
        global SERVE_MESH
        SERVE_MESH = tp
    if args.replicas != 2 or args.fault:
        if "router" not in todo:
            ap.error("--replicas/--fault only apply to the router table "
                     "(use --router or --only router)")
    if args.replicas < 1:
        ap.error("--replicas must be >= 1")
    global ROUTER_REPLICAS, ROUTER_FAULT
    ROUTER_REPLICAS = args.replicas
    if args.fault:
        _parse_fault(args.fault)        # validate up front: SystemExit here
        ROUTER_FAULT = args.fault       # beats a traceback mid-table
    if args.kvcache and "kvcache" not in todo:
        todo.append("kvcache")
    if args.spec and "spec" not in todo:
        todo.append("spec")
    from repro import backend
    backend.enable_compile_cache()      # after any forced-device XLA_FLAGS
    print("name,us_per_call,derived")
    for name in todo:
        TABLES[name]()
    if args.json:
        from benchmarks import report
        report.write_artifact(RESULTS, args.json, tables=todo)
        print(f"# wrote {args.json} ({len(RESULTS)} rows)")


if __name__ == '__main__':
    main()
