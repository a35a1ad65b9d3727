"""Chip smoke: the quickest proof that the system still starts on a TPU.

    python chip_smoke.py              # one chip: phases A, B and C
    python chip_smoke.py --chips 4    # four chips: tensor-parallel serving

Phase A serves qwen2-1.5b at its published widths (28 layers, d_model
1536, 12/2 heads, d_ff 8960, vocab 151936; random bf16 weights made on the
device from a seed) through ServeEngine: 8 greedy requests with 100-500
token prompts and 32 new tokens each, 4 slots, so slots refill mid-flight.
It checks that every request got its 32 tokens and that request 0 served
alone yields the tokens it got inside the batch.

Phase B runs the paper's kernel, gpp v10, compiled through repro.dispatch:
at BENCH size against the complex128 oracle, and at si214 against the
pure-JAX v5 variant on the same chip (the bound is explained at
GPP_SI214_RTOL).

Phase C compiles the model kernels at model widths and checks them against
their references: flash attention at qwen2-1.5b prefill shapes and the
selective scan at hymba-1.5b widths.

--chips 4 runs only the tensor-parallel path: codeqwen1.5-7b (8.19 B
parameters, more than one chip holds) served on a 4-way `model` mesh, with
its parameters created sharded, against the same engine on a 2-way mesh
over two of the chips. Exact-TP promises identical greedy tokens.

Every number printed before the last line is a smoke fact, not a
benchmark. The last line of stdout is one JSON object naming the device;
without a TPU, or when any check fails, the script exits non-zero and
prints no such line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 0
N_REQUESTS = 8
NEW_TOKENS = 32
SERVE_ARCH = "qwen2-1.5b"
TP_ARCH = "codeqwen1.5-7b"
GPP_SIZE = "si214"
# qwen2-1.5b prefill: (batch, seq, heads, kv heads, head_dim)
FLASH_SHAPE = (1, 2048, 12, 2, 128)
# hymba-1.5b selective scan: (batch, T, channels = d_model, ssm_state)
SSM_SHAPE = (1, 1024, 1600, 16)

# gpp v10 (blocked Pallas, f32) against v5 (pure JAX, f32) at si214: both
# sum 2 * 8192 * 1024 * 1024 complex terms per output, in different orders.
# The terms are products of zero-mean random amplitudes, so the outputs
# are random-walk sums, |S| ~ sqrt(N) * rms(term). Two effects separate
# the versions:
#  * association order: blocked/tree sums plus <= 1024 sequential band
#    steps, ~ u * sqrt(1024) ~ 2e-6 relative (u = 6e-8, f32);
#  * branch flips: a 1-ulp difference in wdiffr or delwr (XLA and Mosaic
#    may round a division differently) moves an element across a branch
#    threshold. About N * 1e-7 elements (~2e3) flip, each changing the sum
#    by ~ one term, adding as a random walk: ~ sqrt(2e3) / sqrt(N) ~ 3e-4.
# The bound takes 3x headroom on the larger effect. The oracle check at
# BENCH size holds the kernel to the test suite's 1e-4.
GPP_SI214_RTOL = 1e-3
GPP_BENCH_RTOL = 1e-4


def fact(name: str, value) -> None:
    print(f"smoke: {name} = {value}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)
    print(f"check: {what}: ok", flush=True)


class CompileClock:
    """Seconds JAX spends in backend compilation, from its own events."""

    def __init__(self):
        import jax
        self.total = 0.0

        def listen(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.total += duration

        jax.monitoring.register_event_duration_secs_listener(listen)


def _peak_bytes(dev):
    """Peak device memory in use so far (None where the backend does not
    report it, as the CPU backend does not)."""
    return (dev.memory_stats() or {}).get("peak_bytes_in_use")


def _prompts(vocab: int, lo: int, hi: int):
    import numpy as np
    rng = np.random.default_rng(SEED)
    lens = rng.integers(lo, hi + 1, N_REQUESTS)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lens]


def _requests(prompts, rids):
    from repro.serve.engine import Request
    return [Request(rid=i, prompt=prompts[i], max_new_tokens=NEW_TOKENS)
            for i in rids]


def phase_serve(dev, clock: CompileClock) -> None:
    """A: qwen2-1.5b at full width through ServeEngine on one chip."""
    import jax
    from repro.configs.base import get_config
    from repro.models.registry import build_model
    from repro.serve.engine import ServeEngine

    cfg = get_config(SERVE_ARCH)
    model = build_model(cfg)
    params = jax.jit(model.init_params)(jax.random.PRNGKey(SEED))
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    fact("A.params", n_params)
    prompts = _prompts(cfg.vocab_size, 100, 500)
    fact("A.prompt_lens", [len(p) for p in prompts])

    eng = ServeEngine(cfg, params, max_batch=4, cache_len=2048)
    c0, t0 = clock.total, time.perf_counter()
    out = eng.run(_requests(prompts, range(N_REQUESTS)))
    fact("A.batched_run_s", time.perf_counter() - t0)
    fact("A.compile_s", clock.total - c0)
    fact("A.tokens_served", sum(len(v) for v in out.values()))
    fact("A.decode_steps", eng.last_stats["decode_steps"])
    solo = eng.run(_requests(prompts, [0]))
    fact("A.peak_bytes_in_use", _peak_bytes(dev))
    check(sorted(out) == list(range(N_REQUESTS))
          and all(len(out[i]) == NEW_TOKENS for i in out),
          f"all {N_REQUESTS} requests completed with {NEW_TOKENS} tokens")
    check(solo[0] == out[0], "request 0 alone == request 0 in the batch")


def _rel(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def phase_gpp(clock: CompileClock) -> None:
    """B: gpp v10 compiled through repro.dispatch; v5 on the same chip."""
    import jax
    import numpy as np
    import repro
    from repro.kernels.gpp import problem, ref

    inp = problem.make_inputs(problem.BENCH, seed=SEED)
    ar, xr = ref.ref_numpy(inp)
    a, x = repro.dispatch("gpp", inp, version="v10")
    err = max(_rel(a, ar), _rel(x, xr))
    fact("B.bench_rel_err_vs_oracle", err)
    check(err <= GPP_BENCH_RTOL,
          f"gpp v10 at bench within {GPP_BENCH_RTOL} of the oracle")

    size = problem.SIZES[GPP_SIZE]
    inp = jax.device_put({k: v.astype(np.float32) for k, v in
                          problem.make_inputs(size, seed=SEED).items()})
    out = {}
    for v in ("v10", "v5"):
        c0, t0 = clock.total, time.perf_counter()
        jax.block_until_ready(repro.dispatch("gpp", inp, version=v))
        fact(f"B.{v}_first_call_s", time.perf_counter() - t0)
        fact(f"B.{v}_compile_s", clock.total - c0)
        t0 = time.perf_counter()
        out[v] = jax.block_until_ready(repro.dispatch("gpp", inp,
                                                      version=v))
        fact(f"B.{v}_call_s", time.perf_counter() - t0)
    from repro.tune import tuner
    fact("B.v10_config", tuner.tune(size).config)
    err = max(_rel(out["v10"][0], out["v5"][0]),
              _rel(out["v10"][1], out["v5"][1]))
    fact(f"B.{size.name}_rel_diff_v10_v5", err)
    check(err <= GPP_SI214_RTOL,
          f"gpp v10 at {size.name} within {GPP_SI214_RTOL} of v5")


def phase_kernels() -> None:
    """C: flash at qwen2-1.5b prefill shapes, ssm at hymba-1.5b widths,
    compiled, against their references."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import repro
    from repro.kernels.ssm.kernel_def import SsmKey

    ks = jax.random.split(jax.random.PRNGKey(SEED), 3)
    b, s, h, kvh, hd = FLASH_SHAPE
    q = jax.random.normal(ks[0], (b, s, h, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, s, kvh, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, s, kvh, hd), jnp.bfloat16)
    got = np.asarray(repro.dispatch("flash", q, k, v), np.float32)
    want = np.asarray(repro.dispatch("flash", q, k, v, version="ref"),
                      np.float32)
    fact("C.flash_max_abs_err", float(np.max(np.abs(got - want))))
    check(np.allclose(got, want, atol=0.03, rtol=0.05),
          "flash at qwen2-1.5b prefill shapes matches the reference")

    key = SsmKey(*SSM_SHAPE)
    args, _ = repro.get_kernel("ssm").make_example(key, seed=SEED)
    y, h = repro.dispatch("ssm", *args)
    y_ref, h_ref = repro.dispatch("ssm", *args, version="ref")
    from repro.tune import tuner
    fact("C.ssm_blk_c", tuner.tune_kernel("ssm", key).config.blk_c)
    fact("C.ssm_max_abs_err", float(np.max(np.abs(np.asarray(y - y_ref)))))
    check(np.allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4, rtol=1e-4)
          and np.allclose(np.asarray(h), np.asarray(h_ref), atol=1e-4,
                          rtol=1e-4),
          "ssm at hymba-1.5b widths matches the sequential reference")


def phase_tp(clock: CompileClock) -> None:
    """--chips 4: codeqwen1.5-7b tensor-parallel over 4 chips vs 2."""
    import jax
    from repro.configs.base import get_config
    from repro.dist.sharding import serve_specs
    from repro.launch.mesh import make_mesh
    from repro.models.registry import build_model
    from repro.serve.engine import ServeEngine

    cfg = get_config(TP_ARCH)
    model = build_model(cfg)
    fact("TP.param_count", cfg.param_count())
    # prompts in one prefill bucket (128): one prefill compile per mesh
    prompts = _prompts(cfg.vocab_size, 100, 128)
    cache_len, outs = 512, {}
    for tp in (4, 2):
        mesh = make_mesh((tp,), ("model",), devices=jax.devices()[:tp])
        specs = serve_specs(cfg, mesh, max_batch=4, cache_len=cache_len,
                            model=model)
        params = jax.jit(model.init_params, out_shardings=specs.params)(
            jax.random.PRNGKey(SEED))
        eng = ServeEngine(cfg, params, max_batch=4, cache_len=cache_len,
                          mesh=mesh)
        c0, t0 = clock.total, time.perf_counter()
        outs[tp] = eng.run(_requests(prompts, range(N_REQUESTS)))
        fact(f"TP{tp}.run_s", time.perf_counter() - t0)
        fact(f"TP{tp}.compile_s", clock.total - c0)
        fact(f"TP{tp}.params_bytes_per_device",
             [d["params_bytes"] for d in eng.device_stats()])
        fact(f"TP{tp}.cache_bytes_per_device",
             eng.device_stats()[0]["cache_bytes"])
        fact(f"TP{tp}.peak_bytes_in_use", [_peak_bytes(d)
                                           for d in mesh.devices.flat])
        check(all(len(outs[tp][i]) == NEW_TOKENS for i in outs[tp]),
              f"TP{tp}: all {N_REQUESTS} requests got {NEW_TOKENS} tokens")
        # the engine's jitted closures keep it alive past `del`: free its
        # weights and cache explicitly, or TP2 finds TP4's bytes in HBM
        del eng, params
        for a in jax.live_arrays():
            a.delete()
    same = sum(outs[4][i] == outs[2][i] for i in outs[4])
    fact("TP.requests_identical_tp4_tp2", f"{same}/{N_REQUESTS}")
    check(same == N_REQUESTS, "TP4 greedy tokens == TP2 greedy tokens")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the tensor-parallel path")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro import backend
    except ImportError as e:
        print(f"chip_smoke: the repro package is not under {ROOT}/src: {e}",
              file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices; JAX found {len(devices)}", file=sys.stderr)
        return 1
    fact("cache_dir", backend.enable_compile_cache())
    fact("device_kind", dev.device_kind)
    fact("device_count", len(devices))
    clock = CompileClock()
    if args.chips == 4:
        phase_tp(clock)
    else:
        phase_serve(dev, clock)
        phase_gpp(clock)
        phase_kernels()
    fact("compile_s_total", clock.total)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
