"""Serve a small model with batched requests (deliverable-b serving path):
slot-level continuous batching — finished slots refill from the queue
mid-flight — with greedy + temperature sampling and per-request
latency/throughput stats.

    PYTHONPATH=src python examples/serve_batched.py --requests 8 --batch 4

Tensor-parallel over N forced host devices (docs/serving.md §Sharded
serving; outputs are bit-exact vs --tp 1):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        PYTHONPATH=src python examples/serve_batched.py --tp 4
"""

import argparse
import time

import jax
import numpy as np

from repro import backend
from repro.configs.base import get_config, reduce_config
from repro.launch.mesh import make_mesh
from repro.models.registry import build_model
from repro.serve.engine import Request, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--tp", type=int, default=1,
                    help="serve tensor-parallel over a tp-way model axis "
                         "(needs >= tp jax devices)")
    args = ap.parse_args()
    backend.enable_compile_cache()

    cfg = reduce_config(get_config("phi4-mini-3.8b"), layers=4, d_model=256,
                        vocab=1024)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    mesh = (make_mesh((args.tp,), ("model",)) if args.tp > 1 else None)
    engine = ServeEngine(cfg, params, max_batch=args.batch, mesh=mesh)

    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, 8 + i % 5),
                    max_new_tokens=args.new_tokens,
                    temperature=args.temperature)
            for i in range(args.requests)]

    t0 = time.perf_counter()
    out, stats = engine.run(reqs, collect_stats=True)
    dt = time.perf_counter() - t0
    e = stats["engine"]
    total = sum(len(v) for v in out.values())
    tp = f", tp={args.tp}" if args.tp > 1 else ""
    print(f"served {len(reqs)} requests / {total} tokens "
          f"in {dt:.2f}s ({total/dt:.1f} tok/s, batch={args.batch}{tp})")
    for d in e.get("per_device", []):
        print(f"  device {d['device']}: params "
              f"{d['params_bytes']/2**20:.2f} MiB, cache "
              f"{d['cache_bytes']/2**20:.2f} MiB")
    print(f"  decode_steps={e['decode_steps']} prefills={e['prefills']} "
          f"occupancy={e['occupancy']:.2f} "
          f"mean_ttft={e['mean_ttft_s']*1e3:.0f}ms "
          f"mean_queue_wait={e['mean_queue_wait_s']*1e3:.0f}ms")
    for rid in sorted(out)[:4]:
        st = stats["requests"][rid]
        print(f"  req {rid}: {out[rid][:10]}{'...' if len(out[rid])>10 else ''}"
              f"  (ttft {st.ttft_s*1e3:.0f}ms, {st.tok_per_s:.1f} tok/s)")


if __name__ == "__main__":
    main()
