"""End-to-end LM training driver.

Presets:
    --preset 100m   12L/768d qwen2-family ~100M params (the deliverable-b
                    scale; a few hundred steps ~ 1-2 h on this CPU host)
    --preset 20m    8L/384d  (~15 min for 200 steps on CPU)
    --preset smoke  2L/128d  (~1 min, CI)

Demonstrates the full production path: config -> model -> sharded train
step (mesh via flags) -> prefetching data pipeline -> checkpoint/auto-resume
(kill it and rerun: it continues) -> straggler watchdog.

    PYTHONPATH=src python examples/train_lm.py --preset 20m --steps 200
"""

import argparse

from repro import backend
from repro.configs.base import ModelConfig
from repro.launch.mesh import make_mesh
from repro.train.trainer import TrainLoopConfig, Trainer

PRESETS = {
    "100m": dict(n_layers=12, d_model=768, n_heads=12, n_kv_heads=4,
                 d_ff=2048, vocab_size=32768, seq_len=256, batch=4),
    "20m": dict(n_layers=8, d_model=384, n_heads=6, n_kv_heads=2,
                d_ff=1024, vocab_size=16384, seq_len=256, batch=4),
    "smoke": dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
                  d_ff=384, vocab_size=1024, seq_len=64, batch=4),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="20m", choices=list(PRESETS))
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data", type=int, default=1, help="data mesh axis")
    ap.add_argument("--model", type=int, default=1, help="model mesh axis")
    ap.add_argument("--token-file", default=None,
                    help="flat binary token file (default: synthetic)")
    args = ap.parse_args()
    backend.enable_compile_cache()

    p = PRESETS[args.preset]
    cfg = ModelConfig(
        name=f"train-lm-{args.preset}", family="dense",
        n_layers=p["n_layers"], d_model=p["d_model"], n_heads=p["n_heads"],
        n_kv_heads=p["n_kv_heads"], d_ff=p["d_ff"],
        vocab_size=p["vocab_size"], tie_embeddings=True, remat="none")
    print(f"model: {cfg.param_count()/1e6:.1f}M params")

    loop = TrainLoopConfig(
        total_steps=args.steps, ckpt_every=args.ckpt_every, log_every=10,
        ckpt_dir=args.ckpt_dir or f"runs/train_lm_{args.preset}",
        seq_len=p["seq_len"], global_batch=p["batch"], peak_lr=args.lr,
        token_file=args.token_file)
    mesh = make_mesh((args.data, args.model), ("data", "model"))
    out = Trainer(cfg, loop, mesh).run()
    if out["final_loss"] is None:
        print(f"nothing to do: checkpoint already at step {out['start_step']}")
    else:
        print(f"done. final loss {out['final_loss']:.4f} "
              f"over {len(out['losses'])} steps this run")


if __name__ == "__main__":
    main()
