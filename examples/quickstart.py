"""Quickstart: the public `repro` surface end to end — dispatch a kernel
through the unified registry, train a tiny qwen2-family model for a few
steps on CPU, and sample from it. Runs in ~1 minute.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

import repro
from repro import backend
from repro.configs.base import get_config, reduce_config
from repro.launch.mesh import make_mesh
from repro.train.trainer import TrainLoopConfig, Trainer


def kernel_demo():
    """The registry is the one entry point for every kernel family: list
    it, then dispatch the paper's GPP kernel at TINY size — version=None
    runs the default (autotuned v10, config from the repro.tune cache)."""
    from repro.kernels.gpp import problem
    print(f"registered kernels: {repro.list_kernels()}")
    inputs = problem.make_inputs(problem.TINY)
    ach, asx = repro.dispatch("gpp", inputs)
    print(f"gpp@tiny achtemp[0] = {complex(np.asarray(ach)[0]):.4f}")
    gpp = repro.get_kernel("gpp")
    print(f"gpp versions: {gpp.versions[0]}..{gpp.versions[-1]} "
          f"(default {gpp.default_version}, tunable {gpp.tunable})")


def main():
    backend.enable_compile_cache()
    kernel_demo()

    cfg = reduce_config(get_config("qwen2-1.5b"), layers=2, d_model=128,
                        vocab=256)
    loop = TrainLoopConfig(total_steps=20, ckpt_every=10, log_every=5,
                           ckpt_dir="runs/quickstart_ckpt", seq_len=64,
                           global_batch=4, peak_lr=1e-3)
    mesh = make_mesh((1, 1), ("data", "model"))
    trainer = Trainer(cfg, loop, mesh)
    out = trainer.run()
    if out["final_loss"] is None:
        print(f"checkpoint already at step {out['start_step']}; skipping train")
    else:
        print(f"final loss: {out['final_loss']:.4f} "
              f"(stragglers flagged: {out['stragglers']})")

    # restore the checkpoint and serve a couple of batched requests
    step, state = trainer.ckpt.restore()
    print(f"restored step {step}")
    engine = repro.ServeEngine(cfg, state["params"], max_batch=2)
    reqs = [repro.Request(rid=i, prompt=np.arange(5 + i) % 256,
                          max_new_tokens=8)
            for i in range(3)]
    for rid, toks in engine.run(reqs).items():
        print(f"request {rid}: {toks}")


if __name__ == "__main__":
    main()
