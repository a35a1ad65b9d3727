"""The paper's kernel, end to end: run the GPP optimization journey
(v0 -> v8 per the paper, then the beyond-paper v9/v10 steps) with
correctness checks against the complex128 oracle, CPU wall-clock at BENCH
size, and the modeled TPU-v5e roofline trajectory — the Table-I
reproduction (EXPERIMENTS.md §Perf/GPP).

    PYTHONPATH=src python examples/gpp_science.py [--size si510] [--sweep]
"""

import argparse

from repro import backend
from repro.core.journey import format_journey, run_journey, sweep_blocks


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", default="si214", choices=("si214", "si510"))
    ap.add_argument("--sweep", action="store_true",
                    help="print the v8 block-size tuning sweep")
    ap.add_argument("--tune", action="store_true",
                    help="print the repro.tune autotuner ranking")
    ap.add_argument("--no-cpu", action="store_true",
                    help="skip CPU wall-clock measurements")
    args = ap.parse_args()
    backend.enable_compile_cache()

    rows = run_journey(args.size, measure_cpu=not args.no_cpu)
    print()
    print(format_journey(rows, args.size))

    v0 = rows[0]
    v8 = next(r for r in rows if r.version == "v8")
    vbest = rows[-1]
    speedup = v0.report.modeled_step_s / v8.report.modeled_step_s
    print(f"\nmodeled v8/v0 speedup: {speedup:.2f}x "
          f"(paper measured 2.36x Si-214, 3.27x Si-510); "
          f"v10/v0: {v0.report.modeled_step_s / vbest.report.modeled_step_s:.2f}x")

    if args.sweep:
        print("\nv8 block sweep (top 10):")
        for r in sweep_blocks(args.size)[:10]:
            print(f"  blk=({r['blk_ig']},{r['blk_igp']},{r['blk_band']}) "
                  f"modeled={r['modeled_s']*1e3:.1f}ms "
                  f"tflops={r['tflops']:.2f} vmem={r['vmem_mib']:.1f}MiB")

    if args.tune:
        from repro.kernels.gpp.problem import SIZES
        from repro.tune import tuner
        print("\nautotuner ranking (top 10, model):")
        for cfg, t in tuner.rank(SIZES[args.size])[:10]:
            print(f"  blk=({cfg.blk_ig},{cfg.blk_igp},{cfg.blk_band}) "
                  f"modeled={t*1e3:.1f}ms")


if __name__ == "__main__":
    main()
