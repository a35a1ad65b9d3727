"""Readings that set the limits of the correctness check, on the chip:
for each seed, a short window of the cell at its own load, then the
program's number and the control's number (the reference computed one
precision below the configuration's) on the same sample.

    python benchmarks/chip/control.py --workload <cell> --seconds 10 \
        --seeds 1 2 3 ...

Prints one JSON line per seed; one process serves every seed, so the
programs compile once.
"""

import argparse
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.chip import common, traffic  # noqa: E402


def readings(bench, workload, seeds, seconds, require_tpu=True,
             root=common.ROOT):
    bench_dir = os.path.join(root, os.path.relpath(common.HERE, common.ROOT))
    cell, entry = common.find_cell(bench, workload)
    devices = common.devices_for(int(cell["chips"]), require_tpu=require_tpu)
    cfg = common.load_json(root, entry["file"])
    mix = traffic.load_mix(cell["traffic"], bench_dir)
    common.enable_compile_cache()
    clock = common.CompileClock()
    drv_mod = importlib.import_module(
        f"benchmarks.chip.drivers.{cfg['driver']}")
    for seed in seeds:
        t = time.perf_counter()
        drv = drv_mod.Driver(cell, cfg, mix, seed, seconds, devices, clock,
                             bench_dir)
        drv.setup()
        drv.run_window(False)
        correct, checks = drv.verify(control=True)
        yield {"seed": seed, "correct": correct, "failed": drv.failed,
               "checks": checks, "seconds": time.perf_counter() - t}
        common.free_device_memory()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    try:
        for row in readings(bench, args.workload, args.seeds, args.seconds):
            print(json.dumps(row), flush=True)
    except common.NoChip as e:
        common.log(f"control: {e}")
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
