"""Find the knee of an open-loop serving cell once, on the chip: the
cell's traffic at a list of rates, each for --seconds, from one set-up.
Prints one JSON line per rate: tokens/s, TTFT and ITL tails, the queue
left when the window closed and how long the drain took (a backlog that
grows through the window means the rate is above what the system
sustains).

    python benchmarks/chip/sweep.py --workload <cell> --seconds 20 \
        --rates 4 5 6 7 8
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.chip import common, traffic  # noqa: E402
from benchmarks.chip.drivers import serve  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    cell, entry = common.find_cell(bench, args.workload)
    try:
        devices = common.devices_for(int(cell["chips"]))
    except common.NoChip as e:
        common.log(f"sweep: {e}")
        return 3
    cfg = common.load_json(common.ROOT, entry["file"])
    mix = traffic.load_mix(cell["traffic"])
    common.enable_compile_cache()
    clock = common.CompileClock()
    drv = serve.Driver(cell, cfg, mix, args.seed, args.seconds, devices,
                       clock)
    scheds = {r: traffic.schedule({**mix, "rate_per_s": r}, args.seed,
                                  args.seconds, int(cfg["vocab_size"]))
              for r in args.rates}
    drv.schedule = scheds[args.rates[0]]
    t = time.perf_counter()
    drv.setup()
    drv._warm_up({len(x.prompt) for s in scheds.values() for x in s})
    common.log(f"setup {time.perf_counter() - t:.1f}s")
    for r in args.rates:
        drv.schedule = scheds[r]
        drv.run_window(False)
        print(json.dumps({"rate_per_s": r, "queue_at_end": drv.queue_at_end,
                          "failed": drv.failed, **drv.notes()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
