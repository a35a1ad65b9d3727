"""Run one benchmark cell once on the chip and print its result.

    python benchmarks/chip/run_cell.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything is found by name from BENCHMARK.json at the root of the
checkout: the cell names its configuration (`configs/<config>.json`,
whose `driver` names `drivers/<driver>.py`) and its traffic mix
(`traffic/<mix>.json`); each per-layer metric is read by
`metrics/<metric>.py`; the limits of the correctness check are in
`checks/<cell>.json`.

Set-up (weights or inputs made on the device from the seed, every shape of
the window compiled) is timed as `setup_s`, from process start to the
start of the window. Then the window runs for --seconds; with --trace 1 a
stretch of it is traced and the per-layer metrics are reported instead of
the end-to-end ones. After the window, peak memory is read, the program's
state is freed and the plain reference decides `correct`.

The last line of standard output is one JSON object; the numbers compared
are also printed, each beside its limit, as the last lines of standard
error. Without a TPU, or with fewer chips than the cell asks for, the run
prints no result and exits 3.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.chip import common, traffic  # noqa: E402

NO_CHIP_EXIT = 3


class Context:
    """What a per-layer reader gets: the cell, its configuration, the
    chip's peaks, the reduced trace and the driver's counters."""

    def __init__(self, cell, cfg, chips, peaks, trace, counters):
        self.cell, self.cfg, self.chips = cell, cfg, chips
        self.peaks, self.trace, self.counters = peaks, trace, counters


def load_reader(name: str, root: str = common.HERE):
    path = os.path.join(root, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_of(bench, cell_name: str, kind: str):
    """The cell's end-to-end or per-layer metric entries."""
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", [cell_name])]


def run(bench, workload: str, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, t_start: float = T_START,
        root: str = common.ROOT, keep_trace: str = ""):
    """One run of one cell; returns (result dict, checks dict). `root`
    is the checkout whose benchmark directory holds the cell's files."""
    bench_dir = os.path.join(root, os.path.relpath(common.HERE, common.ROOT))
    cell, entry = common.find_cell(bench, workload)
    chips = int(cell["chips"])
    devices = common.devices_for(chips, require_tpu=require_tpu)
    cfg = common.load_json(root, entry["file"])
    mix = traffic.load_mix(cell["traffic"], bench_dir)
    common.enable_compile_cache()
    clock = common.CompileClock()
    drv_mod = importlib.import_module(
        f"benchmarks.chip.drivers.{cfg['driver']}")
    drv = drv_mod.Driver(cell, cfg, mix, seed, seconds, devices, clock,
                         bench_dir)
    drv.setup()
    setup_s = time.perf_counter() - t_start
    common.log(f"setup_s {setup_s:.3f} compiles {clock.count} "
               f"compile_s {clock.seconds:.3f}")
    drv.run_window(trace)
    peak = common.peak_bytes(devices)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    from benchmarks.chip import peaks as peaks_mod
    from benchmarks.chip import trace_reduce
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]
             + bench["per_layer"]}
    metrics, breakdown = {}, None
    if trace:
        if keep_trace:
            import gzip
            with gzip.open(keep_trace, "wt") as fh:
                json.dump(drv.trace_planes, fh)
        red = trace_reduce.reduce(drv.trace_planes, chips)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": [list(x) for x in red["device_ops"]],
                     "idle_gaps": red["idle_gaps"]}
        ctx = Context(cell, cfg, chips, peaks_mod.peaks_for(dev.device_kind)
                      if require_tpu else None, red, drv.counters())
        for m in metrics_of(bench, workload, "per_layer"):
            v = load_reader(m["name"], bench_dir)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": units[m["name"]]}
    else:
        e2e = drv.end_to_end()
        e2e["setup_s"] = setup_s
        for m in metrics_of(bench, workload, "end_to_end"):
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}
    for k, v in drv.notes().items():
        common.log(f"note {k} {v}")
    t_ref = time.perf_counter()
    correct, checks = drv.verify()
    common.log(f"note reference_s {time.perf_counter() - t_ref:.3f}")
    result = {"correct": bool(correct), "attempted": int(drv.attempted),
              "failed": int(drv.failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default="",
                    help="also write the traced stretch, in the reducer's "
                         "neutral form, to this .json.gz file")
    args = ap.parse_args(argv)
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    try:
        result, checks = run(bench, args.workload, args.seed, args.seconds,
                             bool(args.trace), keep_trace=args.keep_trace)
    except common.NoChip as e:
        common.log(f"run_cell: {e}")
        return NO_CHIP_EXIT
    for k, (v, lim) in checks.items():
        common.log(f"check {k} {v} limit {lim}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
