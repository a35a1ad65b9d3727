"""A new configuration, traffic mix and per-layer metric need only new
files and an entry in BENCHMARK.json: placed in a copy of the benchmark,
each is found by its name."""
import json
import os

from benchmarks.chip import common, run_cell, traffic
from benchmarks.chip.tests import tiny


def test_dummy_config_mix_and_metric_are_found_by_name(tmp_path):
    root, bench = tiny.make_root(str(tmp_path))
    bench_dir = os.path.join(root, "benchmarks", "chip")
    with open(os.path.join(bench_dir, "traffic", "dummy_mix.json"), "w") as fh:
        json.dump({**tiny.TINY_MIX, "rate_per_s": 3.0}, fh)
    with open(os.path.join(bench_dir, "metrics", "dummy.metric.py"), "w") as fh:
        fh.write("def read(ctx):\n    return 41.0 + ctx.chips\n")
    bench["configs"].append({"name": "dummy-cfg",
                             "file": "benchmarks/chip/configs/tiny-lm.json"})
    bench["workloads"].append({"name": "dummy-cfg.dummy_mix",
                               "config": "dummy-cfg", "traffic": "dummy_mix",
                               "chips": 1})
    bench["per_layer"].append({"name": "dummy.metric", "unit": "count",
                               "workloads": ["dummy-cfg.dummy_mix"]})
    cell, entry = common.find_cell(bench, "dummy-cfg.dummy_mix")
    assert common.load_json(root, entry["file"])["name"] == "tiny-lm"
    mix = traffic.load_mix(cell["traffic"], bench_dir)
    assert mix["rate_per_s"] == 3.0
    names = [m["name"] for m in
             run_cell.metrics_of(bench, cell["name"], "per_layer")]
    assert names == ["dummy.metric"]
    ctx = run_cell.Context(cell, {}, 1, None, {}, {})
    assert run_cell.load_reader("dummy.metric", bench_dir)(ctx) == 42.0


def test_every_named_part_of_the_benchmark_exists():
    bench = common.load_json(common.ROOT, "BENCHMARK.json")
    for c in bench["configs"]:
        cfg = common.load_json(common.ROOT, c["file"])
        assert cfg["name"] == c["name"]
        assert os.path.exists(os.path.join(common.HERE, "drivers",
                                           f"{cfg['driver']}.py"))
    for w in bench["workloads"]:
        traffic.load_mix(w["traffic"])
        common.load_json(common.HERE, "checks", f"{w['name']}.json")
    for m in bench["per_layer"]:
        assert callable(run_cell.load_reader(m["name"]))
