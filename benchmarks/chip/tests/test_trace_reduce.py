"""The trace reduction, on a hand-made trace whose numbers are known and
on a small trace recorded on a v5e chip."""
import gzip
import json
import os

import pytest

from benchmarks.chip import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6


def _plane(name, **lines):
    return {"name": name, "lines": [{"name": k, "events": v}
                                    for k, v in lines.items()]}


HAND = [
    _plane("/host:CPU", main=[
        ["bench.traced_window", 0.0, 100 * MS],
        ["bench.step", 0.0, 60 * MS],
        ["sample", 40 * MS, 15 * MS],
        ["sleep", 70 * MS, 25 * MS]]),
    _plane("/device:TPU:0", **{
        "XLA Modules": [["jit__decode_masked(1)", 10 * MS, 30 * MS],
                        ["jit__decode_masked(1)", 60 * MS, 10 * MS],
                        ["jit__prefill_slot(2)", 95 * MS, 10 * MS]],
        "XLA Ops": [["%while.1 = (...) while(...)", 10 * MS, 30 * MS],
                    ["%fusion.2 = f32[8] fusion(...)", 12 * MS, 10 * MS],
                    ["%all-gather.3 = bf16[4] all-gather(...)", 25 * MS, 5 * MS],
                    ["%fusion.2 = f32[8] fusion(...)", 60 * MS, 10 * MS],
                    ["%fusion.9 = f32[8] fusion(...)", 95 * MS, 10 * MS]]}),
    _plane("/device:TPU:1", **{
        "XLA Ops": [["%fusion.2 = f32[8] fusion(...)", 0.0, 50 * MS]]}),
]


def test_union_and_gaps():
    iv = tr.union([("a", 0, 10), ("b", 5, 10), ("c", 20, 5)])
    assert iv == [(0, 15), (20, 25)]
    assert tr.gaps(iv, 0, 30) == [(15, 20), (25, 30)]
    assert tr.busy_ns([("a", 0, 10), ("b", 5, 10)]) == 15


def test_self_time_subtracts_nested_ops():
    st = tr.self_times([("%while.1 = x", 0, 30), ("%f.2 = y", 2, 10),
                        ("%g.3 = z", 15, 5)])
    assert st == pytest.approx({"while.1": 15e-9, "f.2": 10e-9, "g.3": 5e-9})


def test_reduce_hand_trace():
    r = tr.reduce(HAND, chips=1)
    assert r["window_s"] == pytest.approx(0.1)
    # device 0 busy: 10-40, 60-70, 95-100 (clipped to the window)
    assert r["busy_s"] == pytest.approx(0.045)
    assert r["programs"] == {"_decode_masked": (2, pytest.approx(0.04))}
    assert r["collective_s"] == pytest.approx(0.005)
    assert r["op_kinds"]["fusion"] == (3, pytest.approx(0.025))
    names = dict(r["device_ops"])
    assert names["while.1"] == pytest.approx(0.015)
    gap_names = [g[0] for g in r["idle_gaps"]]
    # the longest gaps: 70-95 (host sleeping), 40-60 (sampling), 0-10
    assert gap_names[:3] == ["sleep", "sample", "bench.step"]
    assert [g[1] for g in r["idle_gaps"][:3]] == pytest.approx(
        [0.025, 0.02, 0.01])
    two = tr.reduce(HAND, chips=2)
    assert two["busy_s"] == pytest.approx((0.045 + 0.05) / 2)


def test_program_names():
    assert tr.program_name("jit__decode_masked(3938007651360716616)") == \
        "_decode_masked"
    assert tr.op_name("%tpu_custom_call.1 = f32[4] custom-call(...)") == \
        "tpu_custom_call.1"


def _recorded(name):
    with gzip.open(os.path.join(HERE, "data", name), "rt") as fh:
        return json.load(fh)


def _busy_by_timeline(planes, t0, t1, res_ns=100.0):
    """Busy time of device 0 by marking a fine timeline, independently of
    the interval union."""
    import numpy as np
    dev = tr.device_planes(planes)[0]
    mark = np.zeros(int((t1 - t0) / res_ns) + 1, bool)
    for _, s, d in tr.line_events(dev, tr.OPS_LINE):
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            mark[int((a - t0) / res_ns):int((b - t0) / res_ns)] = True
    return mark.sum() * res_ns * 1e-9


@pytest.mark.parametrize("name", ["serve_v5e.json.gz", "gpp_v5e.json.gz"])
def test_recorded_trace_busy_time(name):
    """Traces recorded on one v5e chip (cut to a short stretch of the
    benchmark's traced window, whose span marks the stretch)."""
    planes = _recorded(name)
    r = tr.reduce(planes, chips=1)
    t0, t1 = tr.window(planes)
    assert r["window_s"] == pytest.approx((t1 - t0) * 1e-9)
    assert r["busy_s"] == pytest.approx(_busy_by_timeline(planes, t0, t1),
                                        abs=2e-4)
    assert 0 < r["busy_s"] <= r["window_s"]
    idle = r["window_s"] - r["busy_s"]
    assert sum(g[1] for g in r["idle_gaps"]) <= idle + 1e-9
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10


def test_recorded_serve_trace_programs():
    planes = _recorded("serve_v5e.json.gz")
    r = tr.reduce(planes, chips=1)
    runs, secs = r["programs"]["_decode_masked"]
    t0, t1 = tr.window(planes)
    dev = tr.device_planes(planes)[0]
    inside = [e for e in tr.line_events(dev, tr.MODULES_LINE)
              if e[0].startswith("jit__decode_masked")
              and e[1] >= t0 and e[1] + e[2] <= t1]
    assert runs == len(inside) >= 2
    assert secs == pytest.approx(sum(e[2] for e in inside) * 1e-9)
    # a decode step of qwen2-1.5b at 64 slots: tens of milliseconds
    assert 0.01 < secs / runs < 0.1


def test_recorded_gpp_trace_kernel_time():
    planes = _recorded("gpp_v5e.json.gz")
    r = tr.reduce(planes, chips=1)
    n, secs = r["op_kinds"]["tpu_custom_call"]
    assert n >= 1 and 0.3 < secs / n < 2.0
