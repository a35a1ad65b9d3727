"""The program spans in a reduced trace, and the device idle time put down
to them: on a hand-made trace whose numbers are known, and on short
traces recorded on a v5e chip with `run_cell.py --keep-trace`."""
import gzip
import json
import os
import types

import pytest

from benchmarks.chip import run_cell, spans
from benchmarks.chip import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6


def _plane(name, **lines):
    return {"name": name, "lines": [{"name": k, "events": v}
                                    for k, v in lines.items()]}


def _ops(*busy_ms):
    return [["%fusion.1 = f32[8] fusion(...)", a * MS, (b - a) * MS]
            for a, b in busy_ms]


# the traced window is 0-100 ms; one round opens at 1 ms, another is cut by
# the window's end and a third lies before it
HOST = [
    ["bench.traced_window", 0.0, 100 * MS],
    ["bench.step", 0.0, 60 * MS],
    ["serve.round", -20 * MS, 15 * MS],
    ["serve.round", 1 * MS, 57 * MS],
    ["serve.admit", 2 * MS, 28 * MS],
    ["serve.prefill", 3 * MS, 2 * MS],
    ["serve.sample", 20 * MS, 10 * MS],
    ["serve.decode", 32 * MS, 3 * MS],
    ["serve.sample", 40 * MS, 15 * MS],
    ["kernel.dispatch", 70 * MS, 20 * MS],
    ["kernel.config", 70 * MS, 2 * MS],
    ["serve.round", 95 * MS, 15 * MS],
]
HAND = [
    _plane("/host:CPU", main=HOST),
    _plane("/device:TPU:0", **{"XLA Ops": _ops(
        (5, 20), (35, 40), (57, 62), (66, 72), (90, 97))}),
    _plane("/device:TPU:1", **{"XLA Ops": _ops((0, 50))}),
]
# device 0 idles 0-5, 20-35, 40-57, 62-66, 72-90 and 97-100 (62 ms)
IDLE_0 = {"serve.round": 8e-3, "serve.admit": 1e-3, "serve.prefill": 2e-3,
          "serve.sample": 25e-3, "serve.decode": 3e-3,
          "kernel.dispatch": 18e-3, spans.OUTSIDE: 5e-3}
# device 1 idles 50-100
IDLE_1 = {"serve.sample": 5e-3, "serve.round": 8e-3, spans.OUTSIDE: 17e-3,
          "kernel.config": 2e-3, "kernel.dispatch": 18e-3}


def _recorded(name):
    with gzip.open(os.path.join(HERE, "data", name), "rt") as fh:
        return json.load(fh)


def _read(name, planes, counters=None):
    """`name`'s reader called as `run_cell.run` calls it: on a Context
    made beside the run's driver `drv`, which holds the planes."""
    drv = types.SimpleNamespace(trace_planes=planes)  # noqa: F841
    red = tr.reduce(planes, chips=1) if planes else None
    ctx = run_cell.Context({}, {}, 1, None, red, counters or {})
    return run_cell.load_reader(name)(ctx)


def check_program_spans_clip_to_the_window():
    assert spans.program_spans(HAND, "serve.round") == [
        ("serve.round", 1 * MS, 57 * MS), ("serve.round", 95 * MS, 5 * MS)]
    assert spans.whole_spans(HAND, "serve.round") == [
        ("serve.round", 1 * MS, 57 * MS)]
    assert [e[0] for e in spans.program_spans(HAND, "kernel.")] == [
        "kernel.dispatch", "kernel.config"]


def check_innermost_follows_nesting():
    lab = spans.innermost(spans.program_spans(HAND, spans.PROGRAM))
    at = {a / MS: name for a, _, name in lab}
    assert at[1] == "serve.round" and at[2] == "serve.admit"
    assert at[3] == "serve.prefill" and at[5] == "serve.admit"
    assert at[20] == "serve.sample" and at[30] == "serve.round"
    assert at[70] == "kernel.config" and at[72] == "kernel.dispatch"
    assert 58 not in at and 90 not in at        # inside no span
    # stretches tile time without overlap
    assert all(b <= c for (_, b, _), (c, _, _) in zip(lab, lab[1:]))


def check_idle_by_span_one_chip():
    got = spans.idle_by_span(HAND, spans.PROGRAM, chips=1)
    assert got == pytest.approx(IDLE_0)
    red = tr.reduce(HAND, chips=1)
    assert sum(got.values()) == pytest.approx(red["window_s"] - red["busy_s"])


def check_idle_by_span_averages_chips():
    got = spans.idle_by_span(HAND, spans.PROGRAM, chips=2)
    want = {k: (IDLE_0.get(k, 0.0) + IDLE_1.get(k, 0.0)) / 2
            for k in set(IDLE_0) | set(IDLE_1)}
    assert got == pytest.approx(want)
    red = tr.reduce(HAND, chips=2)
    assert sum(got.values()) == pytest.approx(red["window_s"] - red["busy_s"])


def check_readers_on_the_hand_trace():
    ws = {"window_stats": {"prefill_tokens": 300,
                           "prefill_bucket_tokens": 400}}
    assert _read("device.idle_in_sample.serve", HAND) == pytest.approx(25.0)
    assert _read("sched.admit_ms_p95", HAND) == pytest.approx(28.0)
    assert _read("gpp.dispatch_ms", HAND) == pytest.approx(20.0)
    assert _read("sched.prefill_pad_share", HAND, ws) == pytest.approx(25.0)


def check_readers_without_program_spans():
    """A program that writes no spans, and an untraced run, read as no
    value, never 0."""
    bare = [_plane("/host:CPU", main=[e for e in HOST
                                      if e[0].startswith("bench.")]),
            HAND[1]]
    names = ("device.idle_in_sample.serve", "sched.admit_ms_p95",
             "gpp.dispatch_ms", "sched.prefill_pad_share")
    for planes in (bare, None):
        got = [_read(n, planes, {"window_stats": {"occupancy": 0.5}})
               for n in names]
        assert got == [None] * 4
    got = spans.idle_by_span(bare, spans.PROGRAM, chips=1)
    assert got == pytest.approx({spans.OUTSIDE: 62e-3})


def check_planes_of_finds_the_runs_driver():
    """`run_cell.run` holds the driver as `drv` and the reader's Context
    as `ctx`, and reads `drv.trace_planes`: the names `planes_of` looks
    for. A Context made outside a run has no planes, and one that carries
    its own is taken at its word."""
    code = run_cell.run.__code__
    assert {"drv", "ctx"} <= set(code.co_varnames)
    assert "trace_planes" in code.co_names
    ctx = run_cell.Context({}, {}, 1, None, None, {})
    assert spans.planes_of(ctx) is None
    drv = types.SimpleNamespace(trace_planes=HAND)  # noqa: F841
    other = run_cell.Context({}, {}, 1, None, None, {})
    assert spans.planes_of(ctx) is HAND and spans.planes_of(other) is None
    own = types.SimpleNamespace(planes=[])
    assert spans.planes_of(own) == []


CHECKS = {f.__name__[len("check_"):]: f for f in (
    check_program_spans_clip_to_the_window, check_innermost_follows_nesting,
    check_idle_by_span_one_chip, check_idle_by_span_averages_chips,
    check_readers_on_the_hand_trace, check_readers_without_program_spans,
    check_planes_of_finds_the_runs_driver)}


@pytest.mark.parametrize("case", sorted(CHECKS))
def test_hand_trace(case):
    CHECKS[case]()


def _idle_by_painting(planes, res_ns=1000.0):
    """{span: idle seconds} of device 0 by painting a fine timeline, spans
    in the order they open so that inner ones paint over outer ones,
    independently of the sweep in `spans`."""
    import numpy as np
    t0, t1 = tr.window(planes)
    n = int((t1 - t0) / res_ns) + 1
    busy = np.zeros(n, bool)
    for _, s, d in tr.line_events(tr.device_planes(planes)[0], tr.OPS_LINE):
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            busy[int((a - t0) / res_ns):int((b - t0) / res_ns)] = True
    label = np.full(n, -1)
    found = spans.program_spans(planes, spans.PROGRAM)
    names = sorted({e[0] for e in found})
    for name, s, d in found:
        label[int((s - t0) / res_ns):int((s + d - t0) / res_ns)] = \
            names.index(name)
    out = {spans.OUTSIDE: float(((label < 0) & ~busy).sum())}
    for i, name in enumerate(names):
        out[name] = float(((label == i) & ~busy).sum())
    return {k: v * res_ns * 1e-9 for k, v in out.items() if v}


@pytest.mark.parametrize("name,most", [
    ("serve_spans_v5e.json.gz", "serve.sample"),
    ("gpp_spans_v5e.json.gz", "kernel.dispatch")])
def test_recorded_idle_split(name, most):
    """The idle split of a short trace recorded on one v5e chip sums to
    the reducer's idle share, agrees with a painted timeline, and puts
    most of the idle time where the host was: pulling the sampled tokens
    in serving, re-tracing each call in the GPP dispatch."""
    planes = _recorded(name)
    red = tr.reduce(planes, chips=1)
    parts = spans.idle_by_span(planes, spans.PROGRAM, chips=1)
    share = 100 * (1 - red["busy_s"] / red["window_s"])
    assert 100 * sum(parts.values()) / red["window_s"] == \
        pytest.approx(share, abs=1e-6)
    painted = _idle_by_painting(planes)
    for k in set(parts) | set(painted):
        assert parts.get(k, 0.0) == pytest.approx(painted.get(k, 0.0),
                                                  abs=2e-4), k
    assert max(parts, key=parts.get) == most
    assert parts[most] > 0.8 * sum(parts.values())
