"""The tensor-parallel readers, on a hand-made trace of two chips and on
the engine's per-device counters."""
import pytest

from benchmarks.chip import run_cell
from benchmarks.chip import trace_reduce as tr

MS = 1e6


def _plane(name, **lines):
    return {"name": name, "lines": [{"name": k, "events": v}
                                    for k, v in lines.items()]}


# chip 0: compute 0-20 and 30-50 ms; an all-gather 20-25 alone, and one
# 40-45 under the second fusion (busy 45 ms); chip 1: compute 0-60.
# Window 0-100.
TWO_CHIPS = [
    _plane("/host:CPU", main=[["bench.traced_window", 0.0, 100 * MS]]),
    _plane("/device:TPU:0", **{"XLA Ops": [
        ["%fusion.1 = bf16[8] fusion(...)", 0.0, 20 * MS],
        ["%all-gather.2 = bf16[32] all-gather(...)", 20 * MS, 5 * MS],
        ["%fusion.3 = bf16[8] fusion(...)", 30 * MS, 20 * MS],
        ["%all-gather-start.4 = bf16[32] all-gather-start(...)", 40 * MS,
         5 * MS]]}),
    _plane("/device:TPU:1", **{"XLA Ops": [
        ["%fusion.1 = bf16[8] fusion(...)", 0.0, 60 * MS]]}),
]


def _ctx(trace=None, counters=None, chips=2, planes=None):
    ctx = run_cell.Context({"name": "t"}, {}, chips, None, trace or {},
                           counters or {})
    ctx.planes = planes
    return ctx


def test_collective_share_counts_overlapped_gathers_in_full():
    red = tr.reduce(TWO_CHIPS, chips=2)
    assert red["collective_s"] == pytest.approx(0.010)
    assert red["busy_s"] == pytest.approx(0.0525)    # (45 + 60) / 2 ms
    share = run_cell.load_reader("tp.collective_share")(
        _ctx(red, planes=TWO_CHIPS))
    # chip 0's collectives over chip 0's own busy time, not the mean
    assert share == pytest.approx(100.0 * 10 / 45)


def test_collective_share_reads_zero_without_collectives():
    chip_1_alone = [TWO_CHIPS[0], {**TWO_CHIPS[2], "name": "/device:TPU:0"}]
    red = tr.reduce(chip_1_alone, chips=1)
    read = run_cell.load_reader("tp.collective_share")
    assert read(_ctx(red, planes=chip_1_alone)) == 0.0
    idle = [TWO_CHIPS[0], _plane("/device:TPU:0", **{"XLA Ops": []})]
    assert read(_ctx({"busy_s": 0.0, "collective_s": 0.0},
                     planes=idle)) is None
    assert read(_ctx(red)) is None               # untraced: no planes


def test_param_bytes_per_chip_reads_the_fullest_chip():
    per_dev = [{"device": 0, "params_bytes": 7_000_000_000},
               {"device": 1, "params_bytes": 7_641_063_424},
               {"device": 2, "params_bytes": 3_600_000_000},
               {"device": 3, "params_bytes": 7_641_063_424}]
    read = run_cell.load_reader("tp.param_bytes_per_chip")
    got = read(_ctx(counters={"window_stats": {"per_device": per_dev}}, chips=4))
    assert got == pytest.approx(7.641063424)


def test_param_bytes_per_chip_reads_nothing_without_a_mesh():
    read = run_cell.load_reader("tp.param_bytes_per_chip")
    assert read(_ctx(counters={"window_stats": {"decode_steps": 3}})) is None
    assert read(_ctx(counters={})) is None
