"""The `sched.overlap_share` reader on stub engine counters."""
import pytest

from benchmarks.chip import run_cell


def _read(counters):
    ctx = run_cell.Context({"name": "t"}, {}, 1, None, {}, counters)
    return run_cell.load_reader("sched.overlap_share")(ctx)


def test_overlap_share_is_overlapped_rounds_over_decode_steps():
    ws = {"decode_steps": 712, "rounds_overlapped": 690}
    assert _read({"window_stats": ws}) == pytest.approx(100.0 * 690 / 712)


def test_overlap_share_reads_nothing_without_the_counter():
    assert _read({"window_stats": {"decode_steps": 712}}) is None
    assert _read({"window_stats": {}}) is None
    assert _read({}) is None


def test_overlap_share_reads_nothing_without_decode_steps():
    ws = {"decode_steps": 0, "rounds_overlapped": 0}
    assert _read({"window_stats": ws}) is None
