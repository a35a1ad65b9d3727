"""The `step.kv_read_share` reader on stub engine counters."""
import pytest

from benchmarks.chip import run_cell


def _read(counters):
    ctx = run_cell.Context({"name": "t"}, {}, 1, None, {}, counters)
    return run_cell.load_reader("step.kv_read_share")(ctx)


def test_kv_read_share_is_lines_read_over_whole_cache_lines():
    ws = {"kv_lines_read": 3 * 512, "kv_lines_full": 3 * 64 * 2048}
    assert _read({"window_stats": ws}) == pytest.approx(100.0 * 512 / 131072)


def test_kv_read_share_reads_nothing_without_the_counters():
    assert _read({"window_stats": {"decode_steps": 3}}) is None
    assert _read({"window_stats": {"kv_lines_read": 0,
                                   "kv_lines_full": 0}}) is None
    assert _read({}) is None
