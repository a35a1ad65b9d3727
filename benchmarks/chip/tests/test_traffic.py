"""The traffic generator: per-seed determinism, the same work for every
seed, and lengths with the stated median."""
import numpy as np

from benchmarks.chip import traffic

MIX = traffic.load_mix("chat")


def _key(s):
    return [(r.due_s, r.prompt.tolist(), r.max_new_tokens) for r in s]


def test_same_seed_same_schedule():
    a = traffic.schedule(MIX, 2**31 + 7, 30, 151936)
    b = traffic.schedule(MIX, 2**31 + 7, 30, 151936)
    assert _key(a) == _key(b)


def test_seeds_reorder_the_same_work():
    a = traffic.schedule(MIX, 1, 30, 151936)
    b = traffic.schedule(MIX, 2, 30, 151936)
    assert _key(a) != _key(b)
    for f in (lambda r: len(r.prompt), lambda r: r.max_new_tokens):
        assert sorted(map(f, a)) == sorted(map(f, b))
    assert len(a) == len(b) == round(MIX["rate_per_s"] * 30)
    assert all(0 <= r.due_s < 30 for r in a)
    assert all(0 <= t < 151936 for r in a for t in r.prompt)


def test_lognormal_lengths_have_the_stated_median():
    spec = {"dist": "lognormal", "median": 256, "sigma": 0.8, "min": 1,
            "max": 10**9}
    x = traffic.lengths(traffic.seed_stream(0), spec, 20000)
    assert abs(np.median(x) - 256) / 256 < 0.03
    clipped = traffic.lengths(traffic.seed_stream(0),
                              {**spec, "min": 16, "max": 512}, 20000)
    assert clipped.min() >= 16 and clipped.max() <= 512


def test_negative_and_large_seeds():
    for s in (-5, 0, 2**33 + 1):
        assert traffic.schedule(MIX, s, 5, 100)
