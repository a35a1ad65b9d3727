"""The check that decides `correct`, driven on the CPU at a size a test
run holds: the control (the reference one precision below the
configuration's) reads far above the program, and a run whose timed path
is broken underneath comes out not correct."""
import json
import os

import numpy as np
import pytest

from benchmarks.chip import control, run_cell
from benchmarks.chip.tests import tiny

SMALL_LM = {**tiny.TINY_LM, "name": "small-lm", "num_hidden_layers": 4,
            "hidden_size": 256, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 64,
            "intermediate_size": 512, "vocab_size": 4096,
            "tie_word_embeddings": False,
            "deployment": {"chips": 1, "max_batch": 8, "cache_len": 256}}
SMALL_MIX = {**tiny.TINY_MIX, "rate_per_s": 10.0,
             "prompt_len": {"dist": "lognormal", "median": 48, "sigma": 0.6,
                            "min": 8, "max": 128},
             "output_len": {"dist": "lognormal", "median": 24, "sigma": 0.6,
                            "min": 8, "max": 96}}


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    root, bench = tiny.make_root(str(tmp_path_factory.mktemp("small")))
    d = os.path.join(root, "benchmarks", "chip")
    for rel, obj in {"configs/small-lm.json": SMALL_LM,
                     "traffic/small.json": SMALL_MIX,
                     "checks/small-lm.small.json": {
                         "widest_logit_gap": {"limit": 0.05}}}.items():
        with open(os.path.join(d, rel), "w") as fh:
            json.dump(obj, fh)
    bench["configs"].append({"name": "small-lm",
                             "file": "benchmarks/chip/configs/small-lm.json"})
    bench["workloads"].append({"name": "small-lm.small", "config": "small-lm",
                               "traffic": "small", "chips": 1})
    for m in bench["end_to_end"]:
        if "tiny-lm.tiny" in m.get("workloads", []):
            m["workloads"].append("small-lm.small")
    return root, bench


@pytest.mark.parametrize("cell,name", [
    ("small-lm.small", "widest_logit_gap"),
    ("tiny-gpp.back_to_back", "max_rel_err")])
def test_control_reads_far_above_the_program(small, cell, name):
    root, bench = small
    rows = list(control.readings(bench, cell, [11, 12, 13], 3.0,
                                 require_tpu=False, root=root))
    prog = max(r["checks"][name][0] for r in rows)
    ctrl = min(r["checks"][f"control_{name}"][0] for r in rows)
    print(cell, [r["checks"] for r in rows])
    assert ctrl >= 3 * prog and ctrl > 0


def _run(small, cell):
    root, bench = small
    result, _ = run_cell.run(bench, cell, 2**32 + 3, 3.0, False,
                             require_tpu=False, root=root)
    return result


def test_sound_runs_are_correct(small):
    assert _run(small, "small-lm.small")["correct"]
    assert _run(small, "tiny-gpp.back_to_back")["correct"]


def test_a_token_altered_where_it_is_produced_is_caught(small, monkeypatch):
    from repro.serve.engine import ServeEngine
    orig = ServeEngine._sample_rows
    calls = [0]

    def altered(self, logits, slots):
        toks = orig(self, logits, slots).copy()
        calls[0] += 1
        if calls[0] % 7 == 0:
            toks = (toks + 1) % self.cfg.vocab_size
        return toks

    monkeypatch.setattr(ServeEngine, "_sample_rows", altered)
    result = _run(small, "small-lm.small")
    assert not result["correct"]
    gap = result["checks"]["widest_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_an_answer_altered_where_it_is_produced_is_caught(small, monkeypatch):
    import repro
    orig = repro.dispatch
    calls = [0]

    def altered(*a, **k):
        ach, asx = orig(*a, **k)
        calls[0] += 1
        return (ach * np.float32(1.05) if calls[0] % 3 == 0 else ach), asx

    monkeypatch.setattr(repro, "dispatch", altered)
    result = _run(small, "tiny-gpp.back_to_back")
    assert not result["correct"]
