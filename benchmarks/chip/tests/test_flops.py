"""The yardstick's operation and byte counts against hand counts."""
import json
import os

import pytest

from benchmarks.chip import common, flops

TINY = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 16,
        "vocab_size": 32, "tie_word_embeddings": True, "qkv_bias": True}


def test_dense_counts_by_hand():
    s = flops.DenseShape.of(TINY)
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8, 3 x 8x16 -> 64+32+32+64+384
    assert s.layer_matmul_params == 576
    assert s.layer_params == 576 + (8 + 4 + 4) + 2 * 8
    assert s.param_count() == 32 * 8 + 2 * 608 + 8
    assert s.kv_line_bytes == 2 * 2 * 1 * 4 * 2
    # two rows at contexts 3 and 5: 2 x 2 x (2 x 576 + 32 x 8) + attention
    # 4 x layers x heads x head_dim x ctx = 4 x 2 x 2 x 4 x (3 + 5)
    assert flops.decode_step_flops(s, [3, 5]) == 2 * 2 * (2 * 576 + 256) + 64 * 8
    # tied: all params once, live lines 2 + 4 read, 2 new lines written
    assert flops.decode_step_bytes(s, [3, 5]) == (1480 * 2 + 6 * 32 + 2 * 32)
    # prefill of 3 tokens: projections, causal attention, one LM-head row
    assert flops.prefill_flops(s, 3) == (2 * 3 * 2 * 576
                                         + 2 * 2 * 2 * 4 * 3 * 4 + 2 * 256)


def test_untied_reads_only_the_gathered_embedding_rows():
    s = flops.DenseShape.of({**TINY, "tie_word_embeddings": False})
    assert s.param_count() == 2 * 32 * 8 + 2 * 608 + 8
    tied = flops.DenseShape.of(TINY)
    assert (flops.decode_step_bytes(s, [1])
            - flops.decode_step_bytes(tied, [1])) == 8 * 2


def test_gpp_counts():
    s = flops.GppShape(nbands=2, ngpown=3, ncouls=4, nw=2)
    assert flops.gpp_flops(s) == 2 * 3 * 4 * 2 * 90
    want = 8 * 4 * 3 * 2 + 8 * 4 * 2 + 8 * 3 * 2 + 4 * 2 * 2 + 4 * 4 + 32
    assert flops.gpp_min_bytes(s) == want


def test_roofline_takes_the_larger_bound():
    assert flops.roofline_s(10.0, 1.0, chips=1, peak_flops=1.0,
                            hbm_bw=1.0) == 10.0
    assert flops.roofline_s(1.0, 40.0, chips=4, peak_flops=1.0,
                            hbm_bw=1.0) == 10.0


@pytest.mark.parametrize("name,count", [("qwen2-1.5b", 1.54e9),
                                        ("codeqwen1.5-7b", 7.25e9)])
def test_published_parameter_counts(name, count):
    with open(os.path.join(common.HERE, "configs", f"{name}.json")) as fh:
        cfg = json.load(fh)
    n = flops.DenseShape.of(cfg).param_count()
    assert abs(n - count) / count < 0.005, n
    assert cfg["reduced"] == []
