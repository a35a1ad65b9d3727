"""A checkout in a temporary directory holding the benchmark plus tiny
cells of each driver, small enough to run on the CPU."""

from __future__ import annotations

import json
import os
import shutil

from benchmarks.chip import common

TINY_LM = {
    "name": "tiny-lm", "source": "test", "driver": "serve",
    "family": "dense_lm", "num_hidden_layers": 2, "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 128, "vocab_size": 256, "tie_word_embeddings": True,
    "qkv_bias": True, "rope_theta": 1000000.0, "rms_norm_eps": 1e-6,
    "reduced": [], "init_std": 0.02, "init_norm_std": 0.1,
    "init_bias_std": 0.02,
    "deployment": {"chips": 1, "max_batch": 4, "cache_len": 64}}
TINY_MIX = {
    "kind": "open_loop", "arrival": "poisson", "rate_per_s": 8.0,
    "prompt_len": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                   "min": 4, "max": 24},
    "output_len": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                   "min": 2, "max": 16},
    "temperature": 0.0, "shape_seed": 0}
TINY_GPP = {"name": "tiny-gpp", "source": "test", "driver": "gpp",
            "nbands": 8, "ngpown": 8, "ncouls": 64, "nw": 2, "reduced": []}


def make_root(tmp: str, lm_limit: float = 1.0, gpp_limit: float = 1e-2):
    """(root, bench): a checkout under tmp with tiny cells added."""
    bench_dir = os.path.join(tmp, "benchmarks", "chip")
    shutil.copytree(common.HERE, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    files = {
        "configs/tiny-lm.json": TINY_LM, "configs/tiny-gpp.json": TINY_GPP,
        "traffic/tiny.json": TINY_MIX,
        "checks/tiny-lm.tiny.json": {"widest_logit_gap": {"limit": lm_limit}},
        "checks/tiny-gpp.back_to_back.json": {
            "max_rel_err": {"limit": gpp_limit}},
    }
    for rel, obj in files.items():
        with open(os.path.join(bench_dir, rel), "w") as fh:
            json.dump(obj, fh)
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["configs"] += [
        {"name": "tiny-lm", "file": "benchmarks/chip/configs/tiny-lm.json"},
        {"name": "tiny-gpp", "file": "benchmarks/chip/configs/tiny-gpp.json"}]
    bench["workloads"] += [
        {"name": "tiny-lm.tiny", "config": "tiny-lm", "traffic": "tiny",
         "chips": 1},
        {"name": "tiny-gpp.back_to_back", "config": "tiny-gpp",
         "traffic": "back_to_back", "chips": 1}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        for real, tiny in (("qwen2-1.5b.chat", "tiny-lm.tiny"),
                           ("gpp-si214.back_to_back", "tiny-gpp.back_to_back")):
            if real in m.get("workloads", []):
                m["workloads"].append(tiny)
    return tmp, bench
