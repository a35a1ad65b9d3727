"""The bridge from a dense-LM configuration file and the benchmark's
weights to the served program: its ModelConfig and its parameter tree.
This file, and no other, knows the program's parameter layout."""

from __future__ import annotations

from typing import Dict, Mapping


def model_config(cfg: Mapping):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=int(cfg["num_hidden_layers"]), d_model=int(cfg["hidden_size"]),
        n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]),
        d_ff=int(cfg["intermediate_size"]), vocab_size=int(cfg["vocab_size"]),
        head_dim=int(cfg["head_dim"]), qkv_bias=bool(cfg["qkv_bias"]),
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]), remat="none")


def to_program(w: Dict) -> Dict:
    """Benchmark weights (layers stacked) -> the program's tree."""
    lw = w["layers"]
    attn = {k: lw[k] for k in ("wq", "wk", "wv", "wo")}
    attn.update({k: lw[k] for k in ("bq", "bk", "bv") if k in lw})
    p = {"embed": w["embed"], "final_norm": w["final_norm"],
         "layers": {"ln1": lw["ln1"], "ln2": lw["ln2"], "attn": attn,
                    "ffn": {"wi": lw["w_up"], "wg": lw["w_gate"],
                            "wo": lw["w_down"]}}}
    if "head" in w:
        p["head"] = w["head"]
    return p
