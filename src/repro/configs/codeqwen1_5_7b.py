"""CodeQwen1.5 7B — qwen1.5 arch, GQA kv=4, QKV bias, theta 1e6, untied
[hf:Qwen/CodeQwen1.5-7B]."""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="codeqwen1.5-7b",
    family="dense",
    n_layers=32,
    d_model=4096,
    n_heads=32,
    n_kv_heads=4,
    d_ff=13440,
    vocab_size=92416,
    qkv_bias=True,
    rope_theta=1e6,
    norm_eps=1e-5,
    tie_embeddings=False,
    optimizer="adamw",
)
