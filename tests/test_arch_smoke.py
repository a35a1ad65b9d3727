"""Per-architecture smoke tests (deliverable f): reduced config of the same
family, one forward/train step + prefill + decode on CPU, asserting output
shapes and no NaNs. Full configs are exercised only via the dry-run."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (ARCH_IDS, applicable_shapes, get_config,
                                reduce_config)
from repro.models.registry import build_model

B, S = 2, 64


def _batch(cfg, rng):
    batch = {
        "tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(rng, (B, S), 0, cfg.vocab_size),
    }
    if cfg.family == "encdec":
        batch["frames"] = 0.02 * jax.random.normal(
            rng, (B, cfg.enc_seq, cfg.d_model), jnp.bfloat16)
    if cfg.family == "vlm":
        batch["vis"] = 0.02 * jax.random.normal(
            rng, (B, cfg.n_vis_tokens, cfg.d_model), jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch):
    cfg = reduce_config(get_config(arch))
    model = build_model(cfg)
    rng = jax.random.PRNGKey(0)
    params = model.init_params(rng)
    batch = _batch(cfg, rng)
    loss, metrics = jax.jit(model.loss_fn)(params, batch)
    assert loss.shape == ()
    assert np.isfinite(float(loss)), f"{arch}: loss NaN"
    assert float(metrics["ntokens"]) > 0
    # one SGD-flavored step moves the loss (gradient flows end to end)
    grads = jax.jit(jax.grad(lambda p: model.loss_fn(p, batch)[0]))(params)
    gn = sum(float(jnp.sum(jnp.abs(g.astype(jnp.float32))))
             for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0, f"{arch}: degenerate grads"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_prefill_decode(arch):
    cfg = reduce_config(get_config(arch))
    model = build_model(cfg)
    rng = jax.random.PRNGKey(1)
    params = model.init_params(rng)
    batch = _batch(cfg, rng)
    logits, cache = jax.jit(model.prefill)(params, batch)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))
    tok = batch["tokens"][:, :1]
    logits2, cache2 = jax.jit(model.decode_step)(params, cache, tok)
    assert logits2.shape == (B, 1, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits2, np.float32)))
    assert int(cache2["pos"]) == int(cache["pos"]) + 1


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_applicable_shapes_policy(arch):
    cfg = get_config(arch)
    shapes = applicable_shapes(cfg)
    assert {"train_4k", "prefill_32k", "decode_32k"} <= set(shapes)
    if cfg.subquadratic:
        assert "long_500k" in shapes     # ssm/hybrid run the 500k cell
    else:
        assert "long_500k" not in shapes  # quadratic archs skip it


def test_param_counts_match_published():
    """Config param formulas vs hand-checked published sizes (±15%)."""
    expected = {
        "qwen2_1_5b": 1.54e9,
        "phi4_mini_3_8b": 3.8e9,
        "codeqwen1_5_7b": 7.25e9,  # hf release: GQA-4, d_ff 13440, untied
        "qwen2_5_32b": 32.5e9,
        "rwkv6_7b": 7.6e9,
        "deepseek_moe_16b": 16.4e9,
        "hymba_1_5b": 1.5e9,
    }
    for arch, n in expected.items():
        got = get_config(arch).param_count()
        assert abs(got - n) / n < 0.15, (arch, got, n)


def test_moe_active_params():
    cfg = get_config("llama4_maverick_400b_a17b")
    assert cfg.active_param_count() < 0.05 * cfg.param_count()
    ds = get_config("deepseek_moe_16b")
    assert 2e9 < ds.active_param_count() < 4e9   # ~2.8B active (paper)


@pytest.mark.parametrize("arch, published", [
    ("codeqwen1_5_7b", dict(n_layers=32, d_model=4096, n_heads=32, n_kv_heads=4,
                            head_dim=128, d_ff=13440, vocab_size=92416,
                            qkv_bias=True, rope_theta=1e6, norm_eps=1e-5,
                            tie_embeddings=False)),
    ("qwen2_1_5b", dict(n_layers=28, d_model=1536, n_heads=12, n_kv_heads=2,
                        head_dim=128, d_ff=8960, vocab_size=151936,
                        qkv_bias=True, rope_theta=1e6, norm_eps=1e-6,
                        tie_embeddings=True)),
])
def test_qwen_configs_state_the_published_model(arch, published):
    """The Qwen configs the chip benchmark serves hold the values of the
    published config.json (benchmarks/chip/configs/ states the same)."""
    cfg = get_config(arch)
    assert {k: getattr(cfg, k) for k in published} == published
    if arch == "codeqwen1_5_7b":
        assert abs(cfg.param_count() - 7.25e9) / 7.25e9 < 0.02
