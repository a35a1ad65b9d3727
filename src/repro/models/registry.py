"""Model assembly: config -> Model (init / loss / prefill / decode_step).

The Model object is the single integration point used by train/, serve/,
launch/dryrun.py and the smoke tests. All apply functions are pure and
jit-friendly; caches are plain dicts with a "pos" scalar.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_lib
from repro.models import mamba as mamba_lib
from repro.models import moe as moe_lib
from repro.models import transformer as T
from repro.models.layers import (
    PARAM_DTYPE, DistCtx, embed, gelu_ffn, layer_norm,
    lm_logits, matmul, rms_norm, softmax_xent, swiglu,
)

PyTree = Any


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    param_axes: Dict[str, Tuple[Optional[str], ...]]
    init_params: Callable[[jax.Array], PyTree]
    abstract_params: Callable[[], PyTree]
    loss_fn: Callable[..., Tuple[jax.Array, Dict]]
    prefill: Callable[..., Tuple[jax.Array, PyTree]]
    decode_step: Callable[..., Tuple[jax.Array, PyTree]]
    # single-row prefill written into one slot of a batched decode cache
    # (continuous batching refill — see serve/engine.py)
    prefill_into_slot: Callable[..., Tuple[jax.Array, PyTree]]
    init_cache: Callable[..., PyTree]
    cache_axes: Callable[..., PyTree]
    # suffix-only prefill continuing from cached prefix K/V lines (paged
    # K/V cache prefix reuse — serve/kvcache.py). None for families whose
    # prefill is not suffix-separable (recurrent state, vis/enc prefixes,
    # token-count-sensitive MoE capacity).
    prefill_continue: Optional[Callable[..., Tuple[jax.Array, PyTree]]] = None
    # multi-position decode: verify Q=k+1 candidate tokens per row in ONE
    # forward (speculative decoding — serve/spec.py). None for families
    # whose step is not position-batchable (recurrent state folds tokens
    # sequentially; moe capacity is token-count sensitive).
    decode_verify: Optional[Callable[..., Tuple[jax.Array, PyTree]]] = None


# ===========================================================================
# per-family forward passes
# ===========================================================================

def _maybe_remat(fn, remat: str):
    if remat == "none":
        return fn
    if remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def _moe_apply(cfg: ModelConfig, lp_moe, h, ctx, token_valid=None):
    """Dispatch MoE FFN: shard_map EP (all-to-all token exchange) when the
    plan asks for it and the token count justifies the exchange; otherwise
    the pure-SPMD capacity dispatch. token_valid (flattened (B*S,) bool)
    keeps padding tokens out of the capacity competition (left-padded
    prefill) — only the capacity path supports it."""
    b_, s, d = h.shape
    flat = h.reshape(-1, d)
    use_ep = (ctx is not None and getattr(ctx, "ep_data", False)
              and ctx.mesh is not None and b_ * s >= 4096
              and token_valid is None)
    if use_ep:
        f, aux = moe_lib.moe_ffn_ep(flat, lp_moe, n_experts=cfg.n_experts,
                                    k=cfg.experts_per_token, mesh=ctx.mesh,
                                    dp_axes=ctx.data_axes)
    else:
        f, aux = moe_lib.moe_ffn(flat, lp_moe, n_experts=cfg.n_experts,
                                 k=cfg.experts_per_token,
                                 token_valid=token_valid)
    return f.reshape(b_, s, d), aux


def _dense_stack(cfg: ModelConfig, layers, x, positions, *, remat, moe: bool,
                 window: int = 0, ctx=None):
    """Scan dense/moe decoder layers over x (B,S,D). Returns (x, aux_loss)."""

    def body(carry, lp):
        x = carry
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        a, _ = T.attn_block(lp["attn"], h, cfg, positions=positions,
                            window=window, ctx=ctx)
        x = x + a
        h = rms_norm(x, lp["ln2"], cfg.norm_eps)
        if moe:
            f, aux = _moe_apply(cfg, lp["moe"], h, ctx)
        else:
            f = swiglu(h, lp["ffn"]["wi"], lp["ffn"]["wg"], lp["ffn"]["wo"])
            aux = jnp.float32(0.0)
        return x + f, aux

    body = _maybe_remat(body, remat)
    x, auxs = jax.lax.scan(body, x, layers)
    return x, jnp.sum(auxs)


def _dense_prefill_stack(cfg: ModelConfig, layers, x, positions, *,
                         moe: bool, window: int = 0, ctx=None,
                         kv_valid=None):
    """Like _dense_stack but also emits the (k, v) cache per layer."""

    def body(carry, lp):
        x = carry
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        a, (k, v) = T.attn_block(lp["attn"], h, cfg, positions=positions,
                                 window=window, ctx=ctx, kv_valid=kv_valid)
        x = x + a
        h = rms_norm(x, lp["ln2"], cfg.norm_eps)
        if moe:
            tv = None if kv_valid is None else kv_valid.reshape(-1)
            f, _ = _moe_apply(cfg, lp["moe"], h, ctx, token_valid=tv)
        else:
            f = swiglu(h, lp["ffn"]["wi"], lp["ffn"]["wg"], lp["ffn"]["wo"])
        return x + f, (k, v)

    x, (ks, vs) = jax.lax.scan(body, x, layers)
    return x, ks, vs


def _dense_decode_stack(cfg: ModelConfig, layers, x, cache, *, ctx,
                        window: int = 0, ring: bool = False, active=None):
    """One decode token through dense/moe layers. The stacked K/V caches
    ride the layer loop's carry, so each layer writes only its new lines
    (T.attn_block_decode); as scan inputs and outputs they would be
    rebuilt whole every step. active: optional (B,) bool of the rows
    whose output is used (T.attn_block_decode)."""
    pos = cache["pos"]

    def body(carry, inp):
        x, ks, vs = carry
        lp, i = inp
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        a, (ks, vs) = T.attn_block_decode(lp["attn"], h, cfg, cache_k=ks,
                                          cache_v=vs, layer=i, pos=pos,
                                          window=window, ctx=ctx, ring=ring,
                                          active=active)
        x = x + a
        h = rms_norm(x, lp["ln2"], cfg.norm_eps)
        if "moe" in lp:
            f, _ = _moe_apply(cfg, lp["moe"], h, ctx)
        else:
            f = swiglu(h, lp["ffn"]["wi"], lp["ffn"]["wg"], lp["ffn"]["wo"])
        return (x + f, ks, vs), None

    (x, ks, vs), _ = jax.lax.scan(body, (x, cache["k"], cache["v"]),
                                  (layers, _layer_ids(cache["k"])))
    return x, {**cache, "k": ks, "v": vs, "pos": pos + 1}


def _layer_ids(stacked):
    """Index of each layer of a stacked (n_layers, ...) cache: the scan
    input beside the layer params when the cache rides the carry."""
    return jnp.arange(stacked.shape[0], dtype=jnp.int32)


# --- rwkv ------------------------------------------------------------------

def _rwkv_stack(cfg: ModelConfig, layers, x, states, *, decode: bool, remat="none"):
    """states: {"wkv": (L,B,H,D,D) f32, "tm": (L,B,D), "cm": (L,B,D)}."""

    def body(carry, inp):
        x = carry
        lp, wkv, tm_shift, cm_shift = inp
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        a, tm_last, wkv = T.rwkv_time_mix(lp["tm"], h, tm_shift, wkv, cfg,
                                          decode=decode)
        x = x + a
        h = rms_norm(x, lp["ln2"], cfg.norm_eps)
        c, cm_last = T.rwkv_channel_mix(lp["cm"], h, cm_shift)
        x = x + c
        return x, (wkv, tm_last, cm_last)

    if not decode:
        body = _maybe_remat(body, remat)
    x, (wkv, tm, cm) = jax.lax.scan(
        body, x, (layers, states["wkv"], states["tm"], states["cm"]))
    return x, {"wkv": wkv, "tm": tm, "cm": cm, "pos": states["pos"] + x.shape[1]}


# --- hymba -----------------------------------------------------------------

def _hymba_stack(cfg: ModelConfig, layers, x, positions, *, remat,
                 cache=None, decode=False, ctx=None):
    w = cfg.attn_window

    def fuse(lp, attn_out, ssm_out):
        a = rms_norm(attn_out, lp["mamba"]["norm_attn"], cfg.norm_eps)
        s = rms_norm(ssm_out, lp["mamba"]["norm_ssm"], cfg.norm_eps)
        return 0.5 * (a + s)

    if not decode and cache is None:
        def body(carry, lp):
            x = carry
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
            a, _ = T.attn_block(lp["attn"], h, cfg, positions=positions,
                                window=w, ctx=ctx)
            m, _, _ = T.mamba_path(lp["mamba"], h, cfg, ctx=ctx)
            x = x + fuse(lp, a, m)
            h = rms_norm(x, lp["ln2"], cfg.norm_eps)
            f = swiglu(h, lp["ffn"]["wi"], lp["ffn"]["wg"], lp["ffn"]["wo"])
            return x + f, jnp.float32(0.0)
        body = _maybe_remat(body, remat)
        x, _ = jax.lax.scan(body, x, layers)
        return x, None

    if not decode:  # prefill: emit window cache + ssm states
        s = x.shape[1]

        def body(carry, lp):
            x = carry
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
            a, (k, v) = T.attn_block(lp["attn"], h, cfg, positions=positions,
                                     window=w, ctx=ctx)
            m, conv_st, h_st = T.mamba_path(lp["mamba"], h, cfg, ctx=ctx)
            x = x + fuse(lp, a, m)
            hh = rms_norm(x, lp["ln2"], cfg.norm_eps)
            f = swiglu(hh, lp["ffn"]["wi"], lp["ffn"]["wg"], lp["ffn"]["wo"])
            # ring-buffer layout: slot i <- position p, p % w == i
            kw = jnp.roll(k[:, -w:], shift=s % w, axis=1)
            vw = jnp.roll(v[:, -w:], shift=s % w, axis=1)
            return x + f, (kw, vw, conv_st, h_st)

        x, (ks, vs, conv, hs) = jax.lax.scan(body, x, layers)
        return x, {"k": ks, "v": vs, "conv": conv, "h": hs,
                   "pos": jnp.int32(s)}

    # decode
    def body(carry, inp):
        x, ks, vs = carry
        lp, i, conv_st, h_st = inp
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        a, (ks, vs) = T.attn_block_decode(lp["attn"], h, cfg, cache_k=ks,
                                          cache_v=vs, layer=i,
                                          pos=cache["pos"], ring=True)
        m, conv_st, h_st = T.mamba_path(lp["mamba"], h, cfg,
                                        conv_state=conv_st, h_state=h_st,
                                        decode=True, ctx=ctx)
        x = x + fuse(lp, a, m)
        hh = rms_norm(x, lp["ln2"], cfg.norm_eps)
        f = swiglu(hh, lp["ffn"]["wi"], lp["ffn"]["wg"], lp["ffn"]["wo"])
        return (x + f, ks, vs), (conv_st, h_st)

    (x, ks, vs), (conv, hs) = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (layers, _layer_ids(cache["k"]), cache["conv"], cache["h"]))
    return x, {"k": ks, "v": vs, "conv": conv, "h": hs,
               "pos": cache["pos"] + 1}


# --- whisper (encdec) ------------------------------------------------------

def _whisper_encode(cfg: ModelConfig, params, frames):
    """frames: (B, enc_seq, D) stub embeddings -> encoder output."""
    from repro.models.layers import sinusoid_pos
    x = frames + sinusoid_pos(frames.shape[1], cfg.d_model)[None]

    def body(carry, lp):
        x = carry
        h = layer_norm(x, lp["ln1"], lp["ln1b"], cfg.norm_eps)
        q, k, v = T._qkv(lp["attn"], h, cfg)
        a = attn_lib.chunked_causal_attention(q, k, v, causal=False)
        a = matmul(a.reshape(*h.shape[:2], -1), lp["attn"]["wo"])
        x = x + a
        h = layer_norm(x, lp["ln2"], lp["ln2b"], cfg.norm_eps)
        f = gelu_ffn(h, lp["ffn"]["wi"], lp["ffn"]["bi"], lp["ffn"]["wo"],
                     lp["ffn"]["bo"])
        return x + f, None

    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return layer_norm(x, params["enc_ln"], params["enc_lnb"], cfg.norm_eps)


def _whisper_dec_stack(cfg: ModelConfig, layers, x, enc_out, positions, *,
                       remat, collect_cache=False, cache=None, decode=False,
                       ctx=None):
    def xattn(lp, h, eo):
        b_, s, _ = h.shape
        hh, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        q = matmul(h, lp["wq"]).reshape(b_, s, hh, hd)
        k = matmul(eo, lp["wk"]).reshape(b_, eo.shape[1], kv, hd)
        v = matmul(eo, lp["wv"]).reshape(b_, eo.shape[1], kv, hd)
        a = attn_lib.chunked_causal_attention(q, k, v, causal=False)
        return matmul(a.reshape(b_, s, -1), lp["wo"]), (k, v)

    def xattn_cached(lp, h, ck, cv):
        b_ = h.shape[0]
        hh, hd = cfg.n_heads, cfg.head_dim
        q = matmul(h, lp["wq"]).reshape(b_, 1, hh, hd)
        a = attn_lib.decode_attention(q, ck, cv, ck.shape[1])
        return matmul(a.reshape(b_, 1, -1), lp["wo"])

    if not decode:
        def body(carry, lp):
            x = carry
            h = layer_norm(x, lp["ln1"], lp["ln1b"], cfg.norm_eps)
            q, k, v = T._qkv(lp["attn"], h, cfg)
            a = attn_lib.chunked_causal_attention(q, k, v)
            a = matmul(a.reshape(*h.shape[:2], -1), lp["attn"]["wo"])
            x = x + a
            h = layer_norm(x, lp["lnx"], lp["lnxb"], cfg.norm_eps)
            xa, (xk, xv) = xattn(lp["xattn"], h, enc_out)
            x = x + xa
            h = layer_norm(x, lp["ln2"], lp["ln2b"], cfg.norm_eps)
            f = gelu_ffn(h, lp["ffn"]["wi"], lp["ffn"]["bi"],
                         lp["ffn"]["wo"], lp["ffn"]["bo"])
            ys = (k, v, xk, xv) if collect_cache else None
            return x + f, ys

        if not collect_cache:
            body = _maybe_remat(body, remat)
        x, ys = jax.lax.scan(body, x, layers)
        return x, ys

    def body(carry, inp):
        x, ks, vs = carry
        lp, i, xk, xv = inp
        h = layer_norm(x, lp["ln1"], lp["ln1b"], cfg.norm_eps)
        a, (ks, vs) = T.attn_block_decode(lp["attn"], h, cfg, cache_k=ks,
                                          cache_v=vs, layer=i,
                                          pos=cache["pos"], rope=False,
                                          ctx=ctx)
        x = x + a
        h = layer_norm(x, lp["lnx"], lp["lnxb"], cfg.norm_eps)
        x = x + xattn_cached(lp["xattn"], h, xk, xv)
        h = layer_norm(x, lp["ln2"], lp["ln2b"], cfg.norm_eps)
        f = gelu_ffn(h, lp["ffn"]["wi"], lp["ffn"]["bi"], lp["ffn"]["wo"],
                     lp["ffn"]["bo"])
        return (x + f, ks, vs), None

    (x, ks, vs), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (layers, _layer_ids(cache["k"]), cache["xk"], cache["xv"]))
    return x, {"k": ks, "v": vs, "xk": cache["xk"], "xv": cache["xv"],
               "pos": cache["pos"] + 1}


# ===========================================================================
# cache construction
# ===========================================================================

def _mk(shape, dtype, abstract):
    return (jax.ShapeDtypeStruct(shape, dtype) if abstract
            else jnp.zeros(shape, dtype))


def make_cache(cfg: ModelConfig, batch: int, cache_len: int,
               abstract: bool = False) -> PyTree:
    """Decode-state pytree per family. cache_len = max context (the shape
    cell's seq_len); for hybrid the attention part only keeps the window."""
    L, kv, hd, d = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    pos = (jax.ShapeDtypeStruct((), jnp.int32) if abstract
           else jnp.zeros((), jnp.int32))
    if cfg.family in ("dense", "vlm"):
        return {"k": _mk((L, batch, cache_len, kv, hd), PARAM_DTYPE, abstract),
                "v": _mk((L, batch, cache_len, kv, hd), PARAM_DTYPE, abstract),
                "pos": pos}
    if cfg.family == "moe":
        lm = L - cfg.first_k_dense
        c = {"k": _mk((lm, batch, cache_len, kv, hd), PARAM_DTYPE, abstract),
             "v": _mk((lm, batch, cache_len, kv, hd), PARAM_DTYPE, abstract),
             "pos": pos}
        if cfg.first_k_dense:
            kd = cfg.first_k_dense
            c["dk"] = _mk((kd, batch, cache_len, kv, hd), PARAM_DTYPE, abstract)
            c["dv"] = _mk((kd, batch, cache_len, kv, hd), PARAM_DTYPE, abstract)
        return c
    if cfg.family == "ssm":
        h = d // cfg.rwkv_head_dim
        rhd = cfg.rwkv_head_dim
        return {"wkv": _mk((L, batch, h, rhd, rhd), jnp.float32, abstract),
                "tm": _mk((L, batch, d), PARAM_DTYPE, abstract),
                "cm": _mk((L, batch, d), PARAM_DTYPE, abstract),
                "pos": pos}
    if cfg.family == "hybrid":
        w = cfg.attn_window
        ci = 2 * d
        return {"k": _mk((L, batch, w, kv, hd), PARAM_DTYPE, abstract),
                "v": _mk((L, batch, w, kv, hd), PARAM_DTYPE, abstract),
                "conv": _mk((L, batch, mamba_lib.CONV_K - 1, ci), PARAM_DTYPE, abstract),
                "h": _mk((L, batch, ci, cfg.ssm_state), jnp.float32, abstract),
                "pos": pos}
    if cfg.family == "encdec":
        return {"k": _mk((L, batch, cache_len, kv, hd), PARAM_DTYPE, abstract),
                "v": _mk((L, batch, cache_len, kv, hd), PARAM_DTYPE, abstract),
                "xk": _mk((L, batch, cfg.enc_seq, kv, hd), PARAM_DTYPE, abstract),
                "xv": _mk((L, batch, cfg.enc_seq, kv, hd), PARAM_DTYPE, abstract),
                "pos": pos}
    raise ValueError(cfg.family)


def cache_logical_axes(cfg: ModelConfig) -> PyTree:
    """Logical axes for the cache pytree (mirrors make_cache's structure)."""
    kvax = ("layers", "batch", "kv_seq", "kv_heads", None)
    if cfg.family in ("dense", "vlm"):
        return {"k": kvax, "v": kvax, "pos": ()}
    if cfg.family == "moe":
        c = {"k": kvax, "v": kvax, "pos": ()}
        if cfg.first_k_dense:
            c["dk"] = kvax
            c["dv"] = kvax
        return c
    if cfg.family == "ssm":
        return {"wkv": ("layers", "batch", "heads", None, None),
                "tm": ("layers", "batch", "d_model"),
                "cm": ("layers", "batch", "d_model"),
                "pos": ()}
    if cfg.family == "hybrid":
        return {"k": ("layers", "batch", None, "kv_heads", None),
                "v": ("layers", "batch", None, "kv_heads", None),
                "conv": ("layers", "batch", None, "heads"),
                "h": ("layers", "batch", "heads", None),
                "pos": ()}
    if cfg.family == "encdec":
        return {"k": kvax, "v": kvax,
                "xk": ("layers", "batch", None, "kv_heads", None),
                "xv": ("layers", "batch", None, "kv_heads", None),
                "pos": ()}
    raise ValueError(cfg.family)


# ===========================================================================
# build_model
# ===========================================================================

def build_model(cfg: ModelConfig) -> Model:
    """Assemble a `Model` for one config: pure, jit-friendly apply
    functions (init_params / loss_fn / prefill / decode_step /
    prefill_into_slot / init_cache) plus the logical-axis metadata the
    sharding engine consumes (param_axes, cache_axes). One call covers
    every family — dense / moe / ssm / hybrid / encdec / vlm — selected
    by cfg.family.

    Example::

        import jax, repro
        from repro.configs.base import get_config, reduce_config
        cfg = reduce_config(get_config("qwen2-1.5b"), d_model=64, vocab=128)
        model = repro.build_model(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        logits, cache = model.prefill(params, {"tokens": jax.numpy.ones(
            (1, 8), jax.numpy.int32)})
    """
    param_fn = T.build_param_fn(cfg)

    from repro.models.layers import build_params

    def init_params(rng):
        tree, _ = build_params(param_fn, rng, abstract=False)
        return tree

    def abstract_params():
        tree, _ = build_params(param_fn, None, abstract=True)
        return tree

    _, param_axes = build_params(param_fn, None, abstract=True)

    def _logits(params, x):
        x = (layer_norm(x, params["final_norm"], params["final_normb"],
                        cfg.norm_eps)
             if cfg.family == "encdec"
             else rms_norm(x, params["final_norm"], cfg.norm_eps))
        table = params["embed"].T if cfg.tie_embeddings else params["head"]
        return lm_logits(x, table)

    # ---- backbone forward (returns final hidden states) -------------------

    def _backbone_train(params, batch, ctx):
        if cfg.family == "encdec":
            enc_out = _whisper_encode(cfg, params, batch["frames"])
            x = embed(batch["tokens"], params["embed"])
            x = x + params["dec_pos"][: x.shape[1]][None].astype(x.dtype)
            x, _ = _whisper_dec_stack(cfg, params["dec_layers"], x, enc_out,
                                      None, remat=cfg.remat)
            return x, jnp.float32(0.0)

        x = embed(batch["tokens"], params["embed"])
        if cfg.family == "vlm":
            x = jnp.concatenate([batch["vis"].astype(x.dtype), x], axis=1)
        positions = jnp.arange(x.shape[1])

        if cfg.family in ("dense", "vlm"):
            x, aux = _dense_stack(cfg, params["layers"], x, positions,
                                  remat=cfg.remat, moe=False, ctx=ctx)
        elif cfg.family == "moe":
            aux = jnp.float32(0.0)
            if cfg.first_k_dense:
                x, _ = _dense_stack(cfg, params["dense_layers"], x, positions,
                                    remat=cfg.remat, moe=False)
            x, aux2 = _dense_stack(cfg, params["layers"], x, positions,
                                   remat=cfg.remat, moe=True, ctx=ctx)
            aux = aux + aux2
        elif cfg.family == "ssm":
            L, b_, d = cfg.n_layers, x.shape[0], cfg.d_model
            h = d // cfg.rwkv_head_dim
            states = {"wkv": jnp.zeros((L, b_, h, cfg.rwkv_head_dim,
                                        cfg.rwkv_head_dim), jnp.float32),
                      "tm": jnp.zeros((L, b_, d), x.dtype),
                      "cm": jnp.zeros((L, b_, d), x.dtype),
                      "pos": jnp.int32(0)}
            x, _ = _rwkv_stack(cfg, params["layers"], x, states, decode=False,
                               remat=cfg.remat)
            aux = jnp.float32(0.0)
        elif cfg.family == "hybrid":
            x, _ = _hymba_stack(cfg, params["layers"], x, positions,
                                remat=cfg.remat, ctx=ctx)
            aux = jnp.float32(0.0)
        else:
            raise ValueError(cfg.family)
        return x, aux

    # ---- loss --------------------------------------------------------------

    def loss_fn(params, batch, ctx: Optional[DistCtx] = None):
        x, aux = _backbone_train(params, batch, ctx)
        if cfg.family == "vlm":  # loss only on the text positions
            x = x[:, batch["vis"].shape[1]:]
        labels = batch["labels"]

        # chunked cross-entropy: a (B,S,V) f32 logits tensor at 200k vocab
        # and S=4096 would be the largest activation in the model — the
        # xent is evaluated per sequence chunk inside a scan (+checkpoint)
        # so the transient stays (B, chunk, V).
        s = x.shape[1]
        chunk = s
        for c in (512, 256, 128, 64):
            if s % c == 0 and s > c:
                chunk = c
                break

        def xent_chunk(x_c, labels_c):
            logits = _logits(params, x_c)
            mask = (labels_c >= 0).astype(jnp.float32)
            per_tok = softmax_xent(logits, jnp.maximum(labels_c, 0))
            return (per_tok * mask).sum(), mask.sum()

        if chunk == s:
            lsum, msum = xent_chunk(x, labels)
        else:
            n = s // chunk
            xc = x.reshape(x.shape[0], n, chunk, -1).swapaxes(0, 1)
            lc = labels.reshape(labels.shape[0], n, chunk).swapaxes(0, 1)

            def body(carry, inp):
                ls, ms = carry
                dl, dm = jax.checkpoint(xent_chunk)(*inp)
                return (ls + dl, ms + dm), None

            (lsum, msum), _ = jax.lax.scan(
                body, (jnp.float32(0.0), jnp.float32(0.0)), (xc, lc))

        ntok = jnp.maximum(msum, 1.0)
        loss = lsum / ntok
        total = loss + cfg.router_aux_coef * aux
        return total, {"loss": loss, "aux": aux, "ntokens": ntok}

    # ---- prefill -------------------------------------------------------------

    def prefill(params, batch, ctx: Optional[DistCtx] = None, *,
                last_index=None):
        """Full forward; returns (last-token logits, cache).

        batch may carry "pad_lens" — a (B,) int32 count of LEFT pad tokens
        per row (attention families only). Positions then start at 0 on
        each row's first real token and pad key/value columns are masked
        out of every softmax, so a left-padded row produces bit-identical
        final-token logits to the unpadded prompt. MoE caveat: pad tokens
        get zero router weight (moe_ffn token_valid) and can't claim
        expert capacity, but capacity itself stays shape-derived from the
        PADDED token count — when the unpadded batch already overflows an
        expert's capacity, padding raises the ceiling and real-token drops
        can differ, so exact equality there additionally requires the
        padded and unpadded counts to land on the same capacity.

        last_index: optional (traced) index into the sequence axis; the
        returned logits are taken there instead of at -1. Used by
        prefill_into_slot, where a right-padded row's last *real* token is
        not the last position."""

        def _last(x):
            if last_index is None:
                return x[:, -1:]
            return jax.lax.dynamic_slice_in_dim(x, last_index, 1, axis=1)

        pad_lens = batch.get("pad_lens")
        if pad_lens is not None:
            assert cfg.family in ("dense", "moe"), (
                "pad_lens (left-padded prefill) is only defined for pure "
                "attention stacks; recurrent state (ssm/hybrid) consumes "
                f"pads and vlm/encdec prepend non-text tokens: {cfg.family}")

        if cfg.family == "encdec":
            enc_out = _whisper_encode(cfg, params, batch["frames"])
            x = embed(batch["tokens"], params["embed"])
            s = x.shape[1]
            x = x + params["dec_pos"][:s][None].astype(x.dtype)
            x, (ks, vs, xks, xvs) = _whisper_dec_stack(
                cfg, params["dec_layers"], x, enc_out, None,
                remat="none", collect_cache=True)
            cache = {"k": ks, "v": vs, "xk": xks, "xv": xvs,
                     "pos": jnp.int32(s)}
            return _logits(params, _last(x)), cache

        x = embed(batch["tokens"], params["embed"])
        if cfg.family == "vlm":
            x = jnp.concatenate([batch["vis"].astype(x.dtype), x], axis=1)
        s = x.shape[1]
        if pad_lens is None:
            positions = jnp.arange(s)
            kv_valid = None
        else:
            positions = jnp.maximum(
                jnp.arange(s)[None, :] - pad_lens[:, None], 0)
            kv_valid = jnp.arange(s)[None, :] >= pad_lens[:, None]

        if cfg.family in ("dense", "vlm"):
            x, ks, vs = _dense_prefill_stack(cfg, params["layers"], x,
                                             positions, moe=False, ctx=ctx,
                                             kv_valid=kv_valid)
            cache = {"k": ks, "v": vs, "pos": jnp.int32(s)}
        elif cfg.family == "moe":
            cache = {}
            if cfg.first_k_dense:
                x, dk, dv = _dense_prefill_stack(cfg, params["dense_layers"],
                                                 x, positions, moe=False,
                                                 kv_valid=kv_valid)
                cache.update({"dk": dk, "dv": dv})
            x, ks, vs = _dense_prefill_stack(cfg, params["layers"], x,
                                             positions, moe=True, ctx=ctx,
                                             kv_valid=kv_valid)
            cache.update({"k": ks, "v": vs, "pos": jnp.int32(s)})
        elif cfg.family == "ssm":
            L, b_, d = cfg.n_layers, x.shape[0], cfg.d_model
            h = d // cfg.rwkv_head_dim
            states = {"wkv": jnp.zeros((L, b_, h, cfg.rwkv_head_dim,
                                        cfg.rwkv_head_dim), jnp.float32),
                      "tm": jnp.zeros((L, b_, d), x.dtype),
                      "cm": jnp.zeros((L, b_, d), x.dtype),
                      "pos": jnp.int32(0)}
            x, cache = _rwkv_stack(cfg, params["layers"], x, states,
                                   decode=False)
        elif cfg.family == "hybrid":
            x, cache = _hymba_stack(cfg, params["layers"], x, positions,
                                    remat="none", cache={}, decode=False,
                                    ctx=ctx)
        else:
            raise ValueError(cfg.family)
        return _logits(params, _last(x)), cache

    # ---- decode --------------------------------------------------------------

    def decode_step(params, cache, tokens, ctx: Optional[DistCtx] = None,
                    active=None):
        """tokens: (B, 1). Returns (logits (B,1,V) f32, new cache).

        cache["pos"] may be a scalar (lockstep decode) or a (B,) vector
        (slot scheduler: every row at its own offset). active: optional
        (B,) bool of the rows whose logits the caller uses; the dense
        stacks' attention may skip the others' cache lines, and their
        logits are then meaningless (T.attn_block_decode)."""
        x = embed(tokens, params["embed"])
        if cfg.family == "encdec":
            pe = params["dec_pos"][cache["pos"]].astype(x.dtype)
            x = x + (pe[:, None] if pe.ndim == 2 else pe[None, None])
            x, cache = _whisper_dec_stack(cfg, params["dec_layers"], x, None,
                                          None, remat="none", cache=cache,
                                          decode=True, ctx=ctx)
        elif cfg.family in ("dense", "vlm"):
            x, cache = _dense_decode_stack(cfg, params["layers"], x, cache,
                                           ctx=ctx, active=active)
        elif cfg.family == "moe":
            pos = cache["pos"]
            if cfg.first_k_dense:
                dsub = {"k": cache["dk"], "v": cache["dv"], "pos": pos}
                x, dsub = _dense_decode_stack(cfg, params["dense_layers"], x,
                                              dsub, ctx=ctx, active=active)
                cache = {**cache, "dk": dsub["k"], "dv": dsub["v"]}
            sub = {"k": cache["k"], "v": cache["v"], "pos": pos}
            x, sub = _dense_decode_stack(cfg, params["layers"], x, sub,
                                         ctx=ctx, active=active)
            cache = {**cache, "k": sub["k"], "v": sub["v"], "pos": pos + 1}
        elif cfg.family == "ssm":
            x, cache = _rwkv_stack(cfg, params["layers"], x, cache,
                                   decode=True)
        elif cfg.family == "hybrid":
            x, cache = _hymba_stack(cfg, params["layers"], x, None,
                                    remat="none", cache=cache, decode=True,
                                    ctx=ctx)
        else:
            raise ValueError(cfg.family)
        return _logits(params, x), cache

    # ---- speculative verify (multi-position decode) --------------------------

    def decode_verify(params, cache, tokens, ctx: Optional[DistCtx] = None):
        """tokens: (B, Q) — per row, the last committed token followed by
        Q-1 draft candidates. Returns (logits (B,Q,V) f32, new cache):
        logits[:, j] is the target distribution AFTER consuming tokens[:, j],
        exactly what Q sequential decode_step calls would have produced.

        cache["pos"] must be the (B,) per-row slot-scheduler layout; row
        writes land at pos..pos+Q-1 and pos advances by Q (the speculated
        tip). The caller rolls pos back to the last ACCEPTED line after the
        accept decision — see serve/spec.py for the contract."""
        assert cfg.family == "dense", (
            "decode_verify is only defined for pure-attention decoder "
            f"stacks (position-batchable step): {cfg.family}")
        x = embed(tokens, params["embed"])
        pos = cache["pos"]

        def body(carry, inp):
            x, ks, vs = carry
            lp, i = inp
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
            a, (ks, vs) = T.attn_block_decode_k(lp["attn"], h, cfg,
                                                cache_k=ks, cache_v=vs,
                                                layer=i, pos=pos)
            x = x + a
            h = rms_norm(x, lp["ln2"], cfg.norm_eps)
            f = swiglu(h, lp["ffn"]["wi"], lp["ffn"]["wg"], lp["ffn"]["wo"])
            return (x + f, ks, vs), None

        (x, ks, vs), _ = jax.lax.scan(
            body, (x, cache["k"], cache["v"]),
            (params["layers"], _layer_ids(cache["k"])))
        new_cache = {"k": ks, "v": vs, "pos": pos + tokens.shape[1]}
        return _logits(params, x), new_cache

    # ---- slot refill (continuous batching) -----------------------------------

    def prefill_into_slot(params, cache, slot, batch, prompt_len,
                          ctx: Optional[DistCtx] = None):
        """Prefill ONE request (batch row of size 1) and overwrite `slot`'s
        cache lines in a batched decode cache, so a new request joins a
        mid-flight batch without retracing or disturbing its batch-mates.

        cache: a batched decode cache whose "pos" is a (B,) per-row vector
          (the slot scheduler's layout — see serve/engine.py).
        slot: (traced) row index to overwrite.
        batch: single-row prefill inputs; "tokens" is (1, P). P may exceed
          the real prompt (right padding to a shape bucket): pad lines land
          beyond prompt_len, stay masked by the per-row length, and are
          overwritten as decode advances. For ssm/hybrid (recurrent state
          folds every token in) and moe (capacity dispatch is token-count
          sensitive) P must equal the real prompt length.
        prompt_len: (traced) number of valid leading positions in the row
          — the slot's pos after admission; logits are taken at
          prompt_len - 1 (the last real token).

        Returns (logits (1,1,V), new cache). Every cache leaf has layout
        (layers, batch, ...), so the write is one dynamic_update_slice at
        (0, slot, 0, ...) per leaf, in place when the caller donates the
        cache (the serve engine does).
        """
        logits, row = prefill(params, batch, ctx, last_index=prompt_len - 1)
        new = {}
        for key, full in cache.items():
            if key == "pos":
                new[key] = full.at[slot].set(
                    jnp.asarray(prompt_len, full.dtype))
                continue
            upd = row[key].astype(full.dtype)
            starts = (0, slot) + (0,) * (full.ndim - 2)
            new[key] = jax.lax.dynamic_update_slice(full, upd, starts)
        return logits, new

    # ---- prefix-continue prefill (paged K/V prefix reuse) --------------------

    def prefill_continue(params, cache, slot, batch, start, n_real,
                         ctx: Optional[DistCtx] = None):
        """Prefill ONLY the unseen suffix of a request whose first `start`
        prompt positions already sit in `slot`'s cache rows (restored from
        shared prefix pages — serve/kvcache.py). Dense family only: the
        suffix hidden states depend on the prefix exclusively through the
        cached K/V (causal attention), so continuing from restored lines is
        bit-identical to a cold full-prompt prefill.

        batch["tokens"]: (1, S) suffix tokens, right-padded to a shape
        bucket like prefill_into_slot (pad lines land beyond the real
        suffix and stay masked by the per-row pos). start: (traced) count
        of already-cached prompt positions. n_real: (traced) real suffix
        length; logits are taken at suffix index n_real - 1 and the slot's
        pos becomes start + n_real.
        """
        assert cfg.family == "dense", (
            "prefill_continue is only defined for pure-attention decoder "
            f"stacks (suffix-separable prefill): {cfg.family}")
        x = embed(batch["tokens"], params["embed"])
        s = x.shape[1]
        positions = start + jnp.arange(s)

        def body(carry, inp):
            x, ks, vs = carry
            lp, i = inp
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
            a, (ks, vs) = T.attn_block_continue(
                lp["attn"], h, cfg, cache_k=ks, cache_v=vs, layer=i,
                slot=slot, start=start, positions=positions, ctx=ctx)
            x = x + a
            h = rms_norm(x, lp["ln2"], cfg.norm_eps)
            f = swiglu(h, lp["ffn"]["wi"], lp["ffn"]["wg"], lp["ffn"]["wo"])
            return (x + f, ks, vs), None

        (x, ks, vs), _ = jax.lax.scan(
            body, (x, cache["k"], cache["v"]),
            (params["layers"], _layer_ids(cache["k"])))
        new_cache = {"k": ks, "v": vs,
                     "pos": cache["pos"].at[slot].set(
                         jnp.asarray(start + n_real, cache["pos"].dtype))}
        last = jax.lax.dynamic_slice_in_dim(x, n_real - 1, 1, axis=1)
        return _logits(params, last), new_cache

    return Model(
        cfg=cfg,
        param_axes=param_axes,
        init_params=init_params,
        abstract_params=abstract_params,
        loss_fn=loss_fn,
        prefill=prefill,
        decode_step=decode_step,
        prefill_into_slot=prefill_into_slot,
        init_cache=functools.partial(make_cache, cfg),
        cache_axes=functools.partial(cache_logical_axes, cfg),
        prefill_continue=(prefill_continue if cfg.family == "dense"
                          else None),
        decode_verify=(decode_verify if cfg.family == "dense" else None),
    )
