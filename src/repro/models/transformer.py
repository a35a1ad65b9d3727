"""The model core: one scanned-layer decoder covering all assigned families.

Families and their block structure (cfg.family):
  dense / vlm : [RMSNorm → GQA+RoPE → RMSNorm → SwiGLU] ×L
  moe         : same, FFN replaced by routed experts (+shared); optional
                leading dense layers (deepseek first_k_dense)
  ssm         : RWKV6 [time-mix (WKV6) → channel-mix] ×L
  hybrid      : Hymba [RMSNorm → (GQA-SWA ∥ Mamba) fused → RMSNorm → SwiGLU] ×L
  encdec      : Whisper [enc: LN → MHA → LN → GELU-FFN] ×Le then
                [dec: LN → causal MHA → LN → cross MHA → LN → GELU-FFN] ×Ld

All layer stacks are `jax.lax.scan`s over stacked params (leading "layers"
axis) so a 64-layer model lowers to one compact while-loop — essential for
the 40-cell × 2-mesh dry-run compile budget.

Three entry points per model (see ModelConfig shapes):
  loss_fn(params, batch)                 → train_4k
  prefill(params, batch)                 → prefill_32k (returns cache+logits)
  decode_step(params, cache, tokens)     → decode_32k / long_500k
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro import backend
from repro.configs.base import ModelConfig
from repro.models import attention as attn_lib
from repro.models import mamba as mamba_lib
from repro.models import rwkv6 as rwkv_lib
from repro.models.layers import (
    PARAM_DTYPE, DistCtx, ParamBuilder, apply_rope, isolate_exact_tp, matmul,
    matmul_rp,
)

PyTree = Any


# ===========================================================================
# parameter construction
# ===========================================================================

def _attn_params(b: ParamBuilder, pre: str, L: int, cfg: ModelConfig,
                 d: Optional[int] = None) -> Dict:
    d = d or cfg.d_model
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": b.param(f"{pre}/wq", (L, d, h * hd), ("layers", "d_model", "heads")),
        "wk": b.param(f"{pre}/wk", (L, d, kv * hd), ("layers", "d_model", "kv_heads")),
        "wv": b.param(f"{pre}/wv", (L, d, kv * hd), ("layers", "d_model", "kv_heads")),
        "wo": b.param(f"{pre}/wo", (L, h * hd, d), ("layers", "heads", "d_model")),
    }
    if cfg.qkv_bias:
        p["bq"] = b.param(f"{pre}/bq", (L, h * hd), ("layers", "heads"), "zeros")
        p["bk"] = b.param(f"{pre}/bk", (L, kv * hd), ("layers", "kv_heads"), "zeros")
        p["bv"] = b.param(f"{pre}/bv", (L, kv * hd), ("layers", "kv_heads"), "zeros")
    return p


def _ffn_params(b: ParamBuilder, pre: str, L: int, d: int, f: int,
                act: str) -> Dict:
    if act == "swiglu":
        return {
            "wi": b.param(f"{pre}/wi", (L, d, f), ("layers", "d_model", "d_ff")),
            "wg": b.param(f"{pre}/wg", (L, d, f), ("layers", "d_model", "d_ff")),
            "wo": b.param(f"{pre}/wo", (L, f, d), ("layers", "d_ff", "d_model")),
        }
    return {
        "wi": b.param(f"{pre}/wi", (L, d, f), ("layers", "d_model", "d_ff")),
        "bi": b.param(f"{pre}/bi", (L, f), ("layers", "d_ff"), "zeros"),
        "wo": b.param(f"{pre}/wo", (L, f, d), ("layers", "d_ff", "d_model")),
        "bo": b.param(f"{pre}/bo", (L, d), ("layers", "d_model"), "zeros"),
    }


def _moe_params(b: ParamBuilder, pre: str, L: int, cfg: ModelConfig) -> Dict:
    d, e, f = cfg.d_model, cfg.n_experts, (cfg.moe_d_ff or cfg.d_ff)
    p = {
        "router": b.param(f"{pre}/router", (L, d, e), ("layers", "d_model", None)),
        "wi": b.param(f"{pre}/wi", (L, e, d, f), ("layers", "experts", "d_model", "d_ff")),
        "wg": b.param(f"{pre}/wg", (L, e, d, f), ("layers", "experts", "d_model", "d_ff")),
        "wo": b.param(f"{pre}/wo", (L, e, f, d), ("layers", "experts", "d_ff", "d_model")),
    }
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        p["shared_wi"] = b.param(f"{pre}/shared_wi", (L, d, fs), ("layers", "d_model", "d_ff"))
        p["shared_wg"] = b.param(f"{pre}/shared_wg", (L, d, fs), ("layers", "d_model", "d_ff"))
        p["shared_wo"] = b.param(f"{pre}/shared_wo", (L, fs, d), ("layers", "d_ff", "d_model"))
    return p


def _rwkv_params(b: ParamBuilder, pre: str, L: int, cfg: ModelConfig) -> Dict:
    d = cfg.d_model
    h = d // cfg.rwkv_head_dim
    hd = cfg.rwkv_head_dim
    km, kd = rwkv_lib.LORA_MIX, rwkv_lib.LORA_DECAY
    f = cfg.d_ff
    return {
        "ln1": b.param(f"{pre}/ln1", (L, d), ("layers", "d_model"), "ones"),
        "ln2": b.param(f"{pre}/ln2", (L, d), ("layers", "d_model"), "ones"),
        "tm": {
            "mu_x": b.param(f"{pre}/tm/mu_x", (L, d), ("layers", "d_model")),
            # r,k,v,w,g stream mus
            "mu_5": b.param(f"{pre}/tm/mu_5", (L, 5, d), ("layers", None, "d_model")),
            "lora_a": b.param(f"{pre}/tm/lora_a", (L, d, 5 * km), ("layers", "d_model", None)),
            "lora_b": b.param(f"{pre}/tm/lora_b", (L, 5, km, d), ("layers", None, None, "d_model")),
            "td_a": b.param(f"{pre}/tm/td_a", (L, d, kd), ("layers", "d_model", None)),
            "td_b": b.param(f"{pre}/tm/td_b", (L, kd, d), ("layers", None, "d_model")),
            "w0": b.param(f"{pre}/tm/w0", (L, d), ("layers", "d_model")),
            "u": b.param(f"{pre}/tm/u", (L, h, hd), ("layers", "heads", None)),
            "wr": b.param(f"{pre}/tm/wr", (L, d, d), ("layers", "d_model", "heads")),
            "wk": b.param(f"{pre}/tm/wk", (L, d, d), ("layers", "d_model", "heads")),
            "wv": b.param(f"{pre}/tm/wv", (L, d, d), ("layers", "d_model", "heads")),
            "wg": b.param(f"{pre}/tm/wg", (L, d, d), ("layers", "d_model", "heads")),
            "wo": b.param(f"{pre}/tm/wo", (L, d, d), ("layers", "heads", "d_model")),
            "ln_x": b.param(f"{pre}/tm/ln_x", (L, d), ("layers", "d_model"), "ones"),
        },
        "cm": {
            "mu_k": b.param(f"{pre}/cm/mu_k", (L, d), ("layers", "d_model")),
            "mu_r": b.param(f"{pre}/cm/mu_r", (L, d), ("layers", "d_model")),
            "wk": b.param(f"{pre}/cm/wk", (L, d, f), ("layers", "d_model", "d_ff")),
            "wv": b.param(f"{pre}/cm/wv", (L, f, d), ("layers", "d_ff", "d_model")),
            "wr": b.param(f"{pre}/cm/wr", (L, d, d), ("layers", "d_model", "d_model")),
        },
    }


def _mamba_params(b: ParamBuilder, pre: str, L: int, cfg: ModelConfig) -> Dict:
    d = cfg.d_model
    ci = 2 * d                      # d_inner
    n = cfg.ssm_state
    dt_rank = max(1, d // 16)
    return {
        "in_proj": b.param(f"{pre}/in_proj", (L, d, 2 * ci), ("layers", "d_model", "heads")),
        "conv_w": b.param(f"{pre}/conv_w", (L, mamba_lib.CONV_K, ci), ("layers", None, "heads")),
        "x_proj": b.param(f"{pre}/x_proj", (L, ci, dt_rank + 2 * n), ("layers", "heads", None)),
        "dt_proj": b.param(f"{pre}/dt_proj", (L, dt_rank, ci), ("layers", None, "heads")),
        # dt ~= softplus(-4.6) ~= 0.01 at init (standard mamba dt range)
        "dt_bias": b.param(f"{pre}/dt_bias", (L, ci), ("layers", "heads"), "const:-4.6"),
        "a_log": b.param(f"{pre}/a_log", (L, ci, n), ("layers", "heads", None), "a_log"),
        "d": b.param(f"{pre}/d", (L, ci), ("layers", "heads"), "ones"),
        "out_proj": b.param(f"{pre}/out_proj", (L, ci, d), ("layers", "heads", "d_model")),
        "norm_attn": b.param(f"{pre}/norm_attn", (L, d), ("layers", "d_model"), "ones"),
        "norm_ssm": b.param(f"{pre}/norm_ssm", (L, d), ("layers", "d_model"), "ones"),
    }


def build_param_fn(cfg: ModelConfig) -> Callable[[ParamBuilder], Dict]:
    """Returns a builder fn producing the full param tree for cfg."""
    d, v, L = cfg.d_model, cfg.vocab_size, cfg.n_layers

    def fn(b: ParamBuilder) -> Dict:
        p: Dict = {"embed": b.param("embed", (v, d), ("vocab", "d_model"))}

        if cfg.family in ("dense", "vlm"):
            p["layers"] = {
                "ln1": b.param("layers/ln1", (L, d), ("layers", "d_model"), "ones"),
                "ln2": b.param("layers/ln2", (L, d), ("layers", "d_model"), "ones"),
                "attn": _attn_params(b, "layers/attn", L, cfg),
                "ffn": _ffn_params(b, "layers/ffn", L, d, cfg.d_ff, cfg.act),
            }
        elif cfg.family == "moe":
            k = cfg.first_k_dense
            if k:
                p["dense_layers"] = {
                    "ln1": b.param("dense_layers/ln1", (k, d), ("layers", "d_model"), "ones"),
                    "ln2": b.param("dense_layers/ln2", (k, d), ("layers", "d_model"), "ones"),
                    "attn": _attn_params(b, "dense_layers/attn", k, cfg),
                    "ffn": _ffn_params(b, "dense_layers/ffn", k, d, cfg.d_ff, cfg.act),
                }
            lm = L - k
            p["layers"] = {
                "ln1": b.param("layers/ln1", (lm, d), ("layers", "d_model"), "ones"),
                "ln2": b.param("layers/ln2", (lm, d), ("layers", "d_model"), "ones"),
                "attn": _attn_params(b, "layers/attn", lm, cfg),
                "moe": _moe_params(b, "layers/moe", lm, cfg),
            }
        elif cfg.family == "ssm":
            p["layers"] = _rwkv_params(b, "layers", L, cfg)
        elif cfg.family == "hybrid":
            p["layers"] = {
                "ln1": b.param("layers/ln1", (L, d), ("layers", "d_model"), "ones"),
                "ln2": b.param("layers/ln2", (L, d), ("layers", "d_model"), "ones"),
                "attn": _attn_params(b, "layers/attn", L, cfg),
                "mamba": _mamba_params(b, "layers/mamba", L, cfg),
                "ffn": _ffn_params(b, "layers/ffn", L, d, cfg.d_ff, cfg.act),
            }
        elif cfg.family == "encdec":
            Le = cfg.n_enc_layers
            p["enc_layers"] = {
                "ln1": b.param("enc_layers/ln1", (Le, d), ("layers", "d_model"), "ones"),
                "ln1b": b.param("enc_layers/ln1b", (Le, d), ("layers", "d_model"), "zeros"),
                "ln2": b.param("enc_layers/ln2", (Le, d), ("layers", "d_model"), "ones"),
                "ln2b": b.param("enc_layers/ln2b", (Le, d), ("layers", "d_model"), "zeros"),
                "attn": _attn_params(b, "enc_layers/attn", Le, cfg),
                "ffn": _ffn_params(b, "enc_layers/ffn", Le, d, cfg.d_ff, "gelu"),
            }
            p["dec_layers"] = {
                "ln1": b.param("dec_layers/ln1", (L, d), ("layers", "d_model"), "ones"),
                "ln1b": b.param("dec_layers/ln1b", (L, d), ("layers", "d_model"), "zeros"),
                "lnx": b.param("dec_layers/lnx", (L, d), ("layers", "d_model"), "ones"),
                "lnxb": b.param("dec_layers/lnxb", (L, d), ("layers", "d_model"), "zeros"),
                "ln2": b.param("dec_layers/ln2", (L, d), ("layers", "d_model"), "ones"),
                "ln2b": b.param("dec_layers/ln2b", (L, d), ("layers", "d_model"), "zeros"),
                "attn": _attn_params(b, "dec_layers/attn", L, cfg),
                "xattn": _attn_params(b, "dec_layers/xattn", L, cfg),
                "ffn": _ffn_params(b, "dec_layers/ffn", L, d, cfg.d_ff, "gelu"),
            }
            p["enc_ln"] = b.param("enc_ln", (d,), ("d_model",), "ones")
            p["enc_lnb"] = b.param("enc_lnb", (d,), ("d_model",), "zeros")
            p["dec_pos"] = b.param("dec_pos", (32768, d), (None, "d_model"))
        else:
            raise ValueError(cfg.family)

        p["final_norm"] = b.param("final_norm", (d,), ("d_model",), "ones")
        if cfg.family == "encdec":
            p["final_normb"] = b.param("final_normb", (d,), ("d_model",), "zeros")
        if not cfg.tie_embeddings:
            p["head"] = b.param("head", (d, v), ("d_model", "vocab"))
        return p

    return fn


# ===========================================================================
# blocks (apply)
# ===========================================================================

def _attend(fn, q, k, v, *args, **kw):
    """fn(q, k, v, *args) with its operands and result fenced under
    exact-TP (layers.isolate_exact_tp): the attention core is one of the
    ops whose bits would otherwise follow the per-device head count."""
    q, k, v = isolate_exact_tp((q, k, v))
    return isolate_exact_tp(fn(q, k, v, *args, **kw))


def _qkv(lp, x, cfg: ModelConfig):
    b_, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = matmul(x, lp["wq"])
    k = matmul(x, lp["wk"])
    v = matmul(x, lp["wv"])
    if cfg.qkv_bias:
        q = q + lp["bq"].astype(q.dtype)
        k = k + lp["bk"].astype(k.dtype)
        v = v + lp["bv"].astype(v.dtype)
    return (q.reshape(b_, s, h, hd), k.reshape(b_, s, kv, hd),
            v.reshape(b_, s, kv, hd))


def attn_block(lp, x, cfg: ModelConfig, *, positions, window=0, rope=True,
               ctx=None, kv_valid=None):
    """Full-sequence attention (train/prefill). Returns (out, (k, v)).

    positions: (S,) shared, or (B,S) per-row (left-padded prefill, where
    each row's real tokens start at its own offset). kv_valid: optional
    (B,S) bool marking real (non-pad) key/value columns."""
    b_, s, _ = x.shape
    q, k, v = _qkv(lp, x, cfg)
    if rope and cfg.rope_theta:
        # (B,S) positions broadcast over the head axis of the (B,H,S,Hd)
        # rope input as (B,1,S)
        pos_r = positions if positions.ndim == 1 else positions[:, None]
        q = apply_rope(q.swapaxes(1, 2), pos_r, cfg.rope_theta).swapaxes(1, 2)
        k = apply_rope(k.swapaxes(1, 2), pos_r, cfg.rope_theta).swapaxes(1, 2)
    if (cfg.use_flash_attention and window == 0 and s % 256 == 0
            and kv_valid is None):
        # Pallas flash kernel: VMEM-blocked online softmax — no (S,S)
        # score tensor ever reaches HBM.
        # On CPU this runs in interpret mode (tests); the dry-run models its
        # traffic analytically (launch/dryrun.py flash adjustment) because
        # the interpret-mode while-loop carries full arrays with per-step
        # copies that misrepresent the kernel's true HBM traffic.
        out = _attend(attn_lib.flash_attention_spmd, q, k, v, ctx,
                      causal=True)
    else:
        out = _attend(attn_lib.chunked_causal_attention, q, k, v,
                      window=window, kv_valid=kv_valid)
    out = matmul_rp(out.reshape(b_, s, -1), lp["wo"])
    return out, (k, v)


def write_kv_lines(cache, layer, pos, new):
    """Write each batch row's new K/V lines into one layer of a stacked
    cache, touching nothing else. cache: (n_layers,B,L,KvH,Hd); new:
    (B,Q,KvH,Hd); pos: (B,) line of each row's first new line. Row b's
    lines land at pos[b]..pos[b]+Q-1 of layer `layer`; a line past the
    cache end is dropped. A caller that carries the cache through its
    layer loop, in a program that donates it, updates it in place."""
    b_, qn = new.shape[:2]
    rows = jnp.arange(b_)[:, None]
    lines = pos[:, None] + jnp.arange(qn)[None, :]
    return cache.at[layer, rows, lines].set(new.astype(cache.dtype),
                                            mode="drop")


def reads_live_lines(per_row: bool, window: int, ring: bool) -> bool:
    """Whether one-token attention runs the `live_decode` kernel, which
    reads only each row's live lines: per-row positions (the slot
    scheduler), no window, no ring, on a TPU. Everywhere else
    `decode_attention` reads the layer's whole slab and masks; on the
    CPU the kernel would only run interpreted."""
    return per_row and not window and not ring and backend.on_tpu()


def attn_block_decode(lp, x, cfg: ModelConfig, *, cache_k, cache_v, layer,
                      pos, window=0, rope=True,
                      ctx: Optional[DistCtx] = None, ring=False,
                      active=None):
    """Attention of Q = x.shape[1] new tokens a row against layer `layer`
    of the stacked caches cache_k/v: (n_layers,B,L,KvH,Hd). Returns (out,
    (cache_k, cache_v)) with the new tokens' K/V lines written.

    pos is the write position of each row's first new token — a scalar
    shared by all rows (the classic lockstep decode, one
    dynamic_update_slice) or a (B,) vector when every batch row is at its
    own offset (the serve engine's slot scheduler, where refilled slots
    join mid-flight): row b's lines then land at pos[b]..pos[b]+Q-1
    (write_kv_lines). active: optional (B,) bool of the rows whose output
    is used; where the live-line kernel runs (reads_live_lines), the other
    rows read no line and get zeros.

    Q > 1 (per-row pos, no ring) is the speculative verify (serve/spec.py):
    one multi-query causal attention in which token j sees the lines up to
    pos+j, bit-identical to Q one-token calls. Lines of rejected
    candidates stay beyond the rolled-back position, masked, and are
    overwritten by the step that first reaches them."""
    b_, qn, _ = x.shape
    per_row = jnp.ndim(pos) == 1
    assert qn == 1 or (per_row and not ring), (qn, per_row, ring)
    q, k, v = _qkv(lp, x, cfg)
    if rope and cfg.rope_theta:
        # scalar pos -> one shared position; vector pos -> (B,1,Q) so the
        # angle table broadcasts over heads per row
        pvec = (pos[:, None] + jnp.arange(qn))[:, None] if per_row else \
            jnp.full((1,), pos, jnp.int32)
        q = apply_rope(q.swapaxes(1, 2), pvec, cfg.rope_theta).swapaxes(1, 2)
        k = apply_rope(k.swapaxes(1, 2), pvec, cfg.rope_theta).swapaxes(1, 2)
    lcache = cache_k.shape[2]
    slot = jnp.mod(pos, lcache) if ring else pos
    if per_row:
        cache_k = write_kv_lines(cache_k, layer, slot, k)
        cache_v = write_kv_lines(cache_v, layer, slot, v)
    else:
        at = (layer, 0, slot, 0, 0)
        cache_k = jax.lax.dynamic_update_slice(cache_k, k[None], at)
        cache_v = jax.lax.dynamic_update_slice(cache_v, v[None], at)
    cache_len = pos + qn
    if qn > 1:
        out = _attend(attn_lib.decode_attention_multi, q, cache_k[layer],
                      cache_v[layer], cache_len, window=window)
    elif reads_live_lines(per_row, window, ring):
        # the kernel takes the stacked caches whole: a slice of one layer
        # would itself be a read of every line
        lens = cache_len if active is None else \
            jnp.where(active, cache_len, 0)
        out = isolate_exact_tp(attn_lib.live_decode_attention(
            isolate_exact_tp(q), cache_k, cache_v, layer, lens, ctx))
    else:
        ck, cv = cache_k[layer], cache_v[layer]
        if ring:
            # ring buffer (sliding window): every slot <= cache_len-1 is
            # valid; window masking is implicit in the buffer size
            eff_len = jnp.minimum(cache_len, lcache)
            out = _attend(attn_lib.decode_attention, q, ck, cv, eff_len)
        elif ctx is not None and ctx.kv_seq_shard and not per_row:
            out = attn_lib.flash_decode_sharded(q, ck, cv, cache_len,
                                                ctx=ctx, window=window)
        else:
            out = _attend(attn_lib.decode_attention, q, ck, cv, cache_len,
                          window=window)
    out = matmul_rp(out.reshape(b_, qn, -1), lp["wo"])
    return out, (cache_k, cache_v)


def attn_block_continue(lp, x, cfg: ModelConfig, *, cache_k, cache_v, layer,
                        slot, start, positions, ctx=None):
    """Suffix attention for prefix-continue prefill (paged K/V cache with
    prefix reuse — serve/kvcache.py). x: (1,S,D) suffix hidden states whose
    first token sits at absolute position `start`; cache_k/v: stacked
    (n_layers,B,Lcache,KvH,Hd) slot caches whose `slot` row already holds
    the first `start` K/V lines of layer `layer` (restored prefix pages).

    The suffix k/v are written into the slot row at `start` and the queries
    attend against the FULL row with q_offset=start: keys at absolute
    positions > each query are causally masked, so stale lines beyond the
    written region contribute exact-0 softmax weight and the output is
    bit-identical to a cold full-prompt prefill of the same row (the
    chunked_causal_attention masking contract). Returns (out, (ck, cv))
    with the slot row updated.
    """
    b_, s, _ = x.shape
    assert b_ == 1
    q, k, v = _qkv(lp, x, cfg)
    if cfg.rope_theta:
        q = apply_rope(q.swapaxes(1, 2), positions, cfg.rope_theta).swapaxes(1, 2)
        k = apply_rope(k.swapaxes(1, 2), positions, cfg.rope_theta).swapaxes(1, 2)
    at = (layer, slot, start, 0, 0)
    cache_k = jax.lax.dynamic_update_slice(
        cache_k, k[None].astype(cache_k.dtype), at)
    cache_v = jax.lax.dynamic_update_slice(
        cache_v, v[None].astype(cache_v.dtype), at)
    row_k = cache_k[layer, slot][None]
    row_v = cache_v[layer, slot][None]
    out = attn_lib.chunked_causal_attention(q, row_k, row_v, q_offset=start)
    out = matmul_rp(out.reshape(b_, s, -1), lp["wo"])
    return out, (cache_k, cache_v)


def rwkv_time_mix(tm, x, shift_in, wkv_state, cfg: ModelConfig, *,
                  decode: bool):
    """RWKV6 time-mix. x: (B,T,D). Returns (out, last_token, new_state)."""
    b_, t, d = x.shape
    h = d // cfg.rwkv_head_dim
    hd = cfg.rwkv_head_dim
    x_prev, last = rwkv_lib.token_shift(x, shift_in)

    diff = x_prev - x
    xx = x + diff * tm["mu_x"].astype(x.dtype)
    delta = jnp.tanh(xx.astype(jnp.float32) @ tm["lora_a"].astype(jnp.float32))
    delta = delta.reshape(b_, t, 5, rwkv_lib.LORA_MIX)
    delta = jnp.einsum("btsk,skd->btsd", delta,
                       tm["lora_b"].astype(jnp.float32)).astype(x.dtype)
    mus = tm["mu_5"].astype(x.dtype)                    # (5, D)
    xs = [x + diff * (mus[i] + delta[:, :, i]) for i in range(5)]
    x_r, x_k, x_v, x_w, x_g = xs

    r = matmul(x_r, tm["wr"]).reshape(b_, t, h, hd).astype(jnp.float32)
    k = matmul(x_k, tm["wk"]).reshape(b_, t, h, hd).astype(jnp.float32)
    v = matmul(x_v, tm["wv"]).reshape(b_, t, h, hd).astype(jnp.float32)
    g = jax.nn.silu(matmul(x_g, tm["wg"]).astype(jnp.float32))

    wlog = tm["w0"].astype(jnp.float32) + \
        (jnp.tanh(x_w.astype(jnp.float32) @ tm["td_a"].astype(jnp.float32))
         @ tm["td_b"].astype(jnp.float32))
    w = jnp.exp(-jnp.exp(wlog)).reshape(b_, t, h, hd)
    u = tm["u"].astype(jnp.float32)

    if decode:
        y, wkv_state = rwkv_lib.wkv6_decode(
            r[:, 0], k[:, 0], v[:, 0], w[:, 0], u, wkv_state)
        y = y[:, None]
    else:
        chunk = 64 if t % 64 == 0 else (t if t < 64 else 1)
        if chunk > 1:
            y, wkv_state = rwkv_lib.wkv6_chunked(r, k, v, w, u, wkv_state,
                                                 chunk=chunk)
        else:
            y, wkv_state = rwkv_lib.wkv6_scan(r, k, v, w, u, wkv_state)

    # per-head group norm, then gate and output projection
    y = y.reshape(b_, t, h, hd)
    mu = jnp.mean(y, -1, keepdims=True)
    var = jnp.var(y, -1, keepdims=True)
    y = (y - mu) * jax.lax.rsqrt(var + 64e-5)
    y = y.reshape(b_, t, d) * tm["ln_x"].astype(jnp.float32)
    out = matmul((y * g).astype(PARAM_DTYPE), tm["wo"])
    return out, last, wkv_state


def rwkv_channel_mix(cm, x, shift_in):
    x_prev, last = rwkv_lib.token_shift(x, shift_in)
    xk = x + (x_prev - x) * cm["mu_k"].astype(x.dtype)
    xr = x + (x_prev - x) * cm["mu_r"].astype(x.dtype)
    k = jnp.square(jax.nn.relu(matmul(xk, cm["wk"]).astype(jnp.float32)))
    kv = matmul(k.astype(PARAM_DTYPE), cm["wv"])
    out = jax.nn.sigmoid(matmul(xr, cm["wr"]).astype(jnp.float32)) * kv
    return out.astype(PARAM_DTYPE), last


def mamba_path(mp, x, cfg: ModelConfig, *, conv_state=None, h_state=None,
               decode: bool = False, ctx: Optional[DistCtx] = None):
    """Mamba selective-SSM path of the Hymba block. x: (B,T,D).
    Returns (y (B,T,D), new_conv_state, new_h_state).

    ctx: when a TP mesh splits the inner channels (Ci column-parallel),
    the registry-dispatched scan (cfg.ssm_impl == "pallas") keys its tuned
    blk_c on the per-shard channel count — see DistCtx.tp_shards."""
    b_, t, d = x.shape
    ci = 2 * d
    n = cfg.ssm_state
    dt_rank = max(1, d // 16)

    xz = matmul(x, mp["in_proj"])                       # (B,T,2Ci)
    xs, z = jnp.split(xz, 2, axis=-1)
    xs, conv_state = mamba_lib.causal_conv1d(xs, mp["conv_w"], conv_state)
    xs = jax.nn.silu(xs.astype(jnp.float32)).astype(PARAM_DTYPE)

    proj = matmul(xs, mp["x_proj"]).astype(jnp.float32)  # (B,T,dtr+2N)
    dt_in, bmat, cmat = jnp.split(proj, [dt_rank, dt_rank + n], axis=-1)
    dt = jax.nn.softplus(dt_in @ mp["dt_proj"].astype(jnp.float32)
                         + mp["dt_bias"].astype(jnp.float32))

    if h_state is None:
        h_state = jnp.zeros((b_, ci, n), jnp.float32)
    if cfg.ssm_impl == "stub" and not decode:
        # §Perf instrumentation: skip the selective scan itself (keep the
        # projections) to isolate the scan's HBM traffic by differencing.
        y = xs.astype(jnp.float32) * mp["d"].astype(jnp.float32)
        y = y * jax.nn.silu(z.astype(jnp.float32))
        return matmul(y.astype(PARAM_DTYPE), mp["out_proj"]), conv_state, h_state
    if decode:
        y, h_state = mamba_lib.ssm_decode(
            xs[:, 0].astype(jnp.float32), dt[:, 0], bmat[:, 0], cmat[:, 0],
            mp["a_log"], mp["d"], h_state)
        y = y[:, None]
    elif cfg.ssm_impl == "pallas":
        # unified-registry dispatch: blk_c comes from the repro.tune cache
        # keyed on the LOCAL channel shard (Ci/tp under a TP mesh), so the
        # cached config matches the slab each device actually executes
        from repro.kernels.ssm import ops as ssm_ops
        from repro.kernels.ssm.kernel_def import SsmKey
        shards = ctx.tp_shards(ci) if ctx is not None else 1
        key = SsmKey(b=b_, t=t, c=ci // shards, n=n)
        y, h_state = ssm_ops.ssm_scan(
            xs.astype(jnp.float32), dt, bmat, cmat, mp["a_log"], mp["d"],
            h_state, problem_key=key)
    else:
        chunk = 64 if (t % 64 == 0 and cfg.ssm_impl == "chunked") else 1
        if chunk > 1:
            y, h_state = mamba_lib.ssm_chunked(
                xs.astype(jnp.float32), dt, bmat, cmat, mp["a_log"], mp["d"],
                h_state, chunk=chunk)
        else:
            y, h_state = mamba_lib.ssm_scan(
                xs.astype(jnp.float32), dt, bmat, cmat, mp["a_log"], mp["d"],
                h_state)

    y = y * jax.nn.silu(z.astype(jnp.float32))
    out = matmul(y.astype(PARAM_DTYPE), mp["out_proj"])
    return out, conv_state, h_state
