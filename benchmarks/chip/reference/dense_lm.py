"""Plain float32 reference of a dense Qwen-family decoder, and the
weights it and the served program share.

Block (Qwen2 / CodeQwen1.5, as published): x += Attn(RMSNorm(x)),
x += SwiGLU(RMSNorm(x)); attention is grouped-query with biases on the
q/k/v projections and rotary embeddings (rotate-half form, theta from the
config); the LM head is the embedding table when tied. Computed in
float32 under `default_matmul_precision("highest")`, one layer at a time,
so that the float32 copy of a large model never has to be whole.

The weights are made here from the run's seed, leaf by leaf from keys
folded from (layer, leaf). The served program gets the same values from
one jitted call of `all_weights` (in bfloat16, the type it serves in);
the reference regenerates each layer and widens it to float32. It takes
nothing from the program.

Control (`control=True`): the same forward with every projection, the LM
head and the K/V computed in fp8 (e4m3, scaled per row and per output
channel), the step a later change would be tempted to take below bf16.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

LAYER_LEAVES = ("ln1", "wq", "bq", "wk", "bk", "wv", "bv", "wo",
                "ln2", "w_gate", "w_up", "w_down")
GLOBAL_LEAVES = ("embed", "head", "final_norm")
GLOBAL = 1 << 20          # the "layer" index of the global leaves


def dims(cfg: Mapping) -> Dict[str, int]:
    return dict(L=int(cfg["num_hidden_layers"]), d=int(cfg["hidden_size"]),
                H=int(cfg["num_attention_heads"]),
                KV=int(cfg["num_key_value_heads"]),
                hd=int(cfg["head_dim"]), ff=int(cfg["intermediate_size"]),
                V=int(cfg["vocab_size"]),
                tied=bool(cfg["tie_word_embeddings"]),
                bias=bool(cfg["qkv_bias"]))


def key_data(seed: int) -> np.ndarray:
    """Raw (2,) uint32 key data for any whole-number seed."""
    s = np.random.SeedSequence([abs(int(seed)), int(int(seed) < 0)])
    return s.generate_state(2, np.uint32)


def _leaf(kd, layer, name: str, shape, cfg: Mapping, dtype):
    std = float(cfg["init_std"])
    n_layers = int(cfg["num_hidden_layers"])
    idx = (LAYER_LEAVES + GLOBAL_LEAVES).index(name)
    key = jax.random.wrap_key_data(kd, impl="threefry2x32")
    key = jax.random.fold_in(jax.random.fold_in(key, layer), idx)
    z = jax.random.normal(key, shape, jnp.float32)
    if name in ("ln1", "ln2", "final_norm"):
        w = 1.0 + float(cfg["init_norm_std"]) * z
    elif name in ("bq", "bk", "bv"):
        w = float(cfg["init_bias_std"]) * z
    elif name in ("wo", "w_down"):
        w = (std / math.sqrt(2 * n_layers)) * z      # residual projections
    else:
        w = std * z
    return w.astype(dtype)


def layer_shapes(cfg: Mapping) -> Dict[str, Tuple[int, ...]]:
    m = dims(cfg)
    q, kv = m["H"] * m["hd"], m["KV"] * m["hd"]
    s = {"ln1": (m["d"],), "wq": (m["d"], q), "wk": (m["d"], kv),
         "wv": (m["d"], kv), "wo": (q, m["d"]), "ln2": (m["d"],),
         "w_gate": (m["d"], m["ff"]), "w_up": (m["d"], m["ff"]),
         "w_down": (m["ff"], m["d"])}
    if m["bias"]:
        s.update(bq=(q,), bk=(kv,), bv=(kv,))
    return s


def layer_weights(cfg: Mapping, kd, layer, dtype=jnp.bfloat16):
    return {n: _leaf(kd, layer, n, shp, cfg, dtype)
            for n, shp in layer_shapes(cfg).items()}


def global_weights(cfg: Mapping, kd, dtype=jnp.bfloat16):
    m = dims(cfg)
    g = {"embed": _leaf(kd, GLOBAL, "embed", (m["V"], m["d"]), cfg, dtype),
         "final_norm": _leaf(kd, GLOBAL, "final_norm", (m["d"],), cfg, dtype)}
    if not m["tied"]:
        g["head"] = _leaf(kd, GLOBAL, "head", (m["d"], m["V"]), cfg, dtype)
    return g


def all_weights(cfg: Mapping, kd, dtype=jnp.bfloat16):
    """Every weight, layers stacked on a leading axis (jit this)."""
    n = int(cfg["num_hidden_layers"])
    layers = jax.vmap(lambda l: layer_weights(cfg, kd, l, dtype))(
        jnp.arange(n))
    return {"layers": layers, **global_weights(cfg, kd, dtype)}


# ------------------------------------------------------------------ forward
def _fp8(x, axis):
    """Round x to e4m3 with one scale per slice along `axis`."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, control: bool):
    if control:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return x @ w


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (S, heads, hd), positions 0..S-1, rotate-half form."""
    s, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    x1, x2 = jnp.split(x, 2, -1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _layer(cfg: Mapping, control: bool, w, x):
    """x: (N, S, d) float32 -> (N, S, d)."""
    m = dims(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    n, s, _ = x.shape
    g = m["H"] // m["KV"]
    h = _rms(x, w["ln1"], eps)
    q, k, v = (_mm(h, w[f"w{p}"], control) for p in "qkv")
    if m["bias"]:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    if control:
        k, v = _fp8(k, -1), _fp8(v, -1)
    mask = jnp.tril(jnp.ones((s, s), bool))

    def attend(qkv):
        qi, ki, vi = qkv
        qi = _rope(qi.reshape(s, m["H"], m["hd"]), theta)
        ki = _rope(ki.reshape(s, m["KV"], m["hd"]), theta)
        vi = vi.reshape(s, m["KV"], m["hd"])
        ki, vi = jnp.repeat(ki, g, axis=1), jnp.repeat(vi, g, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", qi, ki) / math.sqrt(m["hd"])
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", p, vi).reshape(s, -1)

    a = jax.lax.map(attend, (q, k, v))
    x = x + _mm(a, w["wo"], control)
    h = _rms(x, w["ln2"], eps)
    f = jax.nn.silu(_mm(h, w["w_gate"], control)) * _mm(h, w["w_up"], control)
    return x + _mm(f, w["w_down"], control)


@functools.partial(jax.jit, static_argnums=(0,))
def _gen_layer(frozen_cfg, kd, layer):
    return layer_weights(dict(frozen_cfg), kd, layer)


@functools.partial(jax.jit, static_argnums=(0,))
def _gen_globals(frozen_cfg, kd):
    return global_weights(dict(frozen_cfg), kd)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _apply_layer(frozen_cfg, control, w, x):
    with jax.default_matmul_precision("highest"):
        return _layer(dict(frozen_cfg), control, w, x)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head_block(frozen_cfg, control, g, xr, xc, served):
    """Gaps over one block of positions: the reference's best logit minus
    its logit of the served token, and (control) minus its logit of the
    token the control puts first."""
    cfg = dict(frozen_cfg)
    eps = float(cfg["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        table = (g["embed"].T if "head" not in g else g["head"])
        table = table.astype(jnp.float32)
        norm = g["final_norm"].astype(jnp.float32)
        lr = _rms(xr, norm, eps) @ table
        best = jnp.max(lr, -1)
        gap = best - jnp.take_along_axis(lr, served[:, None], -1)[:, 0]
        if not control:
            return gap, gap
        lc = _mm(_rms(xc, norm, eps), table, True)
        pick = jnp.argmax(lc, -1)
        cgap = best - jnp.take_along_axis(lr, pick[:, None], -1)[:, 0]
        return gap, cgap


def _freeze(cfg: Mapping):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


def served_gaps(cfg: Mapping, seed: int, tokens: np.ndarray,
                rows: Sequence[int], cols: Sequence[int],
                served: Sequence[int], *, control: bool = False,
                block: int = 256) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """Run the reference over tokens (N, S) (prompts followed by their
    served tokens, right-padded) and return, at each position (rows[i],
    cols[i]) whose next token the program served as served[i], the gap by
    which the served token's logit lies below the reference's best. With
    control=True also the gap of the control's first choice there."""
    fc = _freeze(cfg)
    kd = jnp.asarray(key_data(seed))
    g = _gen_globals(fc, kd)
    x = g["embed"].astype(jnp.float32)[jnp.asarray(tokens)]
    xr = xc = x
    for layer in range(int(cfg["num_hidden_layers"])):
        w = _gen_layer(fc, kd, jnp.int32(layer))
        xr = _apply_layer(fc, False, w, xr)
        if control:
            xc = _apply_layer(fc, True, w, xc)
        del w
    rows, cols = np.asarray(rows), np.asarray(cols)
    served = np.asarray(served, np.int32)
    gaps, cgaps = [], []
    for i in range(0, len(rows), block):
        sl = slice(i, i + block)
        pad = block - len(rows[sl])
        r = np.pad(rows[sl], (0, pad))
        c = np.pad(cols[sl], (0, pad))
        t = np.pad(served[sl], (0, pad))
        a, b = _head_block(fc, control, g, xr[r, c], xc[r, c], jnp.asarray(t))
        gaps.append(np.asarray(a)[:block - pad])
        cgaps.append(np.asarray(b)[:block - pad])
    gap = np.concatenate(gaps)
    return gap, (np.concatenate(cgaps) if control else None)
