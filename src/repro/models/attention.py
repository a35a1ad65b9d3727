"""Attention: chunked-causal (train/prefill) and decode (incl. distributed
flash-decode over a sequence-sharded KV cache).

Memory design mirrors the paper's cache-blocking lesson (v6): never
materialize the full (S x S) score matrix — queries are processed in blocks
(lax.scan) with online f32 softmax, so the transient working set is
O(chunk x S) per head group. On TPU the same blocking becomes the Pallas
flash kernel; this jnp version is the XLA path and the oracle.

Distributed decode ("flash decode"): for 32k+ caches the KV cache is sharded
along the *sequence* dim over the `model` mesh axis. Each chip computes
partial attention over its shard and the partials are combined with a psum
of (o*l, l, m)-style logsumexp stats under shard_map — one small collective
instead of gathering the whole cache.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import PARAM_DTYPE, DistCtx

NEG_INF = -1e30


def _gqa_reshape(q, n_kv: int):
    """(B,S,H,Hd) -> (B,S,KvH,G,Hd)"""
    b, s, h, hd = q.shape
    return q.reshape(b, s, n_kv, h // n_kv, hd)


def chunked_causal_attention(
    q, k, v, *,
    chunk: int = 512,
    window: int = 0,
    q_offset: int = 0,
    causal: bool = True,
    kv_valid: Optional[jax.Array] = None,
) -> jax.Array:
    """q: (B,Sq,H,Hd), k/v: (B,Skv,KvH,Hd). Causal by default (causal=False
    gives full bidirectional attention — encoder / cross-attention).
    q_offset: absolute position of q[0] relative to k[0] (prefill=0).
    kv_valid: optional (B,Skv) bool — False columns (padding) are masked out
    of every query's softmax, so pad tokens cannot leak into real rows."""
    b, sq, h, hd = q.shape
    _, skv, n_kv, _ = k.shape
    g = h // n_kv
    scale = hd ** -0.5

    qr = _gqa_reshape(q, n_kv)                       # (B,Sq,KvH,G,Hd)
    chunk = min(chunk, sq)
    if sq % chunk:
        # largest divisor of sq <= requested chunk (e.g. whisper's 1500)
        chunk = max(c for c in range(1, chunk + 1) if sq % c == 0)
    n_chunks = sq // chunk
    qr = qr.reshape(b, n_chunks, chunk, n_kv, g, hd)
    kv_pos = jnp.arange(skv)

    def one_chunk(ci, qc):
        # qc: (B,chunk,KvH,G,Hd); ci is the scan CARRY (a traced counter),
        # not scan xs — this stops XLA hoisting the causal mask out of the
        # loop and materializing (n_chunks, chunk, Skv) masks for all chunks
        # at once (a real pessimization observed in the compiled HLO).
        scores = jnp.einsum(
            "bqkgd,bskd->bkgqs", qc, k,
            preferred_element_type=jnp.float32) * scale   # (B,KvH,G,chunk,Skv)
        if causal:
            q_pos = q_offset + ci * chunk + jnp.arange(chunk)
            mask = kv_pos[None, :] <= q_pos[:, None]
            if window:
                mask &= kv_pos[None, :] > (q_pos[:, None] - window)
            scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        if kv_valid is not None:
            scores = jnp.where(kv_valid[:, None, None, None, :], scores,
                               NEG_INF)
        p = jax.nn.softmax(scores, axis=-1).astype(PARAM_DTYPE)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, v,
                          preferred_element_type=jnp.float32).astype(PARAM_DTYPE)

    def body(ci, qc):
        # checkpoint per chunk: during the backward pass only ONE chunk's
        # scores/probs are live (instead of the full stacked (n_chunks, ...)
        # residual), which is what keeps train_4k under the 16 GiB/chip HBM
        # budget at B_local=16.
        return ci + 1, jax.checkpoint(one_chunk)(ci, qc)

    _, out = jax.lax.scan(body, jnp.int32(0), qr.swapaxes(0, 1))
    out = out.swapaxes(0, 1).reshape(b, sq, h, hd)
    return out


def decode_attention(q, k_cache, v_cache, cache_len, *,
                     window: int = 0) -> jax.Array:
    """Single-token decode. q: (B,1,H,Hd); caches: (B,L,KvH,Hd).
    cache_len: number of valid cache positions — a (static or traced)
    scalar shared by every row, or a (B,) vector for per-row lengths
    (the slot-scheduler case, where each slot is mid-flight at its own
    offset)."""
    b, _, h, hd = q.shape
    _, l, n_kv, _ = k_cache.shape
    g = h // n_kv
    scale = hd ** -0.5
    qr = q.reshape(b, n_kv, g, hd)
    scores = jnp.einsum("bkgd,bskd->bkgs", qr, k_cache,
                        preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(l)
    if jnp.ndim(cache_len) == 1:                         # per-row lengths
        mask = pos[None, :] < cache_len[:, None]         # (B, L)
        if window:
            mask &= pos[None, :] >= (cache_len[:, None] - window)
        scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    else:
        mask = pos < cache_len
        if window:
            mask &= pos >= (cache_len - window)
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(PARAM_DTYPE)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, hd).astype(PARAM_DTYPE)


def decode_attention_multi(q, k_cache, v_cache, cache_len, *,
                           window: int = 0) -> jax.Array:
    """Multi-query decode: Q=k+1 candidate tokens per row attend against a
    cache whose last Q lines are the candidates themselves (speculative
    verify — serve/spec.py). q: (B,Q,H,Hd); caches: (B,L,KvH,Hd).

    cache_len counts valid positions INCLUDING the Q candidate lines, so
    candidate j (0-based) sits at absolute position cache_len - Q + j and
    may attend every cache position <= its own — the per-query causal mask
    that makes verify logits bit-identical to Q sequential decode_attention
    calls at the same positions. cache_len: scalar or (B,) per-row."""
    b, qn, h, hd = q.shape
    _, l, n_kv, _ = k_cache.shape
    g = h // n_kv
    scale = hd ** -0.5
    qr = q.reshape(b, qn, n_kv, g, hd).transpose(0, 2, 3, 1, 4)
    scores = jnp.einsum("bkgqd,bskd->bkgqs", qr, k_cache,
                        preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(l)
    clen = cache_len if jnp.ndim(cache_len) == 1 else \
        jnp.full((b,), cache_len, jnp.int32)
    q_pos = clen[:, None] - qn + jnp.arange(qn)[None, :]     # (B,Q) absolute
    mask = pos[None, None, :] <= q_pos[:, :, None]           # (B,Q,L)
    if window:
        mask &= pos[None, None, :] > (q_pos[:, :, None] - window)
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(PARAM_DTYPE)
    out = jnp.einsum("bkgqs,bskd->bkgqd", p, v_cache,
                     preferred_element_type=jnp.float32)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, qn, h, hd)
    return out.astype(PARAM_DTYPE)


# ---------------------------------------------------------------------------
# distributed flash-decode: seq-sharded KV cache + logsumexp-combine psum
# ---------------------------------------------------------------------------

def _partial_decode(q, k_shard, v_shard, valid_mask):
    """Partial attention over a KV shard -> (o_unnorm, l, m) f32 stats.
    q: (B,KvH,G,Hd); k/v_shard: (B,Ls,KvH,Hd); valid_mask: (B?,Ls) bool."""
    hd = q.shape[-1]
    scale = hd ** -0.5
    scores = jnp.einsum("bkgd,bskd->bkgs", q, k_shard,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(valid_mask[:, None, None, :], scores, NEG_INF)
    m = jnp.max(scores, axis=-1)                                 # (B,KvH,G)
    e = jnp.exp(scores - m[..., None])
    l = jnp.sum(e, axis=-1)                                      # (B,KvH,G)
    o = jnp.einsum("bkgs,bskd->bkgd", e, v_shard.astype(jnp.float32),
                   preferred_element_type=jnp.float32)           # unnormalized
    return o, l, m


def flash_decode_sharded(q, k_cache, v_cache, cache_len, *,
                         ctx: DistCtx, window: int = 0) -> jax.Array:
    """Decode attention with the cache's seq dim sharded over ctx.model_axis.

    Inside shard_map each chip sees its local (B, L/mp, KvH, Hd) shard,
    computes partial (o,l,m), and the global softmax is reconstructed with
    two psums (max then sum) — the classic flash-decoding combine, mapped to
    TPU ICI instead of GPU SM partitioning (DESIGN.md hardware adaptation).
    """
    b, _, h, hd = q.shape
    _, l_total, n_kv, _ = k_cache.shape
    g = h // n_kv
    axis = ctx.model_axis

    def local(qr, ks, vs, clen):
        # shard-local positions: shard index via axis_index
        shard = jax.lax.axis_index(axis)
        ls = ks.shape[1]
        pos = shard * ls + jnp.arange(ls)
        mask = pos < clen
        if window:
            mask = mask & (pos >= clen - window)
        bl = ks.shape[0]
        mask = jnp.broadcast_to(mask[None, :], (bl, ls))
        o, lsum, m = _partial_decode(
            qr[:, 0].reshape(bl, n_kv, g, hd), ks, vs, mask)
        # combine partial softmax stats across the model axis
        m_glob = jax.lax.pmax(m, axis)
        corr = jnp.exp(m - m_glob)
        l_glob = jax.lax.psum(lsum * corr, axis)
        o_glob = jax.lax.psum(o * corr[..., None], axis)
        out = o_glob / jnp.maximum(l_glob[..., None], 1e-30)
        return out.reshape(bl, 1, h, hd).astype(PARAM_DTYPE)

    # axis_names={model}: manual only over the model axis; batch/data sharding
    # stays automatic (so batch=1 long_500k and batch-sharded decode_32k both
    # flow through the same code path).
    fn = jax.shard_map(
        local, mesh=ctx.mesh,
        in_specs=(P(), P(None, axis, None, None), P(None, axis, None, None), P()),
        out_specs=P(),
        axis_names=frozenset({axis}),
        check_vma=False,
    )
    return fn(q, k_cache, v_cache, cache_len)


def flash_attention_spmd(q, k, v, ctx: Optional[DistCtx], *,
                         causal: bool = True):
    """Pallas flash attention under shard_map: the kernel's grid loop must
    see LOCAL shards — lowering it through SPMD auto-sharding makes XLA
    all-gather the operands per grid step (measured: PB-scale collectives).
    Heads shard over `model` when divisible, batch over the dp axes;
    otherwise that dim replicates (same fallback as the sharding engine).

    Dispatches through the kernel registry, so the (blk_q, blk_kv) come
    from the repro.tune cache per local shard size instead of the old
    frozen 256/256: _dispatch_flash runs INSIDE shard_map, where q/k/v are
    the per-device shards, so the FlashKey it builds carries the per-shard
    head counts (h/tp when the mesh divides them) — the same local-keying
    contract the ssm registry path gets via DistCtx.tp_shards. Tuning here
    is model-only: this runs at trace time inside jit/shard_map, where a
    measurement pass (timed kernel executions on synthetic inputs) would
    stall every first compile of a new shape."""
    if ctx is None or ctx.mesh is None:
        return _dispatch_flash(q, k, v, causal)
    mesh = ctx.mesh
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    tp = mesh.shape[ctx.model_axis]
    dp = 1
    for a in ctx.data_axes:
        dp *= mesh.shape[a]
    hspec = ctx.model_axis if (h % tp == 0 and kvh % tp == 0) else None
    bspec = tuple(ctx.data_axes) if b % max(dp, 1) == 0 and dp > 1 else None
    qs = P(bspec, None, hspec, None)

    fn = jax.shard_map(
        lambda q_, k_, v_: _dispatch_flash(q_, k_, v_, causal),
        mesh=mesh, in_specs=(qs, qs, qs), out_specs=qs,
        axis_names=frozenset(mesh.axis_names), check_vma=False)
    return fn(q, k, v)


def live_decode_attention(q, cache_k, cache_v, layer, lens,
                          ctx: Optional[DistCtx] = None) -> jax.Array:
    """Single-token decode against layer `layer` of the stacked caches,
    reading only each row's live lines: the registry's `live_decode`
    Pallas kernel. q: (B,1,H,Hd); caches: (n_layers,B,L,KvH,Hd); lens:
    (B,) live lines per row (0: the row reads nothing and gets zeros).
    `decode_attention` over `cache[layer]` is its reference.

    On a mesh the kernel runs under shard_map with heads on `model` where
    the mesh divides them (the serve plan's cache layout, every other
    dim whole), so each device walks only its own K/V heads' lines:
    under GSPMD a Pallas call would gather its operands."""
    from repro.kernels import api
    h, kvh = q.shape[2], cache_k.shape[3]
    run = lambda q_, k_, v_, l_, n_: api.dispatch(  # noqa: E731
        "live_decode", q_, k_, v_, l_, n_)
    if ctx is None or ctx.mesh is None:
        return run(q[:, 0], cache_k, cache_v, layer, lens)[:, None]
    tp = ctx.mesh.shape[ctx.model_axis]
    hspec = ctx.model_axis if (h % tp == 0 and kvh % tp == 0) else None
    qs, cs = P(None, hspec, None), P(None, None, None, hspec, None)
    fn = jax.shard_map(
        run, mesh=ctx.mesh, in_specs=(qs, cs, cs, P(), P()), out_specs=qs,
        axis_names=frozenset(ctx.mesh.axis_names), check_vma=False)
    return fn(q[:, 0], cache_k, cache_v, layer, lens)[:, None]


def _dispatch_flash(q, k, v, causal):
    """Registry dispatch with a model-only tuned config (no timing pass at
    trace time); shapes the tune menu can't tile fall back to config=None,
    which dispatch resolves to the divisor-clamped static config."""
    from repro.kernels import api
    from repro.tune import tuner
    key = api.get_kernel("flash").problem_key(q, k, v, causal=causal)
    try:
        cfg = tuner.tune_kernel("flash", key, measure_mode=False).config
    except ValueError:            # empty config space at this shape
        cfg = None
    return api.dispatch("flash", q, k, v, causal=causal, config=cfg)
