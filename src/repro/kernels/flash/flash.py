"""Flash attention (causal, GQA) as a Pallas TPU kernel.

This is the paper's cache-blocking methodology (v6–v8) applied to the
framework's hottest memory term: the baseline XLA attention materializes the
(B,H,S,S) score tensor in HBM three times per layer (scores, softmax, probs)
— the dominant §Roofline memory term for every train/prefill cell. The flash
kernel streams KV blocks through VMEM with an online softmax, so HBM traffic
drops to O(S·Hd) per head: exactly the "declare the block, keep it in VMEM"
move the GPP kernel makes (DESIGN.md §2).

Blocking (v8-style reasoning):
  grid = (B*H, n_q_blocks, n_kv_blocks), kv innermost (sequential) so the
  q-indexed output block is revisited and accumulated in place;
  q block (BLK_Q, Hd): lanes = Hd (128-aligned for the assigned archs);
  k/v blocks (BLK_KV, Hd) stream; GQA is expressed in the kv index_map
  (head h reads kv head h // group) — no materialized KV replication.
  Causal blocks with q_idx < kv_idx are skipped via pl.when (the TPU grid
  is sequential, so skipped instances cost only the grid step).

Outputs are (acc, l, m) — unnormalized weighted values plus softmax stats;
ops.flash_attention divides outside the kernel (keeps the kernel free of a
lane-broadcast divide). Validated against ref.reference (the chunked-softmax
oracle) in interpret mode by tests/test_flash_kernel.py.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import backend

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, acc_ref, l_ref, m_ref, *,
            blk_q: int, blk_kv: int, scale: float, causal: bool):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        l_ref[...] = jnp.zeros_like(l_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)

    def body():
        q = q_ref[0].astype(jnp.float32)              # (BQ, Hd)
        k = k_ref[0].astype(jnp.float32)              # (BKV, Hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * blk_q + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_kv), 0)
            k_pos = ki * blk_kv + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_kv), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)

        m_prev = m_ref[0]                             # (BQ, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                        # (BQ, BKV)
        l_ref[0] = l_ref[0] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[0] = acc_ref[0] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[0] = m_new

    if causal:
        # skip fully-masked blocks: kv block strictly after the q block
        pl.when(ki * blk_kv <= qi * blk_q + blk_q - 1)(body)
    else:
        body()


def flash_attention_bhsd(q, k, v, *, blk_q: int = 256, blk_kv: int = 256,
                         causal: bool = True,
                         interpret: Optional[bool] = None) -> jax.Array:
    """q: (BH, S, Hd); k/v: (BKvH, S, Hd) with BH = BKvH * group.
    Returns (BH, S, Hd) f32-accurate attention output (cast to q.dtype).
    interpret=None defers to repro.backend (REPRO_INTERPRET override)."""
    interpret = backend.resolve_interpret(interpret)
    bh, sq, hd = q.shape
    bkv, skv, _ = k.shape
    group = bh // bkv
    blk_q = min(blk_q, sq)
    blk_kv = min(blk_kv, skv)
    assert sq % blk_q == 0 and skv % blk_kv == 0, (sq, blk_q, skv, blk_kv)
    n_q, n_kv = sq // blk_q, skv // blk_kv
    scale = hd ** -0.5

    kern = functools.partial(_kernel, blk_q=blk_q, blk_kv=blk_kv,
                             scale=scale, causal=causal)
    grid = (bh, n_q, n_kv)
    out_shape = [
        jax.ShapeDtypeStruct((bh, sq, hd), jnp.float32),   # acc
        jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),    # l
        jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),    # m
    ]
    out_spec = [
        pl.BlockSpec((1, blk_q, hd), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, blk_q, 1), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, blk_q, 1), lambda b, i, j: (b, i, 0)),
    ]
    acc, l, m = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk_q, hd), lambda b, i, j: (b, i, 0)),
            # GQA: head b reads kv head b // group — no KV replication
            pl.BlockSpec((1, blk_kv, hd), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((1, blk_kv, hd), lambda b, i, j: (b // group, j, 0)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(q, k, v)
    out = acc / jnp.maximum(l, 1e-30)
    return out.astype(q.dtype)


def vmem_bytes(blk_q: int, blk_kv: int, hd: int) -> int:
    """Working set: q/k/v blocks (x2 double buffer) + acc/l/m + p."""
    io = 2 * (blk_q * hd + 2 * blk_kv * hd) * 2
    live = (blk_q * hd + 2 * blk_q) * 4 + blk_q * blk_kv * 4 * 3
    return io + live


# ===========================================================================
# backward kernels + custom VJP (training path)
#
# fwd saves (q, k, v, out, L = m + log l). bwd recomputes p per block:
#   D   = rowsum(dout * out)
#   p   = exp(q k^T * scale - L)
#   ds  = p * (dout v^T - D) * scale
#   dq  = sum_kv ds k        (grid: kv innermost, dq block revisited)
#   dk  = sum_q  ds^T q      (grid: q innermost, dk/dv blocks revisited)
#   dv  = sum_q  p^T dout
# ===========================================================================

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, blk_q, blk_kv, scale, causal):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    def body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                                  # (BQ, 1)
        delta = delta_ref[0]                              # (BQ, 1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * blk_q + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_kv), 0)
            k_pos = ki * blk_kv + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_kv), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_ref[0] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(ki * blk_kv <= qi * blk_q + blk_q - 1)(body)
    else:
        body()


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, blk_q, blk_kv, scale, causal, group):
    ki, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    def body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * blk_q + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_kv), 0)
            k_pos = ki * blk_kv + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_kv), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        p = jnp.exp(s - lse)                              # (BQ, BKV)
        dv_ref[0] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_ref[0] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(ki * blk_kv <= qi * blk_q + blk_q - 1)(body)
    else:
        body()


def _fwd_with_stats(q, k, v, blk_q, blk_kv, causal, interpret):
    interpret = backend.resolve_interpret(interpret)
    bh, sq, hd = q.shape
    bkv, skv, _ = k.shape
    group = bh // bkv
    n_q, n_kv = sq // blk_q, skv // blk_kv
    scale = hd ** -0.5
    kern = functools.partial(_kernel, blk_q=blk_q, blk_kv=blk_kv,
                             scale=scale, causal=causal)
    acc, l, m = pl.pallas_call(
        kern,
        grid=(bh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, blk_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_kv, hd), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((1, blk_kv, hd), lambda b, i, j: (b // group, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, hd), jnp.float32),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    out = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_diff(q, k, v, blk_q=256, blk_kv=256, causal=True,
                         interpret=None):
    out, _ = _fwd_with_stats(q, k, v, blk_q, blk_kv, causal, interpret)
    return out


def _flash_fwd(q, k, v, blk_q, blk_kv, causal, interpret):
    out, lse = _fwd_with_stats(q, k, v, blk_q, blk_kv, causal, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(blk_q, blk_kv, causal, interpret, res, dout):
    interpret = backend.resolve_interpret(interpret)
    q, k, v, out, lse = res
    bh, sq, hd = q.shape
    bkv, skv, _ = k.shape
    group = bh // bkv
    n_q, n_kv = sq // blk_q, skv // blk_kv
    scale = hd ** -0.5
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)              # (BH, S, 1)

    q_spec = pl.BlockSpec((1, blk_q, hd), lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec((1, blk_kv, hd), lambda b, i, j: (b // group, j, 0))
    st_spec = pl.BlockSpec((1, blk_q, 1), lambda b, i, j: (b, i, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, blk_q=blk_q, blk_kv=blk_kv,
                          scale=scale, causal=causal),
        grid=(bh, n_q, n_kv),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, st_spec, st_spec],
        out_specs=pl.BlockSpec((1, blk_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, hd), jnp.float32),
        interpret=interpret,
    )(q, k, v, dout, lse, delta)

    # dkv: grid over (BH, kv, q); outputs indexed by (b, kv) accumulate over
    # q steps. GQA: each q head contributes to its kv head's gradient —
    # sum the per-q-head partials afterwards.
    q_spec2 = pl.BlockSpec((1, blk_q, hd), lambda b, j, i: (b, i, 0))
    kv_spec2 = pl.BlockSpec((1, blk_kv, hd), lambda b, j, i: (b // group, j, 0))
    st_spec2 = pl.BlockSpec((1, blk_q, 1), lambda b, j, i: (b, i, 0))
    dkv_spec = pl.BlockSpec((1, blk_kv, hd), lambda b, j, i: (b, j, 0))
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, blk_q=blk_q, blk_kv=blk_kv,
                          scale=scale, causal=causal, group=group),
        grid=(bh, n_kv, n_q),
        in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, st_spec2, st_spec2],
        out_specs=[dkv_spec, dkv_spec],
        out_shape=[jax.ShapeDtypeStruct((bh, skv, hd), jnp.float32)] * 2,
        interpret=interpret,
    )(q, k, v, dout, lse, delta)
    # reduce the group dim into kv heads
    dk = dk_h.reshape(bkv, group, skv, hd).sum(1)
    dv = dv_h.reshape(bkv, group, skv, hd).sum(1)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


flash_attention_diff.defvjp(_flash_fwd, _flash_bwd)
