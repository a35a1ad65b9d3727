"""Decode attention over the live K/V lines of a stacked slot cache, as a
Pallas TPU kernel.

The slot scheduler's cache is one stacked array per K and V,
(n_layers, B, L, KvH, Hd), where row b holds `lens[b]` live lines and the
rest of its L lines are dead (stale or never written). Attention that
slices `cache[layer]` and masks reads every line of every row; this
kernel reads only the live ones:

  * the caches stay in HBM (`memory_space=pl.ANY`) and are never sliced
    outside the kernel: the operand of a custom call is materialised, so
    a `cache[layer]` argument would itself be the whole-slab read;
  * `layer` and `lens` are scalar-prefetched into SMEM; row b's lines
    are fetched in ceil(lens[b] / blk) blocks of `blk` lines, each block
    holding every K/V head of those lines (one contiguous DMA);
  * a block is read as blk x KvH rows of Hd, line-major, heads
    interleaved: the cache's own bytes (its two minor dims merge into
    one without a copy), so no per-head slice is taken in the kernel.
    Each query row is scored against every row of the block and the
    rows of the other K/V heads are masked out, which spends KvH times
    the (small) MXU work of a per-head product;
  * one grid step walks the flat list of live blocks, row after row,
    with the next block's DMA started before the current one is used
    (two VMEM buffers), so the copy engine runs across row boundaries
    and the work scales with live blocks, not with B x L / blk grid
    steps;
  * online softmax keeps f32 (m, l, acc) per query head, the math of
    `kernels/paged/paged.py::_online_block_scan`;
  * a row with lens == 0 reads nothing and returns zeros. Lines past
    lens inside the last block are masked out of the scores and zeroed
    in V, so whatever they hold never reaches the output.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(layer_ref, lens_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sem, nxt_ref, m_ref, l_ref, acc_ref, *,
            blk: int, kvh: int, scale: float):
    rows = lens_ref.shape[0]
    layer = layer_ref[0]
    h = q_ref.shape[1]

    def copies(b, i, slot):
        lines = pl.ds(pl.multiple_of(i * blk * kvh, blk * kvh), blk * kvh)
        return (pltpu.make_async_copy(k_hbm.at[layer, b, lines],
                                      kbuf.at[slot], sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, b, lines],
                                      vbuf.at[slot], sem.at[1, slot]))

    # nxt[r]: the first row >= r with a live line (rows if none); n: the
    # live blocks of all rows
    nxt_ref[rows] = rows

    def scan_rows(j, n):
        r = rows - 1 - j
        live = lens_ref[r]
        nxt_ref[r] = jnp.where(live > 0, r, nxt_ref[r + 1])
        return n + (live + blk - 1) // blk

    n = jax.lax.fori_loop(0, rows, scan_rows, jnp.int32(0))
    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n > 0)
    def _first():
        for c in copies(nxt_ref[0], 0, 0):
            c.start()

    # block row j holds line j // kvh of K/V head j % kvh; query head r
    # reads K/V head r // (h / kvh)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, blk * kvh), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (blk * kvh, 1), 0)
    own_head = (col % kvh) == (
        jax.lax.broadcasted_iota(jnp.int32, (h, 1), 0) // (h // kvh))

    def step(t, carry):
        b, i = carry
        slot = t % 2
        live = lens_ref[b]
        last = i + 1 == (live + blk - 1) // blk
        next_b = jnp.where(last, nxt_ref[b + 1], b)
        next_i = jnp.where(last, 0, i + 1)

        @pl.when(t + 1 < n)
        def _prefetch():
            for c in copies(next_b, next_i, 1 - slot):
                c.start()

        @pl.when(i == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        for c in copies(b, i, slot):
            c.wait()
        s = jax.lax.dot_general(
            q_ref[b], kbuf[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (H, blk*KvH)
        valid = i * blk + col // kvh < live
        if kvh > 1:
            valid = valid & own_head
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        v = vbuf[slot]
        v = jnp.where(i * blk + row // kvh < live, v, jnp.zeros_like(v))
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

        @pl.when(last)
        def _emit():
            o_ref[b] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)

        return next_b, next_i

    jax.lax.fori_loop(0, n, step, (nxt_ref[0], jnp.int32(0)))


def live_decode_attention(q, cache_k, cache_v, layer, lens, *, blk: int,
                          interpret: bool = False) -> jax.Array:
    """q: (B, H, Hd); cache_k/v: (n_layers, B, L, KvH, Hd); layer: scalar;
    lens: (B,) int32 live lines per row, read as at most L -> (B, H, Hd).
    L % blk == 0."""
    b, h, hd = q.shape
    n_layers, _, lines, kvh, _ = cache_k.shape
    assert lines % blk == 0, (lines, blk)
    # (line, head) rows: the layout the cache already has in HBM
    flat = (n_layers, b, lines * kvh, hd)
    buf = (2, blk * kvh, hd)
    whole = lambda *_: (0, 0, 0)                              # noqa: E731
    return pl.pallas_call(
        functools.partial(_kernel, blk=blk, kvh=kvh, scale=hd ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[pl.BlockSpec((b, h, hd), whole),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((b, h, hd), whole),
            scratch_shapes=[pltpu.VMEM(buf, cache_k.dtype),
                            pltpu.VMEM(buf, cache_v.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.SMEM((b + 1,), jnp.int32),
                            pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      jnp.minimum(lens, lines).astype(jnp.int32),
      q.astype(cache_k.dtype), cache_k.reshape(flat), cache_v.reshape(flat))


def vmem_bytes(b: int, h: int, kvh: int, hd: int, blk: int,
               itemsize: int = 2) -> int:
    """VMEM working set: the double-buffered K and V blocks, q and the
    output (each double-buffered by the pipeline), and the f32 softmax
    state of one row (m and l pad to 128 lanes)."""
    return (2 * 2 * blk * kvh * hd * itemsize
            + 2 * 2 * b * h * hd * itemsize
            + h * (hd + 2 * 128) * 4)
