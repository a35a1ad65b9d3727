"""The K/V cache is updated in place.

  * Per-row decode writes each row's one new line with a scatter
    (transformer.write_kv_lines) into the stacked cache that the layer
    loop carries. It must give exactly the cache and the attention output
    that the one-hot select it replaced gave; that select is kept below as
    the reference.
  * The engine donates the cache to every program that takes and returns
    it, so after a step the arrays it passed are deleted, and the compiled
    programs alias the cache to their outputs. Paged and speculative
    engines still serve the plain engine's tokens.
  * reset() lets the last run's cache go before it allocates the next, so
    two caches never live at once.
"""

import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, reduce_config
from repro.models import attention as attn_lib
from repro.models import transformer as T
from repro.models.registry import build_model
from repro.serve.engine import Request, ServeEngine

LINES = 16      # cache lines per row
ROWS = 4
LAYER = 1       # the layer written; layer 0 must stay as it was


@pytest.fixture(scope="module")
def small():
    cfg = reduce_config(get_config("qwen2-1.5b"), layers=2, d_model=64,
                        vocab=128)
    params = build_model(cfg).init_params(jax.random.PRNGKey(0))
    return cfg, params


def _one_hot_decode(lp, x, cfg, *, cache_k, cache_v, pos, ring):
    """The per-row write the scatter replaced, on one layer's
    (B,L,KvH,Hd) cache: a one-hot select over every line of every row (and
    the lockstep dynamic_update_slice for a scalar pos)."""
    b_ = x.shape[0]
    per_row = jnp.ndim(pos) == 1
    q, k, v = T._qkv(lp, x, cfg)
    pvec = pos[:, None, None] if per_row else jnp.full((1,), pos, jnp.int32)
    q = T.apply_rope(q.swapaxes(1, 2), pvec, cfg.rope_theta).swapaxes(1, 2)
    k = T.apply_rope(k.swapaxes(1, 2), pvec, cfg.rope_theta).swapaxes(1, 2)
    lcache = cache_k.shape[1]
    slot = jnp.mod(pos, lcache) if ring else pos
    if per_row:
        oh = jnp.arange(lcache)[None, :] == slot[:, None]
        cache_k = jnp.where(oh[:, :, None, None], k, cache_k)
        cache_v = jnp.where(oh[:, :, None, None], v, cache_v)
    else:
        cache_k = jax.lax.dynamic_update_slice(cache_k, k, (0, slot, 0, 0))
        cache_v = jax.lax.dynamic_update_slice(cache_v, v, (0, slot, 0, 0))
    clen = jnp.minimum(pos + 1, lcache) if ring else pos + 1
    out = attn_lib.decode_attention(q, cache_k, cache_v, clen)
    out = T.matmul_rp(out.reshape(b_, 1, -1), lp["wo"])
    return out, (cache_k, cache_v)


# each case: the positions of successive decode steps, and the ring flag
WRITE_CASES = {
    "first_line": ([[0, 0, 0, 0]], False),
    "last_line": ([[LINES - 1, 3, LINES - 1, 0]], False),
    # rows 1 and 3 are finished slots: their position is held, so the
    # second step writes their line again at the same place
    "held_rows": ([[5, 2, 7, 1], [6, 2, 8, 1]], False),
    "ring": ([[LINES + 3, 2 * LINES - 1, 1, 5 * LINES],
              [LINES + 4, 2 * LINES, 2, 5 * LINES]], True),
    "lockstep": ([4, 5], False),
}


@pytest.mark.parametrize("case", sorted(WRITE_CASES))
def test_inplace_write_matches_one_hot_select(small, case):
    cfg, params = small
    steps, ring = WRITE_CASES[case]
    lp = jax.tree.map(lambda a: a[LAYER], params["layers"]["attn"])
    shape = (cfg.n_layers, ROWS, LINES, cfg.n_kv_heads, cfg.head_dim)
    keys = jax.random.split(jax.random.PRNGKey(7), 2 + len(steps))
    ck = jax.random.normal(keys[0], shape, jnp.bfloat16)
    cv = jax.random.normal(keys[1], shape, jnp.bfloat16)
    ref_k, ref_v = ck, cv

    new = jax.jit(lambda lp, x, ck, cv, layer, pos: T.attn_block_decode(
        lp, x, cfg, cache_k=ck, cache_v=cv, layer=layer, pos=pos,
        ring=ring))
    old = jax.jit(lambda lp, x, ck, cv, pos: _one_hot_decode(
        lp, x, cfg, cache_k=ck, cache_v=cv, pos=pos, ring=ring))
    for i, pos in enumerate(steps):
        pos = jnp.asarray(pos, jnp.int32)
        x = jax.random.normal(keys[2 + i], (ROWS, 1, cfg.d_model),
                              jnp.bfloat16)
        out, (ck, cv) = new(lp, x, ck, cv, jnp.int32(LAYER), pos)
        want, (wk, wv) = old(lp, x, ref_k[LAYER], ref_v[LAYER], pos)
        ref_k, ref_v = ref_k.at[LAYER].set(wk), ref_v.at[LAYER].set(wv)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
        assert ck.dtype == ref_k.dtype and cv.dtype == ref_v.dtype
        np.testing.assert_array_equal(np.asarray(ck), np.asarray(ref_k))
        np.testing.assert_array_equal(np.asarray(cv), np.asarray(ref_v))


def _requests(prompts, max_new=6):
    return [Request(rid=i, prompt=np.asarray(p, np.int32),
                    max_new_tokens=max_new) for i, p in enumerate(prompts)]


PROMPTS = [np.arange(20) % 128, np.arange(20) % 128, np.arange(3, 9)]


@pytest.mark.parametrize("kind", ["plain", "paged", "spec"])
def test_engine_donates_its_cache_to_each_step(small, kind):
    """After step() no array of the caches the engine held before it is
    alive, and the run still serves the plain engine's tokens (the paged
    run takes a prefix hit: restore, then the donated suffix prefill)."""
    cfg, params = small
    kw = {}
    if kind == "paged":
        kw = dict(kv_page_size=4)
    elif kind == "spec":
        dcfg = reduce_config(get_config("qwen2-1.5b"), layers=1, d_model=64,
                             vocab=128)
        kw = dict(draft_cfg=dcfg, spec_k=2, draft_params=build_model(
            dcfg).init_params(jax.random.PRNGKey(1)))
    want = ServeEngine(cfg, params, max_batch=2, cache_len=64).run(
        _requests(PROMPTS))
    eng = ServeEngine(cfg, params, max_batch=2, cache_len=64, **kw)
    eng.reset()
    for r in _requests(PROMPTS):
        eng.submit(r)
    while not eng.idle:
        held = jax.tree.leaves((eng._cache, eng._draft_cache))
        eng.step()
        assert all(a.is_deleted() for a in held)
    assert eng.outputs == want
    if kind == "paged":
        assert eng.kv.stats()["prefix_hits"] > 0
        eng.kv.check_conservation()


@pytest.mark.parametrize("program", ["_decode", "_prefill_slot"])
def test_engine_programs_alias_the_cache(small, program):
    cfg, params = small
    eng = ServeEngine(cfg, params, max_batch=2, cache_len=64)
    eng.reset()
    if program == "_decode":
        args = (jnp.asarray(eng._cur), jnp.asarray([True, False]))
    else:
        args = (np.int32(0), {"tokens": jnp.zeros((1, 8), jnp.int32)},
                np.int32(3))
    fn = getattr(eng, program)
    text = fn.lower(eng.params, eng._cache, *args).compile().as_text()
    assert "input_output_alias" in text


def test_reset_frees_the_last_cache_before_allocating(small):
    cfg, params = small
    eng = ServeEngine(cfg, params, max_batch=2, cache_len=64)
    eng.reset()
    last = [weakref.ref(a) for a in jax.tree.leaves(eng._cache)]
    alive = []
    fresh = eng._fresh_cache

    def counting_fresh():
        gc.collect()
        alive.append(sum(r() is not None for r in last))
        return fresh()

    eng._fresh_cache = counting_fresh
    eng.reset()
    assert alive == [0]
