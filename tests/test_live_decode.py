"""The `live_decode` kernel: decode attention that reads only each row's
live K/V lines of one layer of the stacked slot cache.

  * Against its reference, `decode_attention` over `cache[layer]`
    (interpret mode): row lengths of 0, 1, one short of a block, exactly
    a block, one past it and the whole cache; 6 and 8 query heads a K/V
    head (qwen2-1.5b's 12/2, and one TP4 chip's 8/1); a layer in the
    middle of the stack. Lines past a row's length, and every line of a
    row with none, hold NaN: the output must not move, which shows that
    the kernel never uses a dead line.
  * The route: a ServeEngine whose decode takes the kernel (its predicate
    forced on, as on a TPU) serves the tokens of the whole-slab route,
    and freed slots keep their held position.
  * The counters: `kv_lines_read` and `kv_lines_full` for a scripted
    two-request schedule, by hand.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, reduce_config
from repro.kernels import api
from repro.kernels.live_decode.kernel_def import (LiveDecodeConfig,
                                                  LiveDecodeKey, block_for)
from repro.models import transformer as T
from repro.models.attention import decode_attention
from repro.models.registry import build_model
from repro.serve.engine import Request, ServeEngine

BLK, LINES, LAYERS, LAYER = 16, 64, 3, 1
# 0, 1, one short of a block, a block, one past it, the whole cache
LENS = [0, 1, BLK - 1, BLK, BLK + 1, LINES]


def _inputs(h, kvh, hd=32, seed=0):
    rng = np.random.default_rng(seed)
    shape = (LAYERS, len(LENS), LINES, kvh, hd)
    q = jnp.asarray(rng.normal(size=(len(LENS), h, hd)), jnp.float32)
    ck = jnp.asarray(rng.normal(size=shape), jnp.float32)
    cv = jnp.asarray(rng.normal(size=shape), jnp.float32)
    return q, ck, cv, jnp.asarray(LENS, jnp.int32)


def _kernel(q, ck, cv, lens):
    return api.dispatch("live_decode", q, ck, cv, jnp.int32(LAYER), lens,
                        config=LiveDecodeConfig(blk=BLK), interpret=True)


@pytest.mark.parametrize("h,kvh", [(12, 2), (8, 1)], ids=["G6", "G8"])
def test_kernel_matches_decode_attention(h, kvh):
    q, ck, cv, lens = _inputs(h, kvh)
    got = np.asarray(_kernel(q, ck, cv, lens), np.float32)
    want = np.asarray(decode_attention(q[:, None], ck[LAYER], cv[LAYER],
                                       lens)[:, 0], np.float32)
    live = np.asarray(lens) > 0
    # the reference rounds its probabilities and output to bf16
    np.testing.assert_allclose(got[live], want[live], atol=1e-2, rtol=1e-2)
    assert not got[~live].any()                 # a row of no line: zeros
    ref = api.dispatch("live_decode", q, ck, cv, jnp.int32(LAYER), lens,
                       version="ref")
    np.testing.assert_array_equal(np.asarray(ref, np.float32)[live],
                                  want[live])
    assert not np.asarray(ref, np.float32)[~live].any()


@pytest.mark.parametrize("h,kvh", [(12, 2), (8, 1)], ids=["G6", "G8"])
def test_kernel_never_reads_a_dead_line(h, kvh):
    q, ck, cv, lens = _inputs(h, kvh, seed=1)
    dead = jnp.arange(LINES)[None, :] >= lens[:, None]          # (B, L)
    dead = dead[None, :, :, None, None]
    nan_k = jnp.where(dead, jnp.nan, ck)
    nan_v = jnp.where(dead, jnp.nan, cv)
    clean = np.asarray(_kernel(q, ck, cv, lens))
    poisoned = np.asarray(_kernel(q, nan_k, nan_v, lens))
    assert np.isfinite(poisoned).all()
    np.testing.assert_array_equal(poisoned, clean)


def test_kernel_reads_the_layer_it_is_given():
    """Every other layer of the stack is NaN: the middle one alone is
    read, and its output is that of a one-layer stack."""
    q, ck, cv, lens = _inputs(12, 2, seed=2)
    other = (jnp.arange(LAYERS) != LAYER)[:, None, None, None, None]
    out = _kernel(q, jnp.where(other, jnp.nan, ck),
                  jnp.where(other, jnp.nan, cv), lens)
    alone = api.dispatch("live_decode", q, ck[LAYER:LAYER + 1],
                         cv[LAYER:LAYER + 1], jnp.int32(0), lens,
                         config=LiveDecodeConfig(blk=BLK), interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(alone))


def test_block_and_key():
    assert block_for(2048) == 256 and block_for(64) == 64
    assert block_for(48) == 48 and block_for(300) == 150
    q, ck, cv, lens = _inputs(12, 2)
    key = api.get_kernel("live_decode").problem_key(q, ck, cv, LAYER, lens)
    assert key == LiveDecodeKey(b=6, h=12, kvh=2, n_layers=3, lines=64,
                                hd=32)
    assert key.key_dims() == "6x12x2x3x64x32"


# ----------------------------------------------------------------- route

def _live_everywhere(per_row, window, ring):
    """reads_live_lines as on a TPU: the kernel runs (interpreted here)."""
    return per_row and not window and not ring


@pytest.fixture(scope="module")
def g6():
    """qwen2-1.5b's grouping at a small width: 12 query heads over 2 K/V
    heads of 32, two layers."""
    cfg = reduce_config(get_config("qwen2-1.5b"), layers=2, d_model=384,
                        vocab=128)
    assert (cfg.n_heads, cfg.n_kv_heads) == (12, 2)
    return cfg, build_model(cfg).init_params(jax.random.PRNGKey(0))


def _requests():
    # three requests over two slots: the short one frees its slot early,
    # which stays free (held) once the queue is empty
    return [Request(rid=0, prompt=np.arange(5) % 128, max_new_tokens=12),
            Request(rid=1, prompt=(np.arange(19) * 7) % 128,
                    max_new_tokens=3),
            Request(rid=2, prompt=(np.arange(9) * 3) % 128,
                    max_new_tokens=6)]


def test_engine_route_serves_the_whole_slab_tokens(g6, monkeypatch):
    cfg, params = g6
    want = ServeEngine(cfg, params, max_batch=2, cache_len=64).run(
        _requests())
    monkeypatch.setattr(T, "reads_live_lines", _live_everywhere)
    eng = ServeEngine(cfg, params, max_batch=2, cache_len=64)
    assert eng._kv_blk == 64
    eng.reset()
    for r in _requests():
        eng.submit(r)
    held = {}
    while not eng.idle:
        eng.step()
        pos = np.asarray(eng._cache["pos"])
        for i, s in enumerate(eng._slots):
            if s is None:
                held.setdefault(i, int(pos[i]))
                assert pos[i] == held[i], (i, pos[i], held[i])
            else:
                held.pop(i, None)
    assert eng.outputs == want
    # request 2 ended in slot 1 after 5 decode steps, holding 9 + 5
    # lines there, while slot 0 decoded on alone
    assert held == {0: 5 + 11, 1: 9 + 5}


def test_kv_line_counters_by_hand(g6, monkeypatch):
    """Block 16 over 64 lines, two slots. Request 0 (prompt 5, 4 tokens)
    decodes 3 steps holding 6, 7, 8 lines; request 1 (prompt 20, 2
    tokens) decodes once in the first step, holding 21 lines, then its
    slot is free."""
    cfg, params = g6
    monkeypatch.setattr(T, "reads_live_lines", _live_everywhere)
    monkeypatch.setattr(
        "repro.kernels.live_decode.kernel_def.block_for", lambda lines: 16)
    eng = ServeEngine(cfg, params, max_batch=2, cache_len=64)
    _, stats = eng.run([
        Request(rid=0, prompt=np.arange(5) % 128, max_new_tokens=4),
        Request(rid=1, prompt=np.arange(20) % 128, max_new_tokens=2)],
        collect_stats=True)
    e = stats["engine"]
    assert e["decode_steps"] == 3
    assert e["kv_lines_full"] == 3 * 2 * 64
    assert e["kv_lines_read"] == (16 + 32) + 16 + 16
    # where attention reads the whole slab, both counters agree
    monkeypatch.setattr(T, "reads_live_lines", lambda *a: False)
    e = ServeEngine(cfg, params, max_batch=2, cache_len=64).run(
        [Request(rid=0, prompt=np.arange(5) % 128, max_new_tokens=4)],
        collect_stats=True)[1]["engine"]
    assert e["kv_lines_read"] == e["kv_lines_full"] == 3 * 2 * 64
