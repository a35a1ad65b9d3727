"""Slot-level continuous-batching engine tests (the serve smoke tier).

Covers the scheduler contract: finished slots refill from the queue
mid-flight (fewer decode steps than the old wave loop, with exact
per-request outputs vs the single-request path), done-row masking /
per-request sampling determinism, the stats schema, and the left-pad
prefill regression (pad tokens must not leak into positions/attention).
"""

from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, reduce_config
from repro.models.registry import build_model
from repro.serve.engine import Request, ServeEngine


@pytest.fixture(scope="module")
def small():
    cfg = reduce_config(get_config("qwen2-1.5b"), layers=2, d_model=64,
                        vocab=128)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, model, params


def _wave_decode_steps(reqs, max_batch):
    """Decode steps the old wave loop would take: consecutive waves of
    max_batch requests, each running until its LONGEST request finishes
    (short requests' rows idle; the next wave can't start early)."""
    steps = 0
    for i in range(0, len(reqs), max_batch):
        steps += max(r.max_new_tokens for r in reqs[i:i + max_batch]) - 1
    return steps


def _slot_sim_steps(reqs, max_batch):
    """Pure-python simulation of the slot scheduler's global decode-step
    count. A round admits into free slots FIFO (the first token comes
    from prefill), then brings one decode step's tokens to the host: the
    step a previous round enqueued ahead, else one enqueued now over the
    rows with a token still to sample; before that it enqueues the next
    step over the rows that still have one. A slot leaves when its
    request's last token is on the host."""
    q = deque(r.max_new_tokens for r in reqs)
    left = [0] * max_batch            # tokens not yet on the host
    sampled = [0] * max_batch         # of those, already sampled
    ahead, steps = None, 0

    def due():
        return [i for i in range(max_batch) if left[i] > sampled[i]]

    def enqueue():
        rows = due()
        for i in rows:
            sampled[i] += 1
        return rows

    while q or any(left):
        for i in range(max_batch):
            if not left[i] and q:
                left[i], sampled[i] = q.popleft(), 1
        rows, ahead = ahead, None
        if not rows and due():
            rows = enqueue()
        if due():
            ahead = enqueue()
        for i in range(max_batch):        # the round's tokens arrive
            n = sampled[i] - (i in (ahead or ()))
            left[i] -= n
            sampled[i] -= n
        steps += bool(rows)
    return steps


def test_slot_refill_beats_wave_and_matches_solo(small):
    """The acceptance workload: mixed max_new_tokens (4 and 64), more
    requests than slots. Slots refill mid-flight, so the engine finishes in
    fewer decode steps than the wave loop — and every request's tokens are
    exactly what the single-request path produces."""
    cfg, model, params = small
    mixed = [4, 64, 4, 64, 4, 4]
    reqs = [Request(rid=i, prompt=np.arange(4 + i) % 128, max_new_tokens=n)
            for i, n in enumerate(mixed)]
    eng = ServeEngine(cfg, params, max_batch=2, cache_len=128)
    out, stats = eng.run(reqs, collect_stats=True)
    steps = stats["engine"]["decode_steps"]
    assert steps == _slot_sim_steps(reqs, 2)
    assert steps < _wave_decode_steps(reqs, 2)
    for r in reqs:
        solo = ServeEngine(cfg, params, max_batch=1, cache_len=128)
        s = solo.run([Request(rid=r.rid, prompt=r.prompt,
                              max_new_tokens=r.max_new_tokens)])
        assert s[r.rid] == out[r.rid], r.rid
        assert len(out[r.rid]) == r.max_new_tokens


def test_sampling_independent_of_batchmates(small):
    """Done-row masking + per-request fold_in keys: a sampled request's
    tokens do not depend on slot placement, batch-mates (including rows
    that finish early and would otherwise keep advancing a shared rng), or
    admission order."""
    cfg, model, params = small

    def tgt():
        return Request(rid=5, prompt=(np.arange(6) * 3) % 128,
                       max_new_tokens=8, temperature=0.7)

    mates = [Request(rid=1, prompt=np.arange(3) % 128, max_new_tokens=2),
             Request(rid=2, prompt=np.arange(9) % 128, max_new_tokens=20,
                     temperature=1.1)]
    a = ServeEngine(cfg, params, max_batch=3, cache_len=64,
                    rng_seed=1).run([tgt()] + mates)
    b = ServeEngine(cfg, params, max_batch=3, cache_len=64,
                    rng_seed=1).run(mates + [tgt()])
    c = ServeEngine(cfg, params, max_batch=1, cache_len=64,
                    rng_seed=1).run([tgt()])
    assert a[5] == b[5] == c[5]
    # distinct rids with the same prompt draw from distinct key streams
    d = ServeEngine(cfg, params, max_batch=2, cache_len=64, rng_seed=1).run(
        [tgt(), Request(rid=6, prompt=(np.arange(6) * 3) % 128,
                        max_new_tokens=8, temperature=0.7)])
    assert d[5] != d[6]


def test_stats_schema(small):
    cfg, model, params = small
    reqs = [Request(rid=i, prompt=np.arange(3 + i) % 128,
                    max_new_tokens=1 + 3 * i) for i in range(5)]
    eng = ServeEngine(cfg, params, max_batch=2, cache_len=64)
    out, stats = eng.run(reqs, collect_stats=True)
    e = stats["engine"]
    assert e["requests"] == e["prefills"] == 5
    assert e["new_tokens"] == sum(len(v) for v in out.values())
    assert 0.0 < e["occupancy"] <= 1.0
    assert e["tok_per_s"] > 0 and e["wall_s"] > 0
    assert eng.last_stats is e
    for r in reqs:
        st = stats["requests"][r.rid]
        assert st.new_tokens == r.max_new_tokens
        assert st.decode_steps == r.max_new_tokens - 1
        assert st.prompt_len == len(r.prompt)
        assert 0.0 <= st.queue_wait_s <= st.ttft_s <= st.total_s
        assert st.tok_per_s > 0


def test_max_new_tokens_one_and_zero(small):
    """A prefill-only request (max_new_tokens=1) frees its slot without
    ever riding a decode step, and a degenerate max_new_tokens=0 request
    completes empty instead of hanging the scheduler."""
    cfg, model, params = small
    reqs = [Request(rid=0, prompt=np.arange(4) % 128, max_new_tokens=1),
            Request(rid=1, prompt=np.arange(5) % 128, max_new_tokens=3),
            Request(rid=2, prompt=np.arange(4) % 128, max_new_tokens=0)]
    out, stats = ServeEngine(cfg, params, max_batch=1, cache_len=64).run(
        reqs, collect_stats=True)
    assert len(out[0]) == 1 and len(out[1]) == 3 and out[2] == []
    assert stats["requests"][0].decode_steps == 0
    assert stats["requests"][2].new_tokens == 0


def test_left_pad_prefill_matches_unpadded(small):
    """Left-pad regression: prefilling a left-padded prompt with pad_lens
    must reproduce the unpadded final-position logits exactly — pad tokens
    leak into neither RoPE positions nor the attention softmax."""
    cfg, model, params = small
    prompt = np.arange(7, dtype=np.int32) % 128
    l0, _ = model.prefill(params, {"tokens": jnp.asarray(prompt[None])})
    for pad in (1, 5):
        padded = np.concatenate([np.zeros(pad, np.int32), prompt])
        lp, _ = model.prefill(
            params, {"tokens": jnp.asarray(padded[None]),
                     "pad_lens": jnp.asarray(np.array([pad], np.int32))})
        np.testing.assert_allclose(np.asarray(lp), np.asarray(l0),
                                   rtol=0, atol=1e-5)


def test_left_pad_prefill_matches_unpadded_moe():
    """Same regression through the MoE stack: pad tokens must not claim
    expert capacity slots from real tokens (moe_ffn token_valid masking).
    Expert capacity itself is shape-derived (static shapes), so the sizes
    here are chosen so padded and unpadded T land on the same capacity
    (t=16 and t=19 with E=8, k=2, cf=1.25 both give C=5)."""
    cfg = reduce_config(get_config("deepseek-moe-16b"), layers=2, d_model=64,
                        vocab=128)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(1))
    prompt = (np.arange(16, dtype=np.int32) * 5) % 128
    l0, _ = model.prefill(params, {"tokens": jnp.asarray(prompt[None])})
    padded = np.concatenate([np.zeros(3, np.int32), prompt])
    lp, _ = model.prefill(
        params, {"tokens": jnp.asarray(padded[None]),
                 "pad_lens": jnp.asarray(np.array([3], np.int32))})
    np.testing.assert_allclose(np.asarray(lp), np.asarray(l0),
                               rtol=0, atol=1e-5)


def test_slot_engine_other_families():
    """prefill_into_slot / per-row decode across the non-dense families:
    recurrent state (ssm), ring-buffer + conv state (hybrid), expert
    capacity dispatch (moe — served at exact prompt length so pads can't
    shift capacity), cross-attention cache rows (encdec) and prepended
    vis tokens (vlm) must all refill without disturbing batch-mates."""
    extras = {
        "whisper-small": lambda c: {
            "frames": jnp.zeros((1, c.enc_seq, c.d_model), jnp.bfloat16)},
        "internvl2-26b": lambda c: {
            "vis": jnp.zeros((1, c.n_vis_tokens, c.d_model), jnp.bfloat16)},
    }
    for arch in ("rwkv6-7b", "hymba-1.5b", "deepseek-moe-16b",
                 "whisper-small", "internvl2-26b"):
        cfg = reduce_config(get_config(arch), layers=2, d_model=64, vocab=128)
        model = build_model(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        mk_extra = extras.get(arch)

        def mk(rid, n, new):
            return Request(rid=rid, prompt=np.arange(n) % 128,
                           max_new_tokens=new,
                           extra=mk_extra(cfg) if mk_extra else None)

        reqs = [mk(i, 4 + i, 3 if i % 2 else 7) for i in range(3)]
        eng = ServeEngine(cfg, params, max_batch=2, cache_len=64)
        out = eng.run(reqs)
        for r in reqs:
            solo = ServeEngine(cfg, params, max_batch=1, cache_len=64)
            s = solo.run([mk(r.rid, len(r.prompt), r.max_new_tokens)])
            assert s[r.rid] == out[r.rid], (arch, r.rid)


def test_vlm_bucket_accounts_for_vis_tokens():
    """A vlm prompt near cache_len must not be bucketed past the room left
    after the prepended vis tokens (regression: bucket 64 + 8 vis lines
    into a 64-line slot blew up the cache write)."""
    cfg = reduce_config(get_config("internvl2-26b"), layers=2, d_model=64,
                        vocab=128)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_batch=1, cache_len=64)
    prompt = np.arange(50, dtype=np.int32) % 128   # 50 + 8 vis + 5 new <= 64
    out = eng.run([Request(rid=0, prompt=prompt, max_new_tokens=5,
                           extra={"vis": jnp.zeros((1, cfg.n_vis_tokens,
                                                    cfg.d_model),
                                                   jnp.bfloat16)})])
    assert len(out[0]) == 5


def test_moe_slot_prefill_matches_exact_model():
    """MoE requests are served at exact prompt length (no shape bucket):
    the engine's first sampled token must equal greedy argmax of
    model.prefill on the raw prompt — pad tokens must not claim expert
    capacity (the bug this guards: bucketed right-pad shifting routing)."""
    cfg = reduce_config(get_config("deepseek-moe-16b"), layers=2, d_model=64,
                        vocab=128)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    prompt = (np.arange(5, dtype=np.int32) * 7) % 128   # 5: not a bucket size
    logits, _ = model.prefill(params, {"tokens": jnp.asarray(prompt[None])})
    expect = int(jnp.argmax(logits.astype(jnp.float32).reshape(-1)))
    eng = ServeEngine(cfg, params, max_batch=2, cache_len=64)
    out = eng.run([Request(rid=0, prompt=prompt, max_new_tokens=1)])
    assert out[0] == [expect]
