"""Differential acceptance tier for speculative decoding (serve/spec.py).

The load-bearing contract: at temperature 0, a spec-decode engine's output
is TOKEN-FOR-TOKEN identical to plain decoding for every draft/target pair
and every draft depth k — acceptance is argmax agreement, the correction
token is the plain argmax at the first mismatch, and the bonus token is
the plain argmax past a full accept, so the emitted chain IS the plain
greedy chain regardless of what the draft proposes. At temperature > 0 the
guarantee is distributional (rejection sampling), pinned here only at the
accounting level: accepted + rejected + bonus == tokens_emitted.

Run by the CI serve-smoke job next to the serve/kvcache tiers.
"""

import functools
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _prop import given, settings, st
from repro.configs.base import get_config, reduce_config
from repro.models.registry import build_model
from repro.serve import spec as spec_lib
from repro.serve.engine import Request, ServeEngine

VOCAB = 128


def _reduced(arch, *, layers=2, d_model=64, seed):
    cfg = reduce_config(get_config(arch), layers=layers, d_model=d_model,
                        vocab=VOCAB)
    params = build_model(cfg).init_params(jax.random.PRNGKey(seed))
    return cfg, params


@pytest.fixture(scope="module")
def qwen_pair():
    """The config zoo's qwen pair, reduced: qwen2_1_5b drafts for
    qwen2_5_32b. Different architectures AND different init seeds, so the
    draft genuinely disagrees with the target sometimes."""
    tgt = _reduced("qwen2.5-32b", seed=0)
    drf = _reduced("qwen2-1.5b", layers=1, seed=1)
    return tgt, drf


@pytest.fixture(scope="module")
def phi_pair():
    """phi4_mini_3_8b drafting for a larger dense phi-style target."""
    tgt = _reduced("phi4-mini-3.8b", seed=0)
    drf = _reduced("phi4-mini-3.8b", layers=1, d_model=32, seed=2)
    return tgt, drf


def _requests(n=4, max_new=8, temperature=0.0):
    rng = np.random.RandomState(3)
    return [Request(rid=i, prompt=rng.randint(0, VOCAB, size=3 + (i % 4)),
                    max_new_tokens=max_new, temperature=temperature)
            for i in range(n)]


# -------------------------------------------------------------- differential
# tier: spec-decode == plain decode, token for token, at temperature 0

@pytest.mark.parametrize("pair", ["qwen_pair", "phi_pair"])
@pytest.mark.parametrize("k", [1, 2, 4])
def test_spec_bit_exact_vs_plain_greedy(pair, k, request):
    (cfg, params), (dcfg, dparams) = request.getfixturevalue(pair)
    reqs = _requests()
    plain = ServeEngine(cfg, params, max_batch=2, cache_len=64, rng_seed=0)
    want = plain.run(list(reqs))
    spec = ServeEngine(cfg, params, max_batch=2, cache_len=64, rng_seed=0,
                       draft_cfg=dcfg, draft_params=dparams, spec_k=k)
    got = spec.run(list(reqs))
    assert got == want
    sp = spec.last_stats["spec"]
    assert sp["k"] == k
    assert sp["tokens_emitted"] > 0
    assert sp["accepted"] + sp["rejected"] + sp["bonus"] \
        == sp["tokens_emitted"]


def test_spec_self_draft_accepts_everything(qwen_pair):
    """Draft == target (self-speculation): every candidate must be
    accepted, every round emits k+1 tokens (until the budget caps it) —
    pins the accept loop's upper edge and the bonus-token path."""
    (cfg, params), _ = qwen_pair
    reqs = _requests(n=2, max_new=9)
    plain = ServeEngine(cfg, params, max_batch=2, cache_len=64, rng_seed=0)
    want = plain.run(list(reqs))
    spec = ServeEngine(cfg, params, max_batch=2, cache_len=64, rng_seed=0,
                       draft_cfg=cfg, draft_params=params, spec_k=2)
    got = spec.run(list(reqs))
    assert got == want
    sp = spec.last_stats["spec"]
    assert sp["rejected"] == 0                  # argmax always agrees
    assert sp["acceptance_rate"] == 1.0
    assert sp["accepted_tokens_per_step"] > 1.0


def test_spec_batch_mate_independence(qwen_pair):
    """A spec slot next to other slots (including a temperature slot)
    produces the same tokens as serving it alone — sampling is
    per-request and the verify's per-row masking leaks nothing."""
    (cfg, params), (dcfg, dparams) = qwen_pair
    rng = np.random.RandomState(5)
    reqs = [Request(rid=0, prompt=rng.randint(0, VOCAB, size=4),
                    max_new_tokens=8, temperature=0.0),
            Request(rid=1, prompt=rng.randint(0, VOCAB, size=6),
                    max_new_tokens=5, temperature=0.7),
            Request(rid=2, prompt=rng.randint(0, VOCAB, size=3),
                    max_new_tokens=7, temperature=0.0)]

    def spec_engine():
        return ServeEngine(cfg, params, max_batch=2, cache_len=64,
                           rng_seed=0, draft_cfg=dcfg,
                           draft_params=dparams, spec_k=2)

    batched = spec_engine().run(list(reqs))
    for r in reqs:
        solo = spec_engine().run([r])
        assert solo[r.rid] == batched[r.rid], r.rid


def test_spec_rejects_mesh_and_requires_draft(qwen_pair):
    (cfg, params), (dcfg, dparams) = qwen_pair
    with pytest.raises(ValueError, match="draft_cfg"):
        ServeEngine(cfg, params, spec_k=2)
    bad = reduce_config(get_config("qwen2-1.5b"), layers=1, d_model=32,
                        vocab=VOCAB + 1)
    bad_params = build_model(bad).init_params(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="vocab"):
        ServeEngine(cfg, params, draft_cfg=bad, draft_params=bad_params,
                    spec_k=2)
    moe = reduce_config(get_config("deepseek-moe-16b"), layers=2,
                        d_model=64, vocab=VOCAB)
    with pytest.raises(ValueError, match="target must be a dense"):
        ServeEngine(moe, None, draft_cfg=dcfg, draft_params=dparams,
                    spec_k=2)


def test_spec_composes_with_paged_cache(qwen_pair):
    """Spec + paged K/V: draft lines are slot-resident (never
    page-accounted), pages cover committed target lines only — outputs
    stay bit-exact and page conservation holds after the run."""
    (cfg, params), (dcfg, dparams) = qwen_pair
    reqs = _requests()
    plain = ServeEngine(cfg, params, max_batch=2, cache_len=64, rng_seed=0)
    want = plain.run(list(reqs))
    spec = ServeEngine(cfg, params, max_batch=2, cache_len=64, rng_seed=0,
                       kv_page_size=8, draft_cfg=dcfg,
                       draft_params=dparams, spec_k=2)
    got = spec.run(list(reqs))
    assert got == want
    spec.kv.check_conservation()
    assert spec.kv.pages_live == spec.kv._index_pages


# ----------------------------------------------------------------- property:
# acceptance accounting — accepted + rejected + bonus == tokens_emitted
# for random seeds x k x logits, and over whole engine traces

@settings(max_examples=12)
@given(seed=st.integers(0, 1 << 16), k=st.sampled_from([1, 2, 4]),
       temperature=st.sampled_from([0.0, 0.5, 1.0]))
def test_accept_accounting_identity(seed, k, temperature):
    rng = np.random.RandomState(seed)
    draft_toks = rng.randint(0, VOCAB, size=k)
    draft_logits = (rng.randn(k, VOCAB) * 2).astype(np.float32)
    target_logits = (rng.randn(k + 1, VOCAB) * 2).astype(np.float32)
    emitted, kinds = spec_lib.accept_tokens(
        draft_toks, draft_logits, target_logits, temperature=temperature,
        base_key=jax.random.PRNGKey(0), rid=seed % 7, n_gen=seed % 11)
    c = Counter(kinds)
    assert c["accepted"] + c["rejected"] + c["bonus"] == len(emitted)
    assert 1 <= len(emitted) <= k + 1
    # every untruncated round ends with exactly one terminal token —
    # either the correction at the first rejection or the bonus
    assert c["rejected"] + c["bonus"] == 1
    # accepted tokens are a prefix of the draft proposal
    assert emitted[:c["accepted"]] == list(draft_toks[:c["accepted"]])


@functools.lru_cache(maxsize=None)
def _prop_engines(k):
    """One (plain, spec) engine pair per k, shared across property
    examples so the compiled steps are reused (run() resets state)."""
    cfg, params = _reduced("qwen2-1.5b", seed=0)
    dcfg, dparams = _reduced("qwen2-1.5b", layers=1, seed=4)
    plain = ServeEngine(cfg, params, max_batch=2, cache_len=64, rng_seed=0)
    spec = ServeEngine(cfg, params, max_batch=2, cache_len=64, rng_seed=0,
                       draft_cfg=dcfg, draft_params=dparams, spec_k=k)
    return plain, spec


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 1 << 10), k=st.sampled_from([1, 2, 4]),
       temperature=st.sampled_from([0.0, 0.8]))
def test_engine_accounting_over_random_traces(seed, k, temperature):
    rng = np.random.RandomState(seed)
    reqs = [Request(rid=i, prompt=rng.randint(0, VOCAB,
                                              size=int(rng.randint(2, 8))),
                    max_new_tokens=int(rng.randint(1, 9)),
                    temperature=temperature)
            for i in range(int(rng.randint(1, 4)))]
    plain, spec = _prop_engines(k)
    got = spec.run(list(reqs))
    sp = spec.last_stats["spec"]
    assert sp["accepted"] + sp["rejected"] + sp["bonus"] \
        == sp["tokens_emitted"]
    assert sum(len(v) for v in got.values()) \
        == sum(r.max_new_tokens for r in reqs)
    if temperature == 0.0:
        assert got == plain.run(list(reqs))


# ---------------------------------------------------------------- satellite:
# the decode-specialized kernel route: paged_decode "verify" vs the ref
# oracle at k > 1, over float and int8 pools and both q ranks, and the
# registry still audits clean with the multi-query canonical key censused

def _paged_problem(qlen, seed=0):
    b, h, kvh, page, npt, hd = 2, 4, 2, 8, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    qshape = (b, h, hd) if qlen == 1 else (b, qlen, h, hd)
    q = jax.random.normal(ks[0], qshape, jnp.bfloat16)
    kpool = jax.random.normal(ks[1], (b * npt, page, kvh, hd), jnp.bfloat16)
    vpool = jax.random.normal(ks[2], (b * npt, page, kvh, hd), jnp.bfloat16)
    table = jnp.arange(b * npt, dtype=jnp.int32).reshape(b, npt)
    cache_len = jnp.array([max(qlen, 7), npt * page], jnp.int32)
    return q, kpool, vpool, table, cache_len


@pytest.mark.parametrize("qlen", [1, 3, 5])
def test_verify_kernel_matches_ref_oracle(qlen):
    from repro.kernels.paged import paged as paged_lib
    q, kpool, vpool, table, cache_len = _paged_problem(qlen)
    ref = paged_lib.paged_decode_ref(q, kpool, vpool, table, cache_len)
    for ppb in (1, 2, 4):
        got = paged_lib.paged_decode_verify(
            q, kpool, vpool, table, cache_len, pages_per_block=ppb)
        assert got.shape == ref.shape and got.dtype == ref.dtype
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32),
            atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("qlen", [1, 4])
def test_verify_kernel_int8_pool_matches_dequant_ref(qlen):
    from repro.kernels.paged import paged as paged_lib
    q, kpool, vpool, table, cache_len = _paged_problem(qlen, seed=1)
    qk, kscale = paged_lib.quantize_pool(kpool)
    qv, vscale = paged_lib.quantize_pool(vpool)
    got = paged_lib.paged_decode_verify(
        q, qk, qv, table, cache_len, pages_per_block=2,
        kscale=kscale, vscale=vscale)
    # the oracle sees what the kernel sees: the dequantized pool
    deqk = (qk.astype(jnp.float32) * kscale[:, None, None, None])
    deqv = (qv.astype(jnp.float32) * vscale[:, None, None, None])
    ref = paged_lib.paged_decode_ref(
        q, deqk.astype(jnp.bfloat16), deqv.astype(jnp.bfloat16),
        table, cache_len)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        atol=2e-2, rtol=2e-2)
    with pytest.raises(ValueError, match="kscale"):
        paged_lib.paged_decode_verify(q, qk, qv, table, cache_len,
                                      pages_per_block=2)


def test_multi_query_attention_matches_sequential_static_cache():
    """The static-cache side of the verify route: decode_attention_multi
    over Q candidate lines equals Q sequential decode_attention calls —
    the identity that makes a Q-token decode_step (the verify) bit-exact
    vs Q one-token decode_step calls."""
    from repro.models import attention as attn_lib
    b, qn, h, hd, length = 2, 3, 4, 16, 24
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (b, qn, h, hd), jnp.bfloat16)
    kc = jax.random.normal(ks[1], (b, length, h, hd), jnp.bfloat16)
    vc = jax.random.normal(ks[2], (b, length, h, hd), jnp.bfloat16)
    cache_len = jnp.array([qn + 5, qn + 9], jnp.int32)
    multi = attn_lib.decode_attention_multi(q, kc, vc, cache_len)
    for j in range(qn):
        one = attn_lib.decode_attention(
            q[:, j:j + 1], kc, vc, cache_len - (qn - 1 - j))[:, 0]
        np.testing.assert_array_equal(np.asarray(multi[:, j]),
                                      np.asarray(one))


def test_registry_routes_rank4_q_and_audits_clean():
    """Every paged_decode version accepts the multi-query problem (the
    census traces the full canonical x version cross product), blockwise
    results agree with ref, and the registry audits clean — KV001/VMEM001
    stay quiet with the qlen=4 canonical key in play."""
    from repro.analyze import audit_registry
    from repro.kernels import api
    from repro.kernels.paged.kernel_def import KERNEL, PagedKey

    assert "verify" in KERNEL.versions and "verify" in KERNEL.tunable
    keys = KERNEL.canonical_keys()
    assert any(k.qlen > 1 for k in keys)
    mq = next(k for k in keys if k.qlen > 1)
    # 7-part key_dims round-trips; 6-part stays back-compatible
    assert KERNEL.key_from_dims(mq.key_dims()) == mq
    assert KERNEL.key_from_dims("2x2x2x16x4x32") == \
        PagedKey(b=2, h=2, kvh=2, page=16, npt=4, hd=32)

    args, kw = KERNEL.make_example(mq)
    ref = KERNEL.run(*args, version="ref", config=None, interpret=True, **kw)
    assert ref.shape == (mq.b, mq.qlen, mq.h, mq.hd)
    for version in ("gather", "int8", "verify"):
        cfg = KERNEL.static_config(mq, version)
        got = KERNEL.run(*args, version=version, config=cfg,
                         interpret=True, **kw)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32),
            atol=5e-2, rtol=5e-2)

    report = audit_registry(["paged_decode"])
    n_keys = len(api.get_kernel("paged_decode").canonical_keys())
    assert len(report.censuses) == n_keys * len(KERNEL.versions)
    assert report.errors == [], [f.row() for f in report.errors]


# ---------------------------------------------------------------- satellite:
# evict_inflight mid-verify must roll the slot back to the last ACCEPTED
# token, not the speculated tip (regression for the fenced-replica path,
# where a round can die between verify and accept)

def test_evict_mid_verify_rolls_back_to_last_accepted(qwen_pair):
    (cfg, params), (dcfg, dparams) = qwen_pair
    eng = ServeEngine(cfg, params, max_batch=2, cache_len=64, rng_seed=0,
                      draft_cfg=dcfg, draft_params=dparams, spec_k=4)
    eng.reset()
    eng.submit(Request(rid=7, prompt=np.arange(5, dtype=np.int32),
                       max_new_tokens=8))
    eng.step()                                 # admission + one spec round
    s = eng._slots[0]
    assert s is not None and s.rid == 7
    committed = s.prompt_len + s.n_gen - 1
    assert int(np.asarray(eng._cache["pos"])[0]) == committed

    # drive the engine into the mid-verify state an interrupted round
    # leaves behind: verify launched (device pos at the speculated tip,
    # _spec_inflight armed) but the accept/rollback never ran
    active = jnp.asarray(np.array([st is not None for st in eng._slots]))
    eng._spec_inflight[0] = committed
    vtoks = np.zeros((eng.max_batch, eng.spec_k + 1), np.int32)
    vtoks[:, 0:1] = eng._cur
    _, eng._cache = eng._verify(eng.params, eng._cache,
                                jnp.asarray(vtoks), active)
    _, eng._draft_cache = eng._draft_decode(
        eng.draft_params, eng._draft_cache, jnp.asarray(eng._cur), active)
    assert int(np.asarray(eng._cache["pos"])[0]) \
        == committed + eng.spec_k + 1          # at the tip

    evicted, _ = eng.evict_inflight(rids={7})
    assert [r.rid for r in evicted] == [7]
    # the rollback: last accepted line, NOT the speculated tip
    assert int(np.asarray(eng._cache["pos"])[0]) == committed
    assert int(np.asarray(eng._draft_cache["pos"])[0]) == committed

    # and the evicted request re-serves bit-exact vs plain decode
    plain = ServeEngine(cfg, params, max_batch=2, cache_len=64, rng_seed=0)
    want = plain.run([Request(rid=7, prompt=np.arange(5, dtype=np.int32),
                              max_new_tokens=8)])
    eng.submit(evicted[0])
    while not eng.idle:
        eng.step()
    assert eng.outputs[7] == want[7]
