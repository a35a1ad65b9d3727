"""The engine runs its decode steps one ahead of the host.

A round enqueues the next batched decode step before it waits for this
step's tokens: the tokens stay on the device as the next step's input,
an admission's first token is sampled there, and which rows decode next
follows from the host's counts. The contract under test:

  * every request's token stream equals a step-by-step reference built
    from the model's own prefill, `decode_step` and the engine's sampler
    (`ServeEngine._sample_batch_impl`), one request alone at a time —
    greedy and temperature rows, admissions mid-flight, 1- and 0-token
    requests, the paged cache with prefix reuse, and the sharded engine
    (a 1-device mesh here, a 4-device CPU mesh in a subprocess);
  * a step in flight is never lost, duplicated or counted twice:
    `idle` stays false until the last tokens are on the host, and
    `finalize()`, `reset()` and both forms of `evict_inflight()` called
    mid-flight keep the streams and the counters whole;
  * `rounds_overlapped` counts the decode steps enqueued while the
    previous step's tokens were still on the device, and is 0 for a
    speculative engine, whose rounds stay synchronous.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, reduce_config
from repro.models.registry import build_model
from repro.serve.engine import PREFILL_BUCKETS, Request, ServeEngine

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAX_BATCH, CACHE_LEN = 2, 64


@pytest.fixture(scope="module")
def small():
    cfg = reduce_config(get_config("qwen2-1.5b"), layers=2, d_model=64,
                        vocab=128)
    return cfg, build_model(cfg).init_params(jax.random.PRNGKey(0))


def reference(cfg, params, reqs, *, max_batch=MAX_BATCH,
              cache_len=CACHE_LEN, rng_seed=0):
    """{rid: tokens}, each request served alone in slot 0 of a
    max_batch-row cache: its prompt prefilled at the engine's bucket, its
    first token sampled from the prefill's logits, then one decode_step
    and one sample per further token, keys fold_in(rid, token index)."""
    model = build_model(cfg)
    prefill = jax.jit(lambda p, c, b, n: model.prefill_into_slot(
        p, c, np.int32(0), b, n, None))

    def _step(p, c, t, active):
        logits, new = model.decode_step(p, c, t, None, active=active)
        new["pos"] = jnp.where(active, new["pos"], c["pos"])
        return logits, new

    step = jax.jit(_step)
    sample = jax.jit(ServeEngine._sample_batch_impl)
    key = jax.random.PRNGKey(rng_seed)
    active = np.arange(max_batch) == 0
    out = {}
    for r in reqs:
        toks = []
        if r.max_new_tokens > 0:
            n = len(r.prompt)
            pad = next((b for b in PREFILL_BUCKETS if n <= b <= cache_len), n)
            x = np.zeros((1, pad), np.int32)
            x[0, :n] = r.prompt
            cache = model.init_cache(max_batch, cache_len)
            cache["pos"] = jnp.zeros((max_batch,), jnp.int32)
            logits, cache = prefill(params, cache, {"tokens": jnp.asarray(x)},
                                    np.int32(n))
            rows = 1
            while True:
                temps = np.where(active, r.temperature, 0.0)[:rows]
                rids = np.where(active, r.rid, -1)[:rows]
                ngens = np.full(rows, len(toks), np.int32)
                tok = int(sample(logits, jnp.asarray(temps, jnp.float32),
                                 key, jnp.asarray(rids, jnp.int32),
                                 jnp.asarray(ngens))[0])
                toks.append(tok)
                if len(toks) == r.max_new_tokens:
                    break
                feed = np.zeros((max_batch, 1), np.int32)
                feed[0, 0] = tok
                logits, cache = step(params, cache, jnp.asarray(feed),
                                     jnp.asarray(active))
                rows = max_batch
        out[r.rid] = toks
    return out


def _req(rid, n, max_new, temperature=0.0, shift=0):
    return Request(rid=rid, prompt=(np.arange(n) * 5 + shift) % 128,
                   max_new_tokens=max_new, temperature=temperature)


def _drive(eng, waves):
    """Submit each wave of requests, then step the given number of
    rounds (None: until idle). Returns the StepReports."""
    eng.reset()
    reports = []
    for reqs, rounds in waves:
        for r in reqs:
            eng.submit(r)
        k = 0
        while not eng.idle and (rounds is None or k < rounds):
            reports.append(eng.step())
            k += 1
    return reports


def _drive_on(eng):
    reports = []
    while not eng.idle:
        reports.append(eng.step())
    return reports


# ------------------------------------------------- streams vs the reference

GREEDY = [_req(0, 5, 7), _req(1, 11, 3), _req(2, 3, 9), _req(3, 17, 5)]
TEMPS = [_req(0, 5, 7, 0.7), _req(1, 11, 4, 1.1), _req(2, 3, 9),
         _req(3, 9, 6, 0.5)]
MID = [[_req(0, 6, 9, 0.8)], [_req(1, 4, 5), _req(2, 13, 4, 1.2)],
       [_req(3, 7, 6, 0.6)]]
SHORT = [[_req(0, 5, 6), _req(1, 4, 1)], [_req(2, 9, 0), _req(3, 6, 1, 0.9),
                                          _req(4, 3, 2)]]
# two prompts share 12 tokens (three 4-token pages): the second admission
# takes a prefix hit and prefills its suffix only
SHARED = np.arange(12) % 128
PAGED = [[Request(rid=0, prompt=np.concatenate([SHARED, [40, 41, 42]]),
                  max_new_tokens=8)],
         [Request(rid=1, prompt=np.concatenate([SHARED, [50, 51]]),
                  max_new_tokens=6, temperature=0.7), _req(2, 5, 4)]]


def case_greedy(cfg, params):
    eng = ServeEngine(cfg, params, max_batch=MAX_BATCH, cache_len=CACHE_LEN)
    return eng.run(list(GREEDY)), GREEDY


def case_temperature(cfg, params):
    eng = ServeEngine(cfg, params, max_batch=MAX_BATCH, cache_len=CACHE_LEN)
    return eng.run(list(TEMPS)), TEMPS


def case_mid_flight(cfg, params):
    eng = ServeEngine(cfg, params, max_batch=MAX_BATCH, cache_len=CACHE_LEN)
    _drive(eng, [(MID[0], 2), (MID[1], 1), (MID[2], None)])
    return eng.outputs, sum(MID, [])


def case_one_and_zero_tokens(cfg, params):
    eng = ServeEngine(cfg, params, max_batch=MAX_BATCH, cache_len=CACHE_LEN)
    reports = _drive(eng, [(SHORT[0], 1), (SHORT[1], None)])
    # the 0-token request finishes at once, the 1-token ones on the round
    # that admits them
    done = {rid: k for k, rep in enumerate(reports) for rid in rep.finished}
    adm = {rid: k for k, rep in enumerate(reports) for rid in rep.admitted}
    assert done[1] == adm[1] and done[3] == adm[3] and 2 not in adm
    return eng.outputs, sum(SHORT, [])


def case_paged_prefix(cfg, params):
    eng = ServeEngine(cfg, params, max_batch=MAX_BATCH, cache_len=CACHE_LEN,
                      kv_page_size=4)
    _drive(eng, [(PAGED[0], 2), (PAGED[1], None)])
    assert eng.kv.stats()["prefix_hits"] == 1
    eng.kv.check_conservation()
    return eng.outputs, sum(PAGED, [])


def case_mesh(cfg, params):
    from repro.launch.mesh import make_mesh
    eng = ServeEngine(cfg, params, max_batch=MAX_BATCH, cache_len=CACHE_LEN,
                      mesh=make_mesh((1,), ("model",)))
    _drive(eng, [(MID[0], 2), (MID[1] + MID[2], None)])
    return eng.outputs, sum(MID, [])


STREAMS = {f.__name__[len("case_"):]: f for f in (
    case_greedy, case_temperature, case_mid_flight,
    case_one_and_zero_tokens, case_paged_prefix, case_mesh)}


@pytest.mark.parametrize("case", sorted(STREAMS) + ["mesh_4dev"])
def test_streams_match_step_by_step_reference(small, case):
    if case == "mesh_4dev":
        return _mesh_4dev()
    cfg, params = small
    got, reqs = STREAMS[case](cfg, params)
    want = reference(cfg, params, reqs)
    assert {r.rid: got[r.rid] for r in reqs} == want
    assert all(len(want[r.rid]) == max(r.max_new_tokens, 0) for r in reqs)


def _mesh_4dev():
    """The sharded engine on a forced 4-device CPU mesh, admissions
    mid-flight, against the reference (run in a subprocess: the device
    count is fixed when JAX starts)."""
    src = os.path.join(REPO_ROOT, "src")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [src, os.path.join(REPO_ROOT, "tests")]
                   + ([os.environ["PYTHONPATH"]]
                      if os.environ.get("PYTHONPATH") else [])))
    code = textwrap.dedent("""
        import jax
        from repro.configs.base import get_config, reduce_config
        from repro.launch.mesh import make_mesh
        from repro.models.registry import build_model
        from repro.serve.engine import ServeEngine
        import test_serve_overlap as t
        cfg = reduce_config(get_config("qwen2-1.5b"), layers=2,
                            d_model=256, vocab=128)
        params = build_model(cfg).init_params(jax.random.PRNGKey(0))
        eng = ServeEngine(cfg, params, max_batch=t.MAX_BATCH,
                          cache_len=t.CACHE_LEN,
                          mesh=make_mesh((4,), ("model",)))
        t._drive(eng, [(t.MID[0], 2), (t.MID[1], 1), (t.MID[2], None)])
        reqs = sum(t.MID, [])
        want = t.reference(cfg, params, reqs)
        got = {r.rid: eng.outputs[r.rid] for r in reqs}
        assert got == want, (got, want)
        print("tp4 overlapped == reference")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=560, cwd=REPO_ROOT, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "tp4 overlapped == reference" in r.stdout


# ------------------------------------------------------ the step in flight

LONG = [_req(0, 5, 9, 0.7), _req(1, 8, 6), _req(2, 3, 7, 1.1)]


def _engine(cfg, params):
    return ServeEngine(cfg, params, max_batch=MAX_BATCH, cache_len=CACHE_LEN)


def _whole(eng, stats, reports, drained=(0, 0)):
    """Every decode step is counted once: in the report of the round that
    brought its tokens to the host, or by a finalize() that drained it
    (`drained` = (steps, rows))."""
    assert stats["decode_steps"] == sum(1 for r in reports
                                        if r.decoded) + drained[0]
    occ = stats["occupancy"] * stats["decode_steps"] * MAX_BATCH
    assert round(occ) == sum(r.decoded for r in reports) + drained[1]
    assert stats["kv_lines_full"] == (stats["decode_steps"] * MAX_BATCH
                                      * CACHE_LEN)


def check_idle_until_last_tokens(cfg, params):
    eng = _engine(cfg, params)
    eng.reset()
    eng.submit(LONG[0])
    while True:
        rep = eng.step()
        done = len(eng.outputs[0]) == LONG[0].max_new_tokens
        assert eng.idle == done, (len(eng.outputs[0]), eng.idle)
        # a step is in flight exactly while a token is still to come
        assert (eng._inflight is not None) == (not done)
        if done:
            assert rep.finished == [0]
            break
    assert eng.outputs[0] == reference(cfg, params, [LONG[0]])[0]


def check_finalize_mid_flight(cfg, params):
    eng = _engine(cfg, params)
    reports = _drive(eng, [(LONG, 3)])
    assert eng._inflight is not None
    rows = len(eng._live(eng._inflight))
    mid = dict(eng.finalize())
    assert eng._inflight is None
    _whole(eng, mid, reports, drained=(1, rows))
    # the drained step's tokens are on the host; a request it ended is
    # reported by the next round
    n_host = {r.rid: len(eng.outputs[r.rid]) for r in LONG}
    reports += _drive_on(eng)
    done = [rid for rep in reports for rid in rep.finished]
    assert sorted(done) == [0, 1, 2]
    assert all(len(eng.outputs[r.rid]) >= n_host[r.rid] for r in LONG)
    _whole(eng, eng.finalize(), reports, drained=(1, rows))
    assert eng.outputs == reference(cfg, params, LONG)


def check_reset_mid_flight(cfg, params):
    eng = _engine(cfg, params)
    _drive(eng, [(LONG, 3)])
    assert eng._inflight is not None
    eng.reset()
    assert eng._inflight is None and eng.idle
    reports = _drive(eng, [(LONG, None)])
    _whole(eng, eng.finalize(), reports)
    assert eng.outputs == reference(cfg, params, LONG)


def check_evict_all_mid_flight(cfg, params):
    eng = _engine(cfg, params)
    reports = _drive(eng, [(LONG, 3)])
    on_host = sum(len(v) for v in eng.outputs.values())
    evicted, wasted = eng.evict_inflight()
    assert wasted == on_host and sorted(r.rid for r in evicted) == [0, 1, 2]
    assert eng.idle and eng.outputs == {}
    for r in evicted:                       # failover: re-served here
        eng.submit(r)
    reports += _drive_on(eng)
    _whole(eng, eng.finalize(), reports)
    assert eng.outputs == reference(cfg, params, LONG)


def check_evict_one_mid_flight(cfg, params):
    eng = _engine(cfg, params)
    reports = _drive(eng, [(LONG, 3)])
    live = {s.rid for _, s in eng._live(eng._inflight)}
    assert 1 in live
    evicted, wasted = eng.evict_inflight(rids={1})
    assert [r.rid for r in evicted] == [1] and wasted > 0
    # the step in flight still carries request 0: it is delivered,
    # request 1's token of it is dropped
    reports += _drive_on(eng)
    assert 1 not in eng.outputs
    _whole(eng, eng.finalize(), reports)
    want = reference(cfg, params, LONG)
    assert eng.outputs == {0: want[0], 2: want[2]}


def check_rounds_overlapped(cfg, params):
    # one request alone: its first decode step starts the engine, every
    # later one is enqueued before the previous step's tokens arrive
    eng = _engine(cfg, params)
    _, st = eng.run([_req(0, 5, 9)], collect_stats=True)
    assert st["engine"]["decode_steps"] == 8
    assert st["engine"]["rounds_overlapped"] == 7
    # a 2-token request decodes once, with nothing in flight
    _, st = eng.run([_req(0, 5, 2)], collect_stats=True)
    assert st["engine"]["rounds_overlapped"] == 0
    # admissions mid-flight join the step enqueued ahead
    _drive(eng, [(MID[0], 2), (MID[1], 1), (MID[2], None)])
    st = eng.finalize()
    assert 0 < st["rounds_overlapped"] < st["decode_steps"]
    # spec rounds stay synchronous
    dcfg = reduce_config(get_config("qwen2-1.5b"), layers=1, d_model=64,
                         vocab=128)
    spec = ServeEngine(cfg, params, max_batch=MAX_BATCH, cache_len=CACHE_LEN,
                       draft_cfg=dcfg, spec_k=2, draft_params=build_model(
                           dcfg).init_params(jax.random.PRNGKey(1)))
    out, st = spec.run(list(LONG), collect_stats=True)
    assert st["engine"]["decode_steps"] > 0
    assert st["engine"]["rounds_overlapped"] == 0
    # its greedy row keeps the plain stream
    assert out[1] == reference(cfg, params, [LONG[1]])[1]


INFLIGHT = {f.__name__[len("check_"):]: f for f in (
    check_idle_until_last_tokens, check_finalize_mid_flight,
    check_reset_mid_flight, check_evict_all_mid_flight,
    check_evict_one_mid_flight, check_rounds_overlapped)}


@pytest.mark.parametrize("case", sorted(INFLIGHT))
def test_step_in_flight(small, case):
    INFLIGHT[case](*small)
