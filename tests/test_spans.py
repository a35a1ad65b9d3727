"""The program's spans, device scopes and admission counters, read back
from a profile recorded on the CPU: a tiny ServeEngine run and one
`repro.dispatch("gpp")` call under `jax.profiler.trace`.

Host spans (`jax.profiler.TraceAnnotation`) nest on the host thread:
`serve.round` > `serve.admit` > `serve.prefill`,
`serve.round` > `serve.decode` / `serve.sample`, and
`kernel.dispatch` > `kernel.config`. An admission waits on nothing: its
first token reaches the host in its round's one `serve.sample`. The compiled modules keep the jitted
functions' names, which a profile's reader matches, and their ops carry
the named scopes `serve.decode`, `serve.prefill`, `serve.sample` and
`kernel.<name>`.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.configs.base import get_config, reduce_config
from repro.kernels.gpp import problem
from repro.models.registry import build_model
from repro.serve.engine import Request, ServeEngine

# prompt lengths 3, 9 and 20 prefill buckets 8, 16 and 32
PROMPTS = (3, 9, 20)
BUCKETS = (8, 16, 32)
MAX_NEW = (2, 5, 3)


def _host_spans(log_dir):
    """[(name, start_ns, end_ns, args, line)] of the program's spans."""
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    out = []
    for pl in ProfileData.from_file(path).planes:
        if not pl.name.startswith("/host:"):
            continue
        for i, ln in enumerate(pl.lines):
            for e in ln.events:
                if e.name.startswith(("serve.", "kernel.")):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats),
                                (pl.name, i)))
    return out


def _parent(span, spans):
    """Name of the innermost program span enclosing `span` on its line."""
    name, s, e, _, line = span
    enclosing = [p for p in spans if p is not span and p[4] == line
                 and p[1] <= s and e <= p[2]]
    return min(enclosing, key=lambda p: p[2] - p[1])[0] if enclosing \
        else None


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    cfg = reduce_config(get_config("qwen2-1.5b"), layers=2, d_model=64,
                        vocab=128)
    params = build_model(cfg).init_params(jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_batch=2, cache_len=64)
    reqs = [Request(rid=100 + i, prompt=(np.arange(n) * 7 + i) % 128,
                    max_new_tokens=m)
            for i, (n, m) in enumerate(zip(PROMPTS, MAX_NEW))]
    inp = problem.make_inputs(problem.TINY)
    # compile (and tune) outside the profile, as a warm server would
    eng.run(reqs)
    jax.block_until_ready(repro.dispatch("gpp", inp))
    log_dir = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    reports = []
    with jax.profiler.trace(log_dir, profiler_options=opts):
        eng.reset()
        for r in reqs:
            eng.submit(r)
        while not eng.idle:
            reports.append(eng.step())
        stats = eng.finalize()
        jax.block_until_ready(repro.dispatch("gpp", inp))
    return {"eng": eng, "reqs": reqs, "reports": reports, "stats": stats,
            "spans": _host_spans(log_dir), "inp": inp}


def _named(rec, name):
    return [s for s in rec["spans"] if s[0] == name]


def check_nesting(rec):
    parents = {
        "serve.round": {None},
        "serve.admit": {"serve.round"},
        "serve.prefill": {"serve.admit"},
        "serve.decode": {"serve.round"},
        "serve.sample": {"serve.round"},
        "kernel.dispatch": {None},
        "kernel.config": {"kernel.dispatch"},
    }
    for name, want in parents.items():
        spans = _named(rec, name)
        assert spans, name
        for sp in spans:
            assert _parent(sp, rec["spans"]) in want, (name, sp)
    # a round waits for tokens at most once
    rounds = _named(rec, "serve.round")
    per_round = [[p for p in _named(rec, "serve.sample")
                  if p[4] == r[4] and r[1] <= p[1] and p[2] <= r[2]]
                 for r in rounds]
    assert all(len(p) <= 1 for p in per_round)


def check_admit_args(rec):
    args = sorted((s[3]["rid"], s[3]["prompt_len"], s[3]["bucket"])
                  for s in _named(rec, "serve.admit"))
    assert args == [(r.rid, n, b) for r, n, b in
                    zip(rec["reqs"], PROMPTS, BUCKETS)]


def check_round_args(rec):
    rounds = sorted(_named(rec, "serve.round"), key=lambda s: s[1])
    got = [(s[3]["admitted"], s[3]["rows"]) for s in rounds]
    assert got == [(len(r.admitted), r.decoded) for r in rec["reports"]]
    # the one pull of a round brings its first tokens and decoded rows
    samples = [s[3]["rows"] for s in _named(rec, "serve.sample")
               if _parent(s, rec["spans"]) == "serve.round"]
    assert sorted(samples) == sorted(len(r.admitted) + r.decoded
                                     for r in rec["reports"]
                                     if r.admitted or r.decoded)


def check_counts(rec):
    reports = rec["reports"]
    admitted = sum(len(r.admitted) for r in reports)
    decoded = sum(1 for r in reports if r.decoded)
    assert admitted == len(PROMPTS)
    assert len(_named(rec, "serve.round")) == len(reports)
    assert len(_named(rec, "serve.admit")) == admitted
    assert len(_named(rec, "serve.prefill")) == admitted
    assert len(_named(rec, "serve.decode")) == decoded
    assert len(_named(rec, "serve.sample")) == sum(
        1 for r in reports if r.admitted or r.decoded)
    assert decoded == rec["stats"]["decode_steps"]
    dispatches = _named(rec, "kernel.dispatch")
    assert [(s[3]["name"], s[3]["version"]) for s in dispatches] == \
        [("gpp", repro.get_kernel("gpp").default_version)]
    assert len(_named(rec, "kernel.config")) == 1


def check_prefill_counters(rec):
    st = rec["stats"]
    assert st["prefill_tokens"] == sum(PROMPTS)
    assert st["prefill_bucket_tokens"] == sum(BUCKETS)
    # the pinned engine-stats schema keeps its keys beside the counters
    assert st["prefills"] == len(PROMPTS) and "occupancy" in st


def check_module_names_and_scopes(rec):
    eng = rec["eng"]
    eng.reset()
    active = jnp.asarray(np.array([True, False]))
    decode = eng._decode.lower(eng.params, eng._cache,
                               jnp.asarray(eng._cur), active).compile()
    toks = {"tokens": jnp.zeros((1, 8), jnp.int32)}
    prefill = eng._prefill_slot.lower(eng.params, eng._cache, np.int32(0),
                                      toks, np.int32(3)).compile()
    sample = eng._sample.lower(
        jnp.zeros((2, 1, 128)), jnp.zeros(2), eng.rng,
        jnp.zeros(2, jnp.int32), jnp.zeros(2, jnp.int32)).compile()
    dtext, ptext = decode.as_text(), prefill.as_text()
    assert dtext.startswith("HloModule jit__decode_masked,")
    assert ptext.startswith("HloModule jit__prefill_slot,")
    assert "serve.decode/" in dtext and "serve.prefill/" in ptext
    assert "serve.sample/" in sample.as_text()


def check_kernel_scope(rec):
    lowered = jax.jit(lambda i: repro.dispatch("gpp", i)).lower(rec["inp"])
    assert "kernel.gpp/" in lowered.as_text(debug_info=True)


CHECKS = {f.__name__[len("check_"):]: f for f in (
    check_nesting, check_admit_args, check_round_args, check_counts,
    check_prefill_counters, check_module_names_and_scopes,
    check_kernel_scope)}


@pytest.mark.parametrize("case", sorted(CHECKS))
def test_spans(recorded, case):
    CHECKS[case](recorded)
