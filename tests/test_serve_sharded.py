"""Tensor-parallel ServeEngine over a repro.dist mesh.

Contract under test (docs/serving.md §Sharded serving):
  * serve_specs plans exact-TP — weights shard column-parallel only
    (output dims), the slot K/V cache shards head-wise, scheduler state
    replicates — so every cross-device combine is a concatenation, never
    a psum, and sharded serving is BIT-EXACT vs the single-device engine;
  * the FIFO slot scheduler is device-count-agnostic: the same workload
    produces identical tokens with no mesh, a 1-device mesh, and a forced
    8-device host mesh (subprocess tier, like tests/test_dist.py).
"""

import os
import subprocess
import sys
import textwrap
import types

import jax
import numpy as np
import pytest

from repro.configs.base import get_config, reduce_config
from repro.dist.sharding import ShardingPlan, serve_specs, spec_for
from repro.launch.mesh import make_mesh
from repro.models.registry import build_model
from repro.serve.engine import Request, ServeEngine

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fake_mesh(**axes):
    return types.SimpleNamespace(shape=dict(axes))


# --------------------------------------------------------- exact-TP specs

def test_exact_tp_shards_output_dims_only():
    """tp_out_dims_only: a weight may claim the model axis on its LAST dim
    only — column-parallel wq/wi shard, row-parallel wo/w_down replicate
    (their contraction dim must stay whole for the exact all-gather
    combine)."""
    p = ShardingPlan(mesh=_fake_mesh(model=8), tp_out_dims_only=True)
    # column-parallel: output features last -> sharded
    assert tuple(spec_for(p, ("layers", "d_model", "heads"),
                          (2, 256, 256))) == (None, None, "model")
    assert tuple(spec_for(p, ("layers", "d_model", "d_ff"),
                          (2, 256, 768))) == (None, None, "model")
    # row-parallel: the TP-eligible dim is the contraction, not the last
    # dim -> replicated (the plain plan would shard it)
    rp = spec_for(p, ("layers", "heads", "d_model"), (2, 256, 256))
    assert all(s is None for s in tuple(rp))
    loose = spec_for(ShardingPlan(mesh=_fake_mesh(model=8)),
                     ("layers", "heads", "d_model"), (2, 256, 256))
    assert tuple(loose)[1] == "model"
    # activations/caches are untouched by the restriction: the kv cache
    # still shards head-wise
    kv = spec_for(p, ("layers", "batch", "kv_seq", "kv_heads", None),
                  (2, 4, 64, 8, 32), is_param=False)
    assert tuple(kv)[3] == "model"


def test_serve_specs_structure_and_replication():
    """serve_specs mirrors the engine state: a NamedSharding per param and
    cache leaf, (B,) pos + logits replicated (host-side scheduler)."""
    cfg = reduce_config(get_config("qwen2-1.5b"), layers=2, d_model=64,
                        vocab=128)
    mesh = make_mesh((1,), ("model",))
    specs = serve_specs(cfg, mesh, max_batch=2, cache_len=32)
    assert specs.plan.tp_out_dims_only and specs.plan.dp_axes == ()
    model = build_model(cfg)
    ab = model.abstract_params()
    flat_p = jax.tree.leaves(specs.params)
    assert len(flat_p) == len(jax.tree.leaves(ab))
    assert tuple(specs.cache["pos"].spec) in ((), (None,))
    assert tuple(specs.replicated.spec) in ((), (None,))
    assert set(specs.cache) == {"k", "v", "pos"}


def test_one_device_mesh_bitexact_and_device_stats():
    """A mesh of 1 device must be a pure refactor: identical tokens to the
    mesh-less engine, plus the per-device accounting appearing in stats."""
    cfg = reduce_config(get_config("qwen2-1.5b"), layers=2, d_model=64,
                        vocab=128)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))

    def mk():
        return [Request(rid=i, prompt=np.arange(4 + i) % 128,
                        max_new_tokens=3 + 2 * i,
                        temperature=(0.7 if i == 1 else 0.0))
                for i in range(3)]

    ref = ServeEngine(cfg, params, max_batch=2, cache_len=64).run(mk())
    mesh = make_mesh((1,), ("model",))
    eng = ServeEngine(cfg, params, max_batch=2, cache_len=64, mesh=mesh)
    out, stats = eng.run(mk(), collect_stats=True)
    assert out == ref
    e = stats["engine"]
    assert e["devices"] == 1 and len(e["per_device"]) == 1
    d = e["per_device"][0]
    assert d["params_bytes"] > 0 and d["cache_bytes"] > 0
    assert d["occupancy"] == e["occupancy"]
    assert eng.device_stats()[0]["params_bytes"] == d["params_bytes"]


@pytest.mark.parametrize("rows", [1, 4])
def test_exact_tp_fences_only_inside_scope(rows):
    """Under exact_tp_scope the projections fence their activations with
    optimization barriers (and a one-row lm_head pads to 8 rows); outside
    it the trace holds no barrier. The values agree either way."""
    import jax.numpy as jnp
    from repro.models.layers import exact_tp_scope, lm_logits, matmul
    x = jax.random.normal(jax.random.PRNGKey(0), (rows, 1, 64), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 96), jnp.bfloat16)

    def mk():       # a fresh function per trace: the scope is trace-time
        return lambda x, w: lm_logits(matmul(x, w[:, :64]), w)

    f = mk()
    plain = str(jax.make_jaxpr(f)(x, w))
    with exact_tp_scope(make_mesh((1,), ("model",))):
        fenced = str(jax.make_jaxpr(mk())(x, w))
        got = jax.jit(mk())(x, w)
    assert "optimization_barrier" not in plain
    assert "optimization_barrier" in fenced
    assert ("pad" in fenced) == (rows == 1)
    assert got.shape == (rows, 1, 96)
    np.testing.assert_allclose(np.asarray(got), np.asarray(f(x, w)),
                               rtol=1e-5, atol=1e-5)


def test_exact_tp_scopes_name_the_gathers_and_fences():
    """Under exact_tp_scope the row-parallel projection's gather and its
    fences carry the named scopes `tp.gather` and `tp.fence` into the
    lowered program's locations (what a profile's op names read);
    outside the scope neither name appears."""
    import jax.numpy as jnp
    from repro.models.layers import exact_tp_scope, matmul_rp
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 1, 64), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 32), jnp.bfloat16)

    def text():     # a fresh function per trace: the scope is trace-time
        return jax.jit(lambda x, w: matmul_rp(x, w)).lower(x, w).as_text(
            debug_info=True)

    plain = text()
    with exact_tp_scope(make_mesh((1,), ("model",))):
        scoped = text()
    assert "tp.gather" in scoped and "tp.fence" in scoped
    assert "tp.gather" not in plain and "tp.fence" not in plain


# ------------------------------------------------- multi-device (subprocess)

def _run_sub(code: str, devices: int = 8):
    src = os.path.join(REPO_ROOT, "src")
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=(src + os.pathsep + os.environ["PYTHONPATH"]
                           if os.environ.get("PYTHONPATH") else src))
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=560,
                       cwd=REPO_ROOT, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


@pytest.mark.slow
def test_sharded_engine_bitexact_8dev_dense_and_moe():
    """The acceptance criterion: on a forced 8-device CPU mesh the sharded
    engine produces bit-exact tokens vs the single-device engine for dense
    and moe configs — with weights REALLY sharded (local shards smaller
    than the global leaf), greedy and temperature sampling mixed."""
    _run_sub("""
        import jax, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs.base import get_config, reduce_config
        from repro.models.registry import build_model
        from repro.serve.engine import Request, ServeEngine
        for arch, seed in (("qwen2-1.5b", 0), ("deepseek-moe-16b", 1)):
            cfg = reduce_config(get_config(arch), layers=2, d_model=256,
                                vocab=128)
            model = build_model(cfg)
            params = model.init_params(jax.random.PRNGKey(seed))
            rng = np.random.default_rng(3)
            prompts = [rng.integers(0, 128, 4 + i % 5) for i in range(5)]
            def mk():
                return [Request(rid=i, prompt=prompts[i],
                                max_new_tokens=(3 if i % 2 else 9),
                                temperature=(0.7 if i == 1 else 0.0))
                        for i in range(5)]
            ref = ServeEngine(cfg, params, max_batch=2, cache_len=64).run(mk())
            mesh = make_mesh((8,), ("model",))
            eng = ServeEngine(cfg, params, max_batch=2, cache_len=64,
                              mesh=mesh)
            out = eng.run(mk())
            assert out == ref, (arch, out, ref)
            n_sharded = sum(
                1 for l in jax.tree.leaves(eng.params)
                if l.addressable_shards[0].data.size != l.size)
            assert n_sharded > 0, f"{arch}: nothing sharded"
            ds = eng.device_stats()
            assert len(ds) == 8
            assert ds[0]["params_bytes"] < sum(
                l.nbytes for l in jax.tree.leaves(eng.params))
            print(arch, "bit-exact,", n_sharded, "sharded leaves")
    """)


@pytest.mark.slow
def test_sharded_engine_donates_its_cache_8dev():
    """On the 8-device mesh the sharded steps take the cache to keep, as
    the mesh-less ones do: each step deletes the cache shards it was
    handed, both compiled programs alias the cache to their outputs, and
    the tokens stay those of the mesh-less engine."""
    _run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs.base import get_config, reduce_config
        from repro.models.registry import build_model
        from repro.serve.engine import Request, ServeEngine
        cfg = reduce_config(get_config("qwen2-1.5b"), layers=2, d_model=256,
                            vocab=128)
        params = build_model(cfg).init_params(jax.random.PRNGKey(0))
        def mk():
            return [Request(rid=i, prompt=np.arange(4 + i) % 128,
                            max_new_tokens=5) for i in range(3)]
        ref = ServeEngine(cfg, params, max_batch=2, cache_len=64).run(mk())
        eng = ServeEngine(cfg, params, max_batch=2, cache_len=64,
                          mesh=make_mesh((8,), ("model",)))
        eng.reset()
        dec = eng._decode.lower(eng.params, eng._cache,
                                jnp.asarray(eng._cur),
                                jnp.asarray([True, False])).compile()
        pre = eng._prefill_slot.lower(
            eng.params, eng._cache, np.int32(0),
            {"tokens": jnp.zeros((1, 8), jnp.int32)}, np.int32(3)).compile()
        assert "input_output_alias" in dec.as_text()
        assert "input_output_alias" in pre.as_text()
        for r in mk():
            eng.submit(r)
        while not eng.idle:
            held = jax.tree.leaves(eng._cache)
            eng.step()
            assert all(a.is_deleted() for a in held)
        assert eng.outputs == ref, (eng.outputs, ref)
        print("donated, bit-exact")
    """)


@pytest.mark.slow
def test_bench_serve_mesh_emits_per_device_rows(tmp_path):
    """`benchmarks/run.py --serve --mesh tp=8` (no pre-set XLA_FLAGS: the
    harness forces the device count itself) writes one serve_device_<i>
    artifact row per device with occupancy / tok_per_s metrics."""
    import json
    out = str(tmp_path / "BENCH_serve_tp8.json")
    src = os.path.join(REPO_ROOT, "src")
    env = dict(os.environ,
               PYTHONPATH=(src + os.pathsep + os.environ["PYTHONPATH"]
                           if os.environ.get("PYTHONPATH") else src))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-m", "benchmarks.run", "--serve",
                        "--mesh", "tp=8", "--json", out],
                       capture_output=True, text=True, timeout=560,
                       cwd=REPO_ROOT, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    art = json.load(open(out))
    rows = {row["name"]: row for row in art["rows"]}
    for i in range(8):
        m = rows[f"serve_device_{i}"]["metrics"]
        assert 0.0 < m["occupancy"] <= 1.0
        assert m["tok_per_s"] > 0
        assert m["params_mib"] > 0
    # uniform TP split: every device reports the same shard accounting
    sizes = {rows[f"serve_device_{i}"]["metrics"]["params_mib"]
             for i in range(8)}
    assert len(sizes) == 1
    # and the engine row is still there for the serve-smoke comparisons
    assert rows["serve_engine"]["metrics"]["occupancy"] > 0


# A CodeQwen1.5-shaped decoder at a small size: GQA 8:1 with 4 K/V heads
# (one K/V head and 8 query heads per chip on a 4-way `model` axis), QKV
# bias, theta 1e6, untied head; the benchmark's reference and weights.
CODEQWEN_SMALL = """
CFG = {"name": "codeqwen-small", "num_hidden_layers": 2, "hidden_size": 512,
       "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 16,
       "intermediate_size": 512, "vocab_size": 512,
       "tie_word_embeddings": False, "qkv_bias": True, "rope_theta": 1e6,
       "rms_norm_eps": 1e-5, "init_std": 0.02, "init_norm_std": 0.1,
       "init_bias_std": 0.02}
"""


def test_tp4_gqa4_engine_matches_the_f32_reference():
    """A TP4 ServeEngine serving a CodeQwen1.5-shaped model (4 forced
    host devices) prefills, then decodes through its head-sharded cache,
    with logits within TOL of the float32 reference's full forward at
    every served position; the reference's fp8 control misses TOL; and
    the TP4 tokens are the mesh-less engine's.

    TOL = 0.04 on the logits: served logits (|logit| < 2 here) come out
    of bf16 activations with f32 accumulation, one bf16 step there is
    2**-7, and the largest gap seen over two seeds was 0.013; the fp8
    control (e4m3 projections, K/V and head) misses by 0.15-0.17."""
    out = _run_sub(CODEQWEN_SMALL + textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from benchmarks.chip.adapters import dense_lm as adapter
        from benchmarks.chip.reference import dense_lm as ref
        from repro.launch.mesh import make_mesh
        from repro.serve.engine import Request, ServeEngine
        TOL, SEED = 0.04, 15
        mcfg = adapter.model_config(CFG)
        params = jax.jit(lambda kd: adapter.to_program(
            ref.all_weights(CFG, kd)))(ref.key_data(SEED))
        rng = np.random.default_rng(SEED)
        prompts = [rng.integers(0, 512, n).astype(np.int32)
                   for n in (5, 11, 17, 9)]
        def mk():
            return [Request(rid=i, prompt=p, max_new_tokens=8)
                    for i, p in enumerate(prompts)]
        plain = ServeEngine(mcfg, params, max_batch=2, cache_len=64).run(mk())
        eng = ServeEngine(mcfg, params, max_batch=2, cache_len=64,
                          mesh=make_mesh((4,), ("model",)))
        kv = eng._specs.cache["k"].spec
        assert tuple(kv)[3] == "model", kv          # one K/V head per chip
        # the logits each sampled token came from: (rid, token index)
        served, sample = {}, eng._sample_rows
        def record(logits, slots):
            rows = np.asarray(logits, np.float32).reshape(len(slots), -1)
            for s, row in zip(slots, rows):
                if s is not None:
                    served.setdefault((s.rid, s.n_gen), row)
            return sample(logits, slots)
        eng._sample_rows = record
        out = eng.run(mk())
        fc, kd = ref._freeze(CFG), jnp.asarray(ref.key_data(SEED))
        g = ref._gen_globals(fc, kd)
        def forward(tokens, control):
            x = g["embed"].astype(jnp.float32)[jnp.asarray(tokens)][None]
            for layer in range(CFG["num_hidden_layers"]):
                x = ref._apply_layer(fc, control,
                                     ref._gen_layer(fc, kd, jnp.int32(layer)), x)
            with jax.default_matmul_precision("highest"):
                h = ref._rms(x[0], g["final_norm"].astype(jnp.float32),
                             CFG["rms_norm_eps"])
                return np.asarray(ref._mm(h, g["head"].astype(jnp.float32),
                                          control))
        gap, control_gap = 0.0, 0.0
        for rid, p in enumerate(prompts):
            assert len(out[rid]) == 8
            toks = np.concatenate([p, np.asarray(out[rid][:-1], np.int32)])
            r, c = forward(toks, False), forward(toks, True)
            for j in range(8):
                pos = len(p) - 1 + j
                gap = max(gap, float(np.max(np.abs(served[rid, j] - r[pos]))))
                control_gap = max(control_gap,
                                  float(np.max(np.abs(c[pos] - r[pos]))))
        assert gap <= TOL, (gap, TOL)
        assert control_gap > TOL, (control_gap, TOL)
        assert out == plain, (out, plain)
        print("gap", gap, "control", control_gap, "tp4 == mesh-less")
    """), devices=4)
    assert "tp4 == mesh-less" in out


@pytest.mark.slow
def test_tp4_live_decode_route_serves_the_whole_slab_tokens():
    """With the live-line decode kernel forced on (as on a TPU; it runs
    interpreted here), a TP4 engine runs it under shard_map, one K/V head
    and 8 query heads a device, and serves the tokens of the mesh-less
    engine that reads whole slabs."""
    out = _run_sub("""
        import jax, numpy as np
        from repro.configs.base import ModelConfig
        from repro.launch.mesh import make_mesh
        from repro.models import transformer as T
        from repro.models.registry import build_model
        from repro.serve.engine import Request, ServeEngine
        cfg = ModelConfig(name="gqa8-small", family="dense", n_layers=2,
                          d_model=512, n_heads=32, n_kv_heads=4, d_ff=512,
                          vocab_size=256, head_dim=16, qkv_bias=True,
                          rope_theta=1e6, norm_eps=1e-5,
                          tie_embeddings=False, remat="none")
        params = build_model(cfg).init_params(jax.random.PRNGKey(4))
        rng = np.random.default_rng(4)
        prompts = [rng.integers(0, 256, n) for n in (5, 23, 9)]
        def mk():
            return [Request(rid=i, prompt=p, max_new_tokens=(3, 8, 6)[i])
                    for i, p in enumerate(prompts)]
        want = ServeEngine(cfg, params, max_batch=2, cache_len=64).run(mk())
        T.reads_live_lines = lambda per_row, window, ring: (
            per_row and not window and not ring)
        eng = ServeEngine(cfg, params, max_batch=2, cache_len=64,
                          mesh=make_mesh((4,), ("model",)))
        assert eng._kv_blk == 64
        txt = eng._decode.lower(eng.params, eng._fresh_cache(),
                                np.zeros((2, 1), np.int32),
                                np.ones(2, bool)).as_text()
        assert "shard_map" in txt or "sdy.manual_computation" in txt
        out = eng.run(mk())
        assert out == want, (out, want)
        print("tp4 live route == whole slab")
    """, devices=4)
    assert "tp4 live route == whole slab" in out
