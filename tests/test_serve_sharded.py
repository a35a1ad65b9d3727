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


# ------------------------------------------------- multi-device (subprocess)

def _run_sub(code: str):
    src = os.path.join(REPO_ROOT, "src")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
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
