"""Unified kernel registry (repro.kernels.api): registration + dispatch for
all three families, the generalized (kernel, ProblemKey, backend, version)
tune cache, shared backend policy (REPRO_INTERPRET), the deprecation shims'
bit-identical forwarding, the lazy `import repro` surface, and the bench
artifact's config-provenance (config-churn) channel."""

import json
import os
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro import backend
from repro.kernels import api
from repro.kernels.flash import ops as flash_ops
from repro.kernels.flash.kernel_def import FlashBlockConfig, FlashKey
from repro.kernels.gpp import ops as gpp_ops
from repro.kernels.gpp import problem, ref
from repro.kernels.ssm import ops as ssm_ops
from repro.kernels.ssm.kernel_def import SsmKey, SsmScanConfig
from repro.tune import tuner

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _flash_inputs(seed=0, b=2, s=64, h=4, kvh=2, hd=16, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), dtype)
    k = jax.random.normal(ks[1], (b, s, kvh, hd), dtype)
    v = jax.random.normal(ks[2], (b, s, kvh, hd), dtype)
    return q, k, v


def _ssm_inputs(key=SsmKey(b=2, t=32, c=8, n=4), seed=0):
    return api.get_kernel("ssm").make_example(key, seed=seed)[0]


# ---------------------------------------------------------------------------
# registry + dispatch
# ---------------------------------------------------------------------------

def test_all_families_registered():
    assert {"gpp", "flash", "ssm"} <= set(api.list_kernels())
    for name in api.list_kernels():
        k = api.get_kernel(name)
        assert k.default_version in k.versions
        assert set(k.tunable) <= set(k.versions)


def test_unknown_kernel_and_version():
    with pytest.raises(KeyError):
        api.get_kernel("nope")
    inp = problem.make_inputs(problem.TINY)
    with pytest.raises(ValueError):
        api.dispatch("gpp", inp, version="v99")


def test_dispatch_each_kernel_matches_reference():
    """Every registered family dispatches at TINY size on CPU interpret and
    agrees with its reference implementation (the CI registry-smoke
    contract)."""
    # gpp: default (tuned v10) vs complex128 oracle
    inp = problem.make_inputs(problem.TINY)
    ar, xr = ref.ref_numpy(inp)
    a, x = api.dispatch("gpp", inp)
    assert float(np.max(np.abs(np.asarray(a) - ar))
                 / np.max(np.abs(ar))) < 1e-5
    # flash: pallas vs exact-softmax ref
    q, k, v = _flash_inputs()
    out = api.dispatch("flash", q, k, v)
    out_ref = api.dispatch("flash", q, k, v, version="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               atol=1e-4, rtol=1e-4)
    # ssm: pallas (tuned blk_c) vs sequential-scan ref
    args = _ssm_inputs()
    y, hT = api.dispatch("ssm", *args)
    y_ref, hT_ref = api.dispatch("ssm", *args, version="ref")
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hT_ref),
                               atol=1e-4, rtol=1e-4)


def test_ssm_chunked_version_matches_ref():
    args = _ssm_inputs()
    y_c, h_c = api.dispatch("ssm", *args, version="chunked")
    y_r, h_r = api.dispatch("ssm", *args, version="ref")
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_r),
                               atol=1e-4, rtol=1e-4)


def test_problem_keys_are_stable():
    q, k, v = _flash_inputs()
    fk = api.get_kernel("flash").problem_key(q, k, v, causal=True)
    assert fk == FlashKey(b=2, h=4, kvh=2, sq=64, skv=64, hd=16, causal=True)
    assert fk.key_dims() == "2x4x2x64x64x16c"
    sk = api.get_kernel("ssm").problem_key(*_ssm_inputs())
    assert sk.key_dims() == "2x32x8x4"
    assert problem.TINY.key_dims() == "64x8x8x2"


# ---------------------------------------------------------------------------
# generalized tune cache
# ---------------------------------------------------------------------------

def test_flash_and_ssm_tune_through_generalized_cache(tmp_path):
    """Acceptance: flash and ssm each get a tuned config through the
    generalized repro.tune cache, keyed (kernel, ProblemKey, backend,
    version), and a fresh process state reloads it from disk."""
    cache = str(tmp_path / "tune")
    tuner.clear_memo()
    fkey = FlashKey(b=2, h=4, kvh=2, sq=64, skv=64, hd=16)
    skey = SsmKey(b=2, t=32, c=8, n=4)
    tcs = {
        "flash": tuner.tune_kernel("flash", fkey, cache_dir=cache,
                                   measure_mode=False),
        "ssm": tuner.tune_kernel("ssm", skey, cache_dir=cache,
                                 measure_mode=False),
    }
    assert isinstance(tcs["flash"].config, FlashBlockConfig)
    assert isinstance(tcs["ssm"].config, SsmScanConfig)
    # key format: kernel|dims|backend|version
    assert tcs["flash"].key == "flash|2x4x2x64x64x16c|cpu|pallas"
    assert tcs["ssm"].key == "ssm|2x32x8x4|cpu|pallas"
    on_disk = json.load(open(os.path.join(cache, tuner.CACHE_FILE)))
    assert set(on_disk) == {tcs["flash"].key, tcs["ssm"].key}

    # fresh process state -> disk hit, config reconstructed per kernel
    tuner.clear_memo()
    for kernel, key in (("flash", fkey), ("ssm", skey)):
        tc2 = tuner.tune_kernel(kernel, key, cache_dir=cache)
        assert tc2.source == "cache"
        assert tc2.config == tcs[kernel].config
        assert tc2.kernel == kernel


def test_gpp_and_flash_keys_do_not_collide(tmp_path):
    """The kernel name is part of the key — same dims under two kernels
    stay distinct cache entries."""
    cache = str(tmp_path / "tune")
    tuner.clear_memo()
    tc_g = tuner.tune(problem.TINY, cache_dir=cache, measure_mode=False)
    assert tc_g.key.startswith("gpp|")
    tc_f = tuner.tune_kernel(
        "flash", FlashKey(b=2, h=4, kvh=2, sq=64, skv=64, hd=16),
        cache_dir=cache, measure_mode=False)
    on_disk = json.load(open(os.path.join(cache, tuner.CACHE_FILE)))
    assert tc_g.key in on_disk and tc_f.key in on_disk


def test_tuned_config_feasible_for_every_kernel():
    """rank_kernel's winners tile the problem exactly and fit VMEM."""
    from repro.core.hw import TPU_V5E
    fkey = FlashKey(b=8, h=16, kvh=4, sq=4096, skv=4096, hd=128)
    cfg, _ = tuner.rank_kernel("flash", fkey)[0]
    assert fkey.sq % cfg.blk_q == 0 and fkey.skv % cfg.blk_kv == 0
    assert cfg.vmem_bytes(fkey.hd) <= TPU_V5E.vmem_bytes
    skey = SsmKey(b=16, t=4096, c=6400, n=16)
    scfg, _ = tuner.rank_kernel("ssm", skey)[0]
    assert skey.c % scfg.blk_c == 0
    assert scfg.vmem_bytes(skey) <= TPU_V5E.vmem_bytes


# ---------------------------------------------------------------------------
# backend policy (REPRO_INTERPRET)
# ---------------------------------------------------------------------------

def test_backend_interpret_env_override(monkeypatch):
    monkeypatch.delenv(backend.INTERPRET_ENV, raising=False)
    assert backend.default_interpret() is True       # CPU container
    monkeypatch.setenv(backend.INTERPRET_ENV, "1")
    assert backend.default_interpret() is True
    monkeypatch.setenv(backend.INTERPRET_ENV, "0")
    assert backend.default_interpret() is False
    assert backend.resolve_interpret(None) is False  # env wins over default
    assert backend.resolve_interpret(True) is True   # explicit wins over env
    monkeypatch.setenv(backend.INTERPRET_ENV, "maybe")
    with pytest.raises(ValueError):
        backend.default_interpret()


def test_on_tpu_false_on_cpu():
    assert backend.on_tpu() is False
    assert backend.backend_name() == "cpu"


def test_compile_cache_placement(monkeypatch, tmp_path):
    """$JAX_COMPILATION_CACHE_DIR wins and is left for JAX to read; without
    it the cache goes to the fixed <repo>/.jax_cache."""
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(backend.CACHE_ENV, str(tmp_path))
        assert backend.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was    # nothing set
        monkeypatch.delenv(backend.CACHE_ENV)
        d = backend.enable_compile_cache()
        assert os.path.samefile(os.path.dirname(d), ROOT)
        assert os.path.basename(d) == ".jax_cache"
        assert jax.config.jax_compilation_cache_dir == d
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


# ---------------------------------------------------------------------------
# deprecation shims: bit-identical + a single warning
# ---------------------------------------------------------------------------

def test_gpp_shim_bit_identical_across_versions():
    inp = problem.make_inputs(problem.TINY)
    for v in ("v0", "v1", "v2", "v3", "v4", "v5", "v6", "v7", "v8", "v9",
              "v10"):
        a_old, x_old = gpp_ops.gpp(inp, version=v)
        a_new, x_new = api.dispatch("gpp", inp, version=v)
        assert np.array_equal(np.asarray(a_old), np.asarray(a_new)), v
        assert np.array_equal(np.asarray(x_old), np.asarray(x_new)), v


def test_flash_shim_bit_identical():
    q, k, v = _flash_inputs(seed=7)
    old = flash_ops.flash_attention(q, k, v, blk_q=32, blk_kv=32)
    new = api.dispatch("flash", q, k, v,
                       config=FlashBlockConfig("x", 32, 32))
    assert np.array_equal(np.asarray(old), np.asarray(new))
    # the shim's frozen default (256/256, clamped) == explicit legacy config
    old_def = flash_ops.flash_attention(q, k, v)
    new_def = api.dispatch("flash", q, k, v,
                           config=FlashBlockConfig("x", 256, 256))
    assert np.array_equal(np.asarray(old_def), np.asarray(new_def))


@pytest.mark.parametrize("call", [
    lambda: gpp_ops.gpp(problem.make_inputs(problem.TINY), version="v5"),
    lambda: flash_ops.flash_attention(*_flash_inputs(), blk_q=32, blk_kv=32),
], ids=["gpp", "flash"])
def test_shims_warn_exactly_once(call, monkeypatch):
    import repro.kernels as kernels_pkg
    monkeypatch.setattr(kernels_pkg, "_WARNED", set())
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        call()
        call()
    dep = [w for w in rec if issubclass(w.category, DeprecationWarning)
           and "deprecated" in str(w.message)]
    assert len(dep) == 1, [str(w.message) for w in rec]


def test_dispatch_odd_shapes_fall_back_to_clamped_static():
    """Shapes the power-of-two tune menu can't tile (empty config space)
    must still dispatch via the clamped static config — the legacy entry
    points handled e.g. sq=48 or c=6 by clamping, and dispatch must not
    regress that."""
    # s=48: the clamp (min) happens to tile; s=300: nothing in the menu
    # divides it and a plain min() clamp (256) would silently NaN the tail
    # rows — the divisor clamp must pick a tiling block instead
    for s in (48, 300):
        q, k, v = _flash_inputs(s=s)
        out = api.dispatch("flash", q, k, v)
        out_ref = api.dispatch("flash", q, k, v, version="ref")
        assert not np.any(np.isnan(np.asarray(out))), s
        np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                                   atol=1e-4, rtol=1e-4)
    # c=6: the clamp (min) takes the whole dim; c=130: a 128 block leaves
    # a ragged last block, whose padding channels must not leak into y
    for c in (6, 130):
        args = _ssm_inputs(SsmKey(b=2, t=16, c=c, n=4))
        y, hT = api.dispatch("ssm", *args)
        y_ref, _ = api.dispatch("ssm", *args, version="ref")
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=1e-4, rtol=1e-4)


def test_dispatch_rejects_stray_kwargs():
    """A misspelled or legacy kwarg must raise, not be silently swallowed
    (e.g. the old flash signature's blk_q, or causal typoed as casual)."""
    q, k, v = _flash_inputs()
    with pytest.raises(TypeError):
        api.dispatch("flash", q, k, v, blk_q=32, blk_kv=32)
    with pytest.raises(TypeError):
        api.dispatch("flash", q, k, v, casual=False)
    with pytest.raises(TypeError):
        api.dispatch("gpp", problem.make_inputs(problem.TINY), blk_ig=32)


def test_ssm_ops_is_not_deprecated():
    """The new ssm op layer is a first-class wrapper, no warning."""
    args = _ssm_inputs()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        ssm_ops.ssm_scan(*args, version="ref")
    assert not [w for w in rec if issubclass(w.category, DeprecationWarning)]


# ---------------------------------------------------------------------------
# lazy public surface
# ---------------------------------------------------------------------------

def test_repro_public_surface():
    assert set(repro.__all__) >= {"get_kernel", "dispatch", "list_kernels",
                                  "ServeEngine", "build_model",
                                  "run_journey"}
    assert repro.dispatch is api.dispatch
    assert repro.get_kernel is api.get_kernel
    from repro.serve.engine import ServeEngine
    assert repro.ServeEngine is ServeEngine
    with pytest.raises(AttributeError):
        repro.not_a_symbol


# ---------------------------------------------------------------------------
# bench artifact: config provenance + churn notes
# ---------------------------------------------------------------------------

def test_artifact_kernel_config_and_churn_note(tmp_path):
    sys.path.insert(0, ROOT)
    from benchmarks import report
    kc_old = {"kernel": "flash", "version": "pallas",
              "config": {"name": "pallas", "blk_q": 256, "blk_kv": 256},
              "source": "model"}
    kc_new = dict(kc_old, config={"name": "pallas", "blk_q": 512,
                                  "blk_kv": 128}, source="cache")
    old = [{"name": "tuned_flash", "us_per_call": None,
            "derived": "modeled_s=1.0", "kernel_config": kc_old}]
    new = [{"name": "tuned_flash", "us_per_call": None,
            "derived": "modeled_s=1.0", "kernel_config": kc_new}]
    art_old = report.make_artifact(old)
    assert art_old["rows"][0]["kernel_config"] == kc_old
    regs, imps, notes = report.compare(art_old, report.make_artifact(new))
    assert not regs and not imps
    assert any("config churn" in n and "tuned_flash" in n for n in notes)
    # identical configs -> no churn note
    _, _, notes2 = report.compare(art_old, report.make_artifact(old))
    assert not any("config churn" in n for n in notes2)
