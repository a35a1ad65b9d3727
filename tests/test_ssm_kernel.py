"""Selective-scan Pallas kernel: shape sweeps + hypothesis seeds vs the
models/mamba.ssm_scan oracle, interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _prop import given, settings, st

from repro.kernels.ssm.ssm_scan import kernel_hbm_bytes, ssm_scan_pallas
from repro.models.mamba import ssm_scan


def _mk(b, t, c, n, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (b, t, c))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, c)) - 2)
    bm = jax.random.normal(ks[2], (b, t, n))
    cm = jax.random.normal(ks[3], (b, t, n))
    alog = jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)
                   )[None].repeat(c, 0)
    d = jax.random.normal(ks[5], (c,))
    h0 = 0.1 * jax.random.normal(ks[6], (b, c, n))
    return x, dt, bm, cm, alog, d, h0


@pytest.mark.parametrize("b,t,c,n,blk", [
    (2, 64, 8, 4, 4),
    (1, 128, 16, 8, 8),
    (3, 32, 8, 16, 8),
    (1, 16, 200, 4, 128),      # ragged last channel block (hymba's 1600)
])
def test_shape_sweep(b, t, c, n, blk):
    args = _mk(b, t, c, n)
    y1, h1 = ssm_scan(*args)
    y2, h2 = ssm_scan_pallas(*args, blk_c=blk, interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               atol=1e-4, rtol=1e-4)


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 1000))
def test_property_seeds(seed):
    args = _mk(2, 32, 4, 4, seed=seed)
    y1, h1 = ssm_scan(*args)
    y2, h2 = ssm_scan_pallas(*args, blk_c=4, interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=1e-4, rtol=1e-4)


def test_mamba_path_pallas_route_matches_chunked():
    """cfg.ssm_impl="pallas" routes the hybrid prefill scan through the
    kernel registry (ops.ssm_scan with an explicit shard-local SsmKey);
    output must match the chunked XLA path bit-for-bit here (1 device,
    same f32 math) and the tune cache must hold a key for the LOCAL
    channel count the call site derived."""
    import dataclasses

    from repro.configs.base import get_config, reduce_config
    from repro.models.registry import build_model
    cfg = reduce_config(get_config("hymba-1.5b"), layers=2, d_model=64,
                        vocab=128)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    toks = jnp.asarray((np.arange(8) % 128)[None])
    l0, _ = model.prefill(params, {"tokens": toks})
    mp = build_model(dataclasses.replace(cfg, ssm_impl="pallas"))
    lp, _ = mp.prefill(params, {"tokens": toks})
    np.testing.assert_allclose(np.asarray(lp, dtype=np.float32),
                               np.asarray(l0, dtype=np.float32),
                               rtol=0, atol=1e-5)


def test_dispatch_problem_key_override_tunes_local_shard():
    """api.dispatch(problem_key=...) keys config resolution on the given
    (shard-local) problem instead of the global operand shapes — the
    contract the sharded ServeEngine's kernel call sites rely on."""
    from repro.kernels import api
    from repro.kernels.ssm.kernel_def import SsmKey
    from repro.tune import tuner
    args = _mk(1, 8, 16, 4)
    local = SsmKey(b=1, t=8, c=8, n=4)          # c/2: a 2-way TP shard
    y, hT = api.dispatch("ssm", *args, problem_key=local, interpret=True)
    yref, href = ssm_scan(*args)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yref),
                               atol=1e-4, rtol=1e-4)
    # the tuned config came from the LOCAL key and tiles the local slab
    tc = tuner.tune_kernel("ssm", local)
    assert local.key_dims() in tc.key
    assert local.c % tc.config.blk_c == 0


def test_kernel_traffic_model_sane():
    # kernel I/O must be far below the chunked-XLA materialization:
    # ~6 (B,T,C,N) f32 arrays vs ~3 (B,T,C) + small
    b, t, c, n = 16, 4096, 6400, 16
    kernel = kernel_hbm_bytes(b, t, c, n)
    chunked = 6 * b * t * c * n * 4
    assert kernel < chunked / 10
