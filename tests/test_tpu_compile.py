"""Compile the main-path Pallas kernels at real widths for a described TPU
v5e chip — no chip attached, nothing executed.

Interpret mode accepts blocks the TPU kernel compiler (Mosaic) refuses,
so every other kernel test is blind to tiling. Each test here lowers one
kernel for one v5e chip of a `v5e:2x2` topology and asserts that the
compiled program holds the Pallas kernel (`tpu_custom_call`):

  * gpp v7-v9 at si214, and v10 under the tuner's top candidates;
  * gpp v6 at si214 is refused (its aqsm tile's lane dim is 8), which is
    why the journey marks it as not compiled on the chip;
  * flash attention at qwen2-1.5b prefill shapes, forward and backward;
  * the selective scan at hymba-1.5b widths under every channel block
    the ssm tuner may pick (1600 channels: a ragged last block).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and test workers import every
test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import api
from repro.kernels.flash import flash
from repro.kernels.gpp import pallas_gpp, problem
from repro.kernels.ssm import ssm_scan
from repro.kernels.ssm.kernel_def import SsmKey
from repro.tune import tuner

SI214 = problem.SI214
HYMBA_SCAN = SsmKey(b=1, t=1024, c=1600, n=16)   # d_model 1600, ssm_state 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 - any failure to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _gpp_inputs(one_chip, size):
    shapes = {"wtilde_re": (size.ncouls, size.ngpown),
              "aqsn_re": (size.ncouls, size.nbands),
              "aqsm_re": (size.ngpown, size.nbands),
              "wx": (size.nw, size.nbands), "vcoul": (size.ncouls,)}
    for plane in ("wtilde", "aqsn", "aqsm"):
        shapes[f"{plane}_im"] = shapes[f"{plane}_re"]
    shapes["eps_re"] = shapes["eps_im"] = shapes["wtilde_re"]
    return {k: _sds(v, jnp.float32, one_chip) for k, v in shapes.items()}


@pytest.mark.parametrize("version", ["v7", "v8", "v9"])
def test_gpp_static_versions_compile_at_si214(one_chip, version):
    cfg = pallas_gpp.CONFIGS[version]
    assert cfg.mosaic_tileable(SI214)
    txt = _compile_text(
        lambda d: pallas_gpp.gpp_pallas(d, cfg, interpret=False),
        _gpp_inputs(one_chip, SI214))
    assert "tpu_custom_call" in txt


def test_gpp_v10_candidates_compile_at_si214(one_chip):
    """The configs the tuner's measurement pass would time on a chip."""
    inp = _gpp_inputs(one_chip, SI214)
    for cfg, _ in tuner.rank(SI214)[:3]:
        txt = _compile_text(
            lambda d, cfg=cfg: pallas_gpp.gpp_pallas(d, cfg,
                                                     interpret=False), inp)
        assert "tpu_custom_call" in txt, cfg


def test_gpp_v6_layout_refused_at_si214(one_chip):
    assert not pallas_gpp.V6.mosaic_tileable(SI214)
    with pytest.raises(ValueError, match="divisible by 8 and 128"):
        _compile_text(
            lambda d: pallas_gpp.gpp_pallas(d, pallas_gpp.V6,
                                            interpret=False),
            _gpp_inputs(one_chip, SI214))


def _qwen2_prefill(one_chip):
    # qwen2-1.5b: 12 query heads, 2 kv heads, head_dim 128, 2048 tokens
    q = _sds((12, 2048, 128), jnp.bfloat16, one_chip)
    kv = _sds((2, 2048, 128), jnp.bfloat16, one_chip)
    return q, kv, kv


def test_flash_forward_compiles_at_qwen2_prefill(one_chip):
    txt = _compile_text(
        lambda q, k, v: flash.flash_attention_bhsd(
            q, k, v, blk_q=256, blk_kv=256, interpret=False),
        *_qwen2_prefill(one_chip))
    assert "tpu_custom_call" in txt


def test_flash_backward_compiles_at_qwen2_prefill(one_chip):
    def loss(q, k, v):
        out = flash.flash_attention_diff(q, k, v, 256, 256, True, False)
        return out.astype(jnp.float32).sum()

    txt = _compile_text(jax.grad(loss, argnums=(0, 1, 2)),
                        *_qwen2_prefill(one_chip))
    assert txt.count("tpu_custom_call") >= 3    # fwd, dq, dkv kernels


def test_ssm_scan_compiles_at_hymba_widths(one_chip):
    b, t, c, n = HYMBA_SCAN.b, HYMBA_SCAN.t, HYMBA_SCAN.c, HYMBA_SCAN.n
    args = (_sds((b, t, c), jnp.float32, one_chip),
            _sds((b, t, c), jnp.float32, one_chip),
            _sds((b, t, n), jnp.float32, one_chip),
            _sds((b, t, n), jnp.float32, one_chip),
            _sds((c, n), jnp.float32, one_chip),
            _sds((c,), jnp.float32, one_chip),
            _sds((b, c, n), jnp.float32, one_chip))
    space = api.get_kernel("ssm").config_space(HYMBA_SCAN, "pallas")
    assert space
    for cfg in space:
        txt = _compile_text(
            lambda *a, blk=cfg.blk_c: ssm_scan.ssm_scan_pallas(
                *a, blk_c=blk, interpret=False), *args)
        assert "tpu_custom_call" in txt, cfg
