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
    the ssm tuner may pick (1600 channels: a ragged last block);
  * the serve engine's decode and slot-prefill steps at qwen2-1.5b widths
    (64 slots of 2048 lines), which alias the cache they are handed and
    write their new lines in place: no whole-cache select or copy, and in
    decode no whole-cache dynamic-update-slice;
  * the same two steps of codeqwen1.5-7b, whole at its published widths,
    tensor-parallel over the four chips of the v5e:2x2 (the serve plan's
    exact-TP layout, one K/V head per chip): they fit a chip and alias
    the cache shards they are handed, and each chip makes only its own
    shard of the cache.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and test workers import every
test file.
"""

import os
import re

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


@pytest.mark.parametrize("program", ["_decode", "_prefill_slot"])
def test_serve_steps_write_the_cache_in_place(one_chip, program):
    """qwen2-1.5b widths with 2 layers, 64 slots of 2048 lines."""
    from repro.configs.base import ModelConfig
    from repro.models.registry import build_model
    from repro.serve.engine import ServeEngine
    layers, slots, lines = 2, 64, 2048
    cfg = ModelConfig(name="qwen2-1.5b-2l", family="dense", n_layers=layers,
                      d_model=1536, n_heads=12, n_kv_heads=2, d_ff=8960,
                      vocab_size=151936, head_dim=128, qkv_bias=True,
                      rope_theta=1e6, norm_eps=1e-6, tie_embeddings=True,
                      remat="none")
    model = build_model(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), tree)

    params = on_chip(model.abstract_params())
    cache = model.init_cache(slots, lines, abstract=True)
    cache["pos"] = jax.ShapeDtypeStruct((slots,), jnp.int32)
    cache = on_chip(cache)
    eng = ServeEngine(cfg, params, max_batch=slots, cache_len=lines)
    if program == "_decode":
        args = (_sds((slots, 1), jnp.int32, one_chip),
                _sds((slots,), jnp.bool_, one_chip))
    else:
        i32 = _sds((), jnp.int32, one_chip)
        args = (i32, {"tokens": _sds((1, 512), jnp.int32, one_chip)}, i32)
    txt = getattr(eng, program).lower(params, cache, *args).compile().as_text()
    # arguments: the weight leaves, then the cache's k, pos and v
    k_arg = len(jax.tree.leaves(params))
    alias = re.search(r"input_output_alias=\{(.*?)\} *\}", txt.splitlines()[0])
    assert alias, "the program aliases none of its arguments"
    for arg in (k_arg, k_arg + 2):
        assert f"({arg}, {{}}, may-alias)" in alias.group(1), alias.group(1)
    whole = f"bf16[{layers},{slots},{lines},"
    ops = ["select(", "copy("]
    if program == "_decode":
        ops.append("dynamic-update-slice(")
    offending = [ln.strip()[:200] for ln in txt.splitlines()
                 if " = " in ln
                 and ln.split(" = ", 1)[1].startswith(whole)
                 and any(op in ln.split(" = ", 1)[1] for op in ops)]
    assert not offending, offending


TP4_SLOTS, TP4_LINES = 32, 2048


def _tp4_codeqwen_engine(topo, monkeypatch):
    """(engine, model) of codeqwen1.5-7b (32 layers, d 4096, 32/4 heads of
    128, ff 13440, vocab 92416, untied) over a `model` mesh of the four
    described chips, 32 slots of 2048 lines."""
    from repro.configs.base import get_config
    from repro.launch.mesh import make_mesh
    from repro.models.registry import build_model
    from repro.serve.engine import ServeEngine
    cfg = get_config("codeqwen1.5-7b")
    mesh = make_mesh((4,), ("model",), devices=topo.devices)
    # shapes, not arrays: the engine's placement of its weights onto the
    # mesh has no described device to put them on
    monkeypatch.setattr(jax, "device_put", lambda x, *a, **k: x)
    eng = ServeEngine(cfg, None, max_batch=TP4_SLOTS, cache_len=TP4_LINES,
                      mesh=mesh)
    return eng, build_model(cfg)


@pytest.mark.parametrize("program", ["_decode", "_prefill_slot"])
def test_tp4_codeqwen_steps_fit_and_donate(topo, monkeypatch, program):
    eng, model = _tp4_codeqwen_engine(topo, monkeypatch)
    sp, slots = eng._specs, TP4_SLOTS

    def shaped(tree, shardings):
        return jax.tree.map(lambda a, s: _sds(a.shape, a.dtype, s), tree,
                            shardings)

    params = shaped(model.abstract_params(), sp.params)
    cache = model.init_cache(slots, TP4_LINES, abstract=True)
    cache["pos"] = jax.ShapeDtypeStruct((slots,), jnp.int32)
    cache = shaped(cache, sp.cache)
    assert tuple(sp.cache["k"].spec)[3] == "model"    # one K/V head a chip
    rep = sp.replicated
    if program == "_decode":
        args = (_sds((slots, 1), jnp.int32, rep), _sds((slots,), jnp.bool_, rep))
    else:
        i32 = _sds((), jnp.int32, rep)
        args = (i32, {"tokens": _sds((1, 512), jnp.int32, rep)}, i32)
    compiled = getattr(eng, program).lower(params, cache, *args).compile()
    mem = compiled.memory_analysis()
    per_chip = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert per_chip < 16e9, per_chip
    txt = compiled.as_text()
    k_arg = len(jax.tree.leaves(params))
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", txt.splitlines()[0])
    assert alias, "the program aliases none of its arguments"
    for arg in (k_arg, k_arg + 2):
        assert f"({arg}, {{}}, may-alias)" in alias.group(1), alias.group(1)


def test_tp4_codeqwen_cache_is_made_in_its_shards(topo, monkeypatch):
    """Each chip zeroes only its quarter of the K/V (one head of four):
    made whole on one chip first, the 4.3 GB cache does not fit there
    beside that chip's 7.6 GB of weights."""
    eng, _ = _tp4_codeqwen_engine(topo, monkeypatch)
    mem = eng._zero_cache_sharded.lower().compile().memory_analysis()
    quarter = 2 * 32 * TP4_SLOTS * TP4_LINES * 1 * 128 * 2   # K and V, bf16
    assert quarter <= mem.output_size_in_bytes < quarter + 4096
    assert mem.temp_size_in_bytes < 4096
