"""`live_decode` family registration for the unified kernel registry.

One decode token per row attending over the live lines of one layer of
the slot scheduler's stacked K/V cache (live_decode.py). Versions:

  * "ref"    — `models.attention.decode_attention` over `cache[layer]`
    (it reads the whole slab and masks), with rows of no live line set
    to zero, the kernel's contract for them;
  * "pallas" — the kernel, which fetches only ceil(lens / blk) blocks of
    `blk` lines per row.

The config is the block (`LiveDecodeConfig.blk`, 256 lines by default,
clamped to divide the cache length). The roofline model charges a full
cache, every row at L lines: the key holds shapes, not lengths, so that
is the most the kernel can read.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import backend
from repro.core.hw import TPU_V5E
from repro.core.vpu_model import GRID_OVERHEAD_S, PASS_RATE
from repro.kernels import api
from repro.kernels.live_decode import live_decode as lib

SOFTMAX_PASSES = 12.0          # exp + max/sum/online-rescale per score
BF16 = 2


@dataclasses.dataclass(frozen=True)
class LiveDecodeKey:
    """ProblemKey for one layer's decode attention: B rows, H query heads
    over KvH K/V heads of Hd, against a stacked cache of n_layers x L
    lines a row."""
    b: int
    h: int
    kvh: int
    n_layers: int
    lines: int
    hd: int
    name: str = "live_decode"

    def key_dims(self) -> str:
        return (f"{self.b}x{self.h}x{self.kvh}x{self.n_layers}x{self.lines}"
                f"x{self.hd}")


@dataclasses.dataclass(frozen=True)
class LiveDecodeConfig:
    name: str = "live_decode"
    blk: int = 256

    def clamped(self, lines: int) -> "LiveDecodeConfig":
        blk = min(self.blk, lines)
        while lines % blk:
            blk -= 1
        return dataclasses.replace(self, blk=blk)


def block_for(lines: int) -> int:
    """The lines of one block the kernel fetches, for a cache of `lines`
    lines a row (the static config, clamped)."""
    return LiveDecodeConfig().clamped(lines).blk


class LiveDecodeKernel(api.Kernel):
    name = "live_decode"
    versions = ("ref", "pallas")
    default_version = "pallas"

    def problem_key(self, q, cache_k, cache_v, layer, lens
                    ) -> LiveDecodeKey:
        b, h, hd = q.shape
        n_layers, _, lines, kvh, _ = cache_k.shape
        return LiveDecodeKey(b=b, h=h, kvh=kvh, n_layers=n_layers,
                             lines=lines, hd=hd)

    def clamp(self, config: LiveDecodeConfig, key: LiveDecodeKey
              ) -> LiveDecodeConfig:
        return config.clamped(key.lines)

    def static_config(self, key: LiveDecodeKey, version: str
                      ) -> Optional[LiveDecodeConfig]:
        return LiveDecodeConfig().clamped(key.lines)

    def model_step_s(self, key: LiveDecodeKey, config: LiveDecodeConfig,
                     version: str) -> float:
        cfg = config.clamped(key.lines)
        blocks = key.b * key.lines // cfg.blk
        flops = 4.0 * key.b * key.h * key.lines * key.hd     # qk + pv
        vpu_s = key.b * key.h * key.lines * SOFTMAX_PASSES / PASS_RATE
        compute_s = flops / TPU_V5E.mxu_flops + vpu_s \
            + blocks * GRID_OVERHEAD_S
        bytes_ = (2 * key.b * key.lines * key.kvh * key.hd * BF16
                  + 2 * key.b * key.h * key.hd * BF16)        # q, out
        return max(compute_s, bytes_ / TPU_V5E.hbm_bw)

    def make_example(self, key: LiveDecodeKey, seed: int = 0
                     ) -> Tuple[tuple, dict]:
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        shape = (key.n_layers, key.b, key.lines, key.kvh, key.hd)
        q = jax.random.normal(ks[0], (key.b, key.h, key.hd), jnp.bfloat16)
        ck = jax.random.normal(ks[1], shape, jnp.bfloat16)
        cv = jax.random.normal(ks[2], shape, jnp.bfloat16)
        # every row live, from a full row down to a single line
        lens = key.lines - (jnp.arange(key.b, dtype=jnp.int32)
                            % key.lines)
        return (q, ck, cv, jnp.int32(key.n_layers // 2), lens), {}

    def config_from_json(self, d: Dict) -> LiveDecodeConfig:
        return LiveDecodeConfig(**d)

    # -- static-analysis hooks (repro.analyze) -----------------------------
    def canonical_keys(self) -> List[LiveDecodeKey]:
        # two layers: the census charges the whole stacked operand, the
        # model one layer's lines
        return [LiveDecodeKey(b=2, h=4, kvh=2, n_layers=2, lines=64, hd=32)]

    def key_from_dims(self, dims: str) -> LiveDecodeKey:
        b, h, kvh, n_layers, lines, hd = (int(d) for d in dims.split("x"))
        return LiveDecodeKey(b=b, h=h, kvh=kvh, n_layers=n_layers,
                             lines=lines, hd=hd)

    def config_vmem_bytes(self, config: LiveDecodeConfig,
                          key: LiveDecodeKey) -> int:
        return lib.vmem_bytes(key.b, key.h, key.kvh, key.hd,
                              config.clamped(key.lines).blk)

    def config_divides(self, config: LiveDecodeConfig, key: LiveDecodeKey
                       ) -> List[str]:
        if config.blk <= 0 or key.lines % config.blk:
            return [f"lines={key.lines} not tiled by block {config.blk}"]
        return []

    def allowed_float_dtypes(self, version: str) -> frozenset:
        # bf16 operands, f32 scores, softmax state and accumulator
        return frozenset({"bfloat16", "float32"})

    def run(self, q, cache_k, cache_v, layer, lens, *, version: str,
            config: Optional[LiveDecodeConfig], interpret: Optional[bool]):
        """q: (B,H,Hd); cache_k/v: (n_layers,B,L,KvH,Hd); layer: scalar;
        lens: (B,) int32 -> (B,H,Hd); rows with lens == 0 are zero."""
        if version == "ref":
            from repro.models.attention import decode_attention
            out = decode_attention(q[:, None], cache_k[layer],
                                   cache_v[layer], lens)[:, 0]
            return jnp.where(lens[:, None, None] > 0, out,
                             jnp.zeros_like(out))
        cfg = (config or LiveDecodeConfig()).clamped(cache_k.shape[2])
        return lib.live_decode_attention(
            q, cache_k, cache_v, layer, lens, blk=cfg.blk,
            interpret=backend.resolve_interpret(interpret))


KERNEL = api.register(LiveDecodeKernel())
