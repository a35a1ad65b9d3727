"""Selective-scan (ssm) family registration for the unified kernel registry.

The ssm Pallas kernel (`ssm_scan.py`) previously had no public op layer —
consumers reached into the module and hand-picked `blk_c`. This descriptor
registers three versions behind the same contract as models/mamba.ssm_scan
(`x, dt: (B,T,C); bmat/cmat: (B,T,N); a_log: (C,N); d: (C,); h0: (B,C,N)`):

  ref      — the sequential lax.scan oracle (models/mamba.ssm_scan)
  chunked  — the chunk-parallel MXU form (models/mamba.ssm_chunked)
  pallas   — the VMEM-resident-state Pallas kernel, channel-blocked

and exposes the channel block `blk_c` as the tunable config. The model
hook charges the real blk_c tradeoff: a bigger channel block means fewer
grid instances (less per-instance issue overhead and fewer total fori-loop
steps paying sequencing latency) but a larger VMEM slab — the tuner picks
the largest feasible block, per (B, T, C, N), instead of a frozen 128.

Census (per (t, c) element, documented approximation): exp(dt·a) over N
states ≈ 9N passes (exp is an 8-pass NR sequence), state update ≈ 2N+1,
y-reduction ≈ N+1 → ~12N+2 passes; lanes = N (the minor dim), so small
state sizes under-fill the 128-lane VREG equally for every candidate.
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
from repro.kernels.ssm import ssm_scan as scan_lib

# channel blocks are lane dims: multiples of 128, or (clamped) the whole C
BLK_C_MENU = (128, 256, 512)
LOOP_STEP_OVERHEAD_S = 0.02e-6     # per fori-loop iteration (sequencing)


@dataclasses.dataclass(frozen=True)
class SsmKey:
    b: int
    t: int
    c: int
    n: int
    name: str = "ssm"

    def key_dims(self) -> str:
        return f"{self.b}x{self.t}x{self.c}x{self.n}"


@dataclasses.dataclass(frozen=True)
class SsmScanConfig:
    name: str = "ssm"
    blk_c: int = 128

    def clamped(self, key: SsmKey) -> "SsmScanConfig":
        return dataclasses.replace(self, blk_c=min(self.blk_c, key.c))

    def mosaic_tileable(self, key: SsmKey) -> bool:
        """Mosaic tiles the (T, blk_c) slabs iff blk_c is a multiple of
        128 lanes or the whole channel dim (a ragged last block is fine:
        see ssm_scan.py)."""
        return self.blk_c == key.c or self.blk_c % 128 == 0

    def vmem_bytes(self, key: SsmKey) -> int:
        """x/dt/y slabs (T, blk_c) + b/c mats (T, N), double-buffered,
        plus the resident state/params (blk_c, N)."""
        io = (3 * key.t * self.blk_c + 2 * key.t * key.n) * 4
        live = (3 * self.blk_c * key.n + self.blk_c) * 4    # h0/hT/a_log, d
        return 2 * io + live


class SsmKernel(api.Kernel):
    name = "ssm"
    versions = ("ref", "chunked", "pallas")
    default_version = "pallas"
    tunable = ("pallas",)

    def problem_key(self, x, dt, bmat, cmat, a_log, d, h0) -> SsmKey:
        b, t, c = x.shape
        return SsmKey(b=b, t=t, c=c, n=a_log.shape[1])

    def config_space(self, key: SsmKey, version: str) -> List[SsmScanConfig]:
        out = []
        for blk in sorted({min(b, key.c) for b in BLK_C_MENU}):
            cfg = SsmScanConfig("tune", blk)
            if cfg.vmem_bytes(key) <= TPU_V5E.vmem_bytes:
                out.append(cfg)
        return out

    def clamp(self, config: SsmScanConfig, key: SsmKey) -> SsmScanConfig:
        return config.clamped(key)

    def static_config(self, key: SsmKey, version: str
                      ) -> Optional[SsmScanConfig]:
        return SsmScanConfig().clamped(key)        # the legacy blk_c=128

    def tie_break(self, config: SsmScanConfig) -> Tuple:
        return (-config.blk_c,)

    def finalize_config(self, config: SsmScanConfig, version: str
                        ) -> SsmScanConfig:
        return dataclasses.replace(config, name=version)

    def model_step_s(self, key: SsmKey, config: SsmScanConfig,
                     version: str) -> float:
        cfg = config.clamped(key)
        n_blk = -(-key.c // cfg.blk_c)
        lane_fill = min(key.n, 128) / 128.0
        # a ragged last block computes its padding channels too
        passes = key.b * key.t * n_blk * cfg.blk_c * (12.0 * key.n + 2.0)
        compute_s = passes / PASS_RATE / lane_fill
        instances = key.b * n_blk
        loop_s = instances * key.t * LOOP_STEP_OVERHEAD_S
        overhead_s = instances * GRID_OVERHEAD_S
        mem_s = scan_lib.kernel_hbm_bytes(key.b, key.t, key.c,
                                          key.n) / TPU_V5E.hbm_bw
        return max(compute_s + loop_s + overhead_s, mem_s)

    def measure_ok(self, key: SsmKey) -> bool:
        # the interpreted fori loop runs T python steps — tiny problems only
        return key.b * key.t * key.c * key.n <= 1 << 16

    def make_example(self, key: SsmKey, seed: int = 0) -> Tuple[tuple, dict]:
        ks = jax.random.split(jax.random.PRNGKey(seed), 7)
        x = jax.random.normal(ks[0], (key.b, key.t, key.c))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (key.b, key.t, key.c))
                             - 2)
        bm = jax.random.normal(ks[2], (key.b, key.t, key.n))
        cm = jax.random.normal(ks[3], (key.b, key.t, key.n))
        alog = jnp.log(jnp.arange(1, key.n + 1, dtype=jnp.float32)
                       )[None].repeat(key.c, 0)
        d = jax.random.normal(ks[5], (key.c,))
        h0 = 0.1 * jax.random.normal(ks[6], (key.b, key.c, key.n))
        return (x, dt, bm, cm, alog, d, h0), {}

    def config_from_json(self, d: Dict) -> SsmScanConfig:
        return SsmScanConfig(**d)

    # -- static-analysis hooks (repro.analyze) -----------------------------
    def canonical_keys(self) -> List[SsmKey]:
        return [SsmKey(b=2, t=32, c=64, n=8)]

    def key_from_dims(self, dims: str) -> SsmKey:
        b, t, c, n = (int(d) for d in dims.split("x"))
        return SsmKey(b=b, t=t, c=c, n=n)

    def config_vmem_bytes(self, config: SsmScanConfig, key: SsmKey) -> int:
        return config.vmem_bytes(key)

    def config_divides(self, config: SsmScanConfig, key: SsmKey
                       ) -> List[str]:
        if config.blk_c <= 0 or not config.mosaic_tileable(key):
            return [f"c={key.c} not tiled by block {config.blk_c}"]
        return []

    def allowed_float_dtypes(self, version: str) -> frozenset:
        return frozenset({"float32"})

    def run(self, x, dt, bmat, cmat, a_log, d, h0, *, version: str,
            config: Optional[SsmScanConfig], interpret: Optional[bool]):
        if version == "ref":
            from repro.models.mamba import ssm_scan
            return ssm_scan(x, dt, bmat, cmat, a_log, d, h0)
        if version == "chunked":
            from repro.models.mamba import ssm_chunked
            t = x.shape[1]
            chunk = max(cc for cc in range(1, min(64, t) + 1) if t % cc == 0)
            return ssm_chunked(x, dt, bmat, cmat, a_log, d, h0, chunk=chunk)
        cfg = config or SsmScanConfig()
        return scan_lib.ssm_scan_pallas(
            x, dt, bmat, cmat, a_log, d, h0, blk_c=cfg.blk_c,
            interpret=backend.resolve_interpret(interpret))


KERNEL = api.register(SsmKernel())
