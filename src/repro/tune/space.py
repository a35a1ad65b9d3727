"""Candidate-space generation for the GPP block-size tuner.

Generalizes `journey.sweep_blocks`' fixed grid to any `GppSize`: a candidate
block size must (a) exactly tile every axis it blocks (the kernel asserts
divisibility), (b) be a block Mosaic can tile (multiples of 8 sublanes and
128 lanes, or the whole dim), and (c) keep the analytic VMEM working set
inside the chip's VMEM budget (double-buffered inputs + live intermediates, BlockConfig
.vmem_bytes). The menu is geometric — powers of two per axis — because the
TPU's 8x128 VREG/DMA granularity makes intermediate sizes strictly worse
than the nearest power of two on at least one of lane fill or traffic.
"""

from __future__ import annotations

from typing import List, Sequence

from repro.core.hw import TPU_V5E
from repro.kernels.gpp.pallas_gpp import BlockConfig
from repro.kernels.gpp.problem import GppSize

# per-axis menus: every power of two in the plausible range; filtered per
# size by divisibility. ig is the lane-reduction axis (bigger amortizes),
# igp the lane axis (128 fills the VREG), band the sequential axis.
IG_MENU = (8, 16, 32, 64, 128, 256, 512, 1024)
IGP_MENU = (4, 8, 16, 32, 64, 128, 256)
BAND_MENU = (4, 8, 16, 32, 64, 128, 256, 512)


def _divisors(n: int, menu: Sequence[int]) -> List[int]:
    return [b for b in menu if b <= n and n % b == 0]


def candidates(size: GppSize, *, fused: bool = True,
               aqsm_transposed: bool = True,
               vmem_budget: int = TPU_V5E.vmem_bytes) -> List[BlockConfig]:
    """All feasible BlockConfigs for `size`: divisibility-exact on every
    axis, tileable by Mosaic and VMEM-feasible. Deterministic order (menu
    order)."""
    out = []
    for big in _divisors(size.ncouls, IG_MENU):
        for bigp in _divisors(size.ngpown, IGP_MENU):
            for bb in _divisors(size.nbands, BAND_MENU):
                cfg = BlockConfig("tune", big, bigp, bb,
                                  aqsm_transposed=aqsm_transposed,
                                  fused_acc=fused)
                if (cfg.mosaic_tileable(size)
                        and cfg.vmem_bytes(size.nw) <= vmem_budget):
                    out.append(cfg)
    return out
