"""GPP Pallas TPU kernel — the paper's v6 (cache blocking), v7 (index swap)
and v8 (block-size tuning) steps, made *explicit* through BlockSpecs, plus
the beyond-the-paper v9 step (fused VMEM scratch accumulation).

Grid: (n_igp_blocks, n_ig_blocks, n_band_blocks) — band innermost, so the
output block (indexed by igp/ig only) is revisited across band steps and
accumulated in place (@pl.when(band_step == 0) zero-init). The wtilde/eps
tiles' index maps don't depend on the band index, so the Pallas pipeline
keeps them resident in VMEM across the whole in-block band sweep — this IS
the paper's v4/v6 reuse structure, declared rather than hoped-for from a
cache (DESIGN.md §2).

In-kernel layout (TPU 8x128 VREG lanes):
  wtilde/eps tiles: (BLK_IG, BLK_IGP)  — sublanes=ig, lanes=igp
  aqsn: passed transposed (nbands, ncouls), tile (BLK_BAND, BLK_IG):
        row read aqsn[b, :] is a sublane-indexed load (cheap)
  aqsm v6 layout: (ngpown, nbands), tile (BLK_IGP, BLK_BAND): the per-band
        read is a *lane-dim dynamic slice + relayout* — the TPU analogue of
        the paper's non-contiguous aqsmtemp(igp,band) access.
  aqsm v7 layout: transposed (nbands, ngpown), tile (BLK_BAND, BLK_IGP):
        per-band read is a sublane row, broadcast straight onto lanes.
  v8: same code as v7 with tuned (larger) blocks — lanes filled (BLK_IGP>=128),
      VMEM working set sized for double-buffering (see VMEM_MODEL).
  v9: fused accumulation — the per-(igp,ig) partial sums live in a VMEM
      scratch accumulator (pl.pallas_call scratch_shapes) instead of
      read-modify-writing the output block every band step; outputs are
      written once, on the last band step. With the output RMW off the
      critical path the igp/ig grid axes are declared `parallel`
      (dimension_semantics), so Mosaic may overlap grid sequencing with the
      VPU work. v10 is v9 under an autotuned BlockConfig (repro.tune).

Numerics: planar f32; validated in interpret mode against ref.ref_numpy
(complex128) by tests/test_gpp_kernel.py.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import backend
from repro.kernels.gpp.problem import LIMITONE, LIMITTWO, TOL_ZERO, GppSize


@dataclasses.dataclass(frozen=True)
class BlockConfig:
    name: str
    blk_ig: int
    blk_igp: int
    blk_band: int
    aqsm_transposed: bool   # v7/v8 layout swap
    fused_acc: bool = False  # v9+: VMEM scratch accumulators + parallel dims

    def vmem_bytes(self, nw: int = 2) -> int:
        """Analytic VMEM working set (×2 for double buffering on inputs)."""
        t_ig_igp = self.blk_ig * self.blk_igp * 4
        inputs = 4 * t_ig_igp                                 # wt/eps re+im
        inputs += 2 * self.blk_band * self.blk_ig * 4         # aqsn tile
        inputs += 2 * self.blk_band * self.blk_igp * 4        # aqsm tile
        inputs += self.blk_band * nw * 4 + self.blk_ig * 4    # wx, vcoul
        # live (BLK_IG, BLK_IGP) f32 intermediates of the band sweep: 24
        # planes matches Mosaic's scoped-VMEM allocation on v5e, which
        # refuses every si214 config with 0.5 MiB planes under the 16 MiB
        # limit and accepts every one with 0.25 MiB planes
        live = 24 * t_ig_igp
        total = 2 * inputs + live
        if self.fused_acc:
            total += OUT_TILE[0] * OUT_TILE[1] * 4            # scratch tile
        return total

    def mosaic_tileable(self, size: GppSize) -> bool:
        """Whether Mosaic (the TPU kernel compiler) accepts these blocks:
        the last two dims of every block must be multiples of (8, 128) or
        span the whole array dim. ig and igp are lane dims (wtilde tile,
        aqsn tile), band a sublane dim (aqsn/wx tiles) — except in v6's
        untransposed aqsm tile (blk_igp, blk_band), where band is the lane
        dim: that is the layout the paper's index swap (v7) repairs."""
        def ok(blk, full, mult):
            return blk == full or blk % mult == 0
        lanes_band = not self.aqsm_transposed
        return (ok(self.blk_ig, size.ncouls, 128)
                and ok(self.blk_igp, size.ngpown, 128)
                and ok(self.blk_band, size.nbands, 128 if lanes_band else 8))

    def clamped(self, size: GppSize) -> "BlockConfig":
        """Shrink blocks to fit a smaller problem (power-of-two dims keep
        divisibility; gpp_pallas re-asserts it)."""
        return dataclasses.replace(
            self, blk_ig=min(self.blk_ig, size.ncouls),
            blk_igp=min(self.blk_igp, size.ngpown),
            blk_band=min(self.blk_band, size.nbands))


# canonical journey configs. v6: first blocking attempt — small band blocks
# and aqsm in (igp, band) layout, whose lane dim (band=8) is below the 128
# DMA/VREG granularity (traffic inflation + per-band lane relayout). v7:
# index swap fixes the layout. v8: block sizes tuned (core/journey.py sweep)
# so per-instance compute amortizes grid/DMA issue overhead.
V6 = BlockConfig("v6", blk_ig=256, blk_igp=128, blk_band=8, aqsm_transposed=False)
V7 = BlockConfig("v7", blk_ig=256, blk_igp=128, blk_band=8, aqsm_transposed=True)
V8 = BlockConfig("v8", blk_ig=512, blk_igp=128, blk_band=32, aqsm_transposed=True)
# v9: v8's tuned blocks + fused scratch accumulation. v10 has no static
# config — it is v9 under whatever BlockConfig repro.tune picks per size.
V9 = BlockConfig("v9", blk_ig=512, blk_igp=128, blk_band=32,
                 aqsm_transposed=True, fused_acc=True)

CONFIGS = {"v6": V6, "v7": V7, "v8": V8, "v9": V9}

# one grid instance's output block: an (8, 128) f32 vreg (see _acc_tile)
OUT_TILE = (8, 128)


def _band_sweep(wt_re_ref, wt_im_ref, eps_re_ref, eps_im_ref,
                aqsn_re_ref, aqsn_im_ref, aqsm_re_ref, aqsm_im_ref,
                wx_ref, vcoul_ref, *, cfg: BlockConfig, nw: int):
    """The in-block band sweep shared by the v6–v8 and v9 kernels: reduce
    this grid instance's (BLK_IG, BLK_IGP, BLK_BAND) tile down to nw
    (ach_re, ach_im, asx_re, asx_im) scalars."""
    wt_re = wt_re_ref[...]            # (BIG, BIGP) — resident across bands
    wt_im = wt_im_ref[...]
    eps_re = eps_re_ref[...]
    eps_im = eps_im_ref[...]
    vcoul = vcoul_ref[...]            # (BIG, 1)

    # band-invariant subexpressions (the paper's v5 hoist)
    wt2_re = wt_re * wt_re - wt_im * wt_im
    wt2_im = 2.0 * wt_re * wt_im
    om2_re = wt2_re * eps_re - wt2_im * eps_im
    om2_im = wt2_re * eps_im + wt2_im * eps_re

    def band_iter(b, carry):
        accs = carry

        an_re = aqsn_re_ref[b, :][:, None]           # (BIG, 1) sublane row
        an_im = aqsn_im_ref[b, :][:, None]
        if cfg.aqsm_transposed:
            # v7/v8: sublane row read, broadcast onto lanes
            am_re = aqsm_re_ref[b, :][None, :]       # (1, BIGP)
            am_im = aqsm_im_ref[b, :][None, :]
        else:
            # v6: lane-dim dynamic slice + relayout (the "wrong" layout)
            am_re = aqsm_re_ref[:, b][None, :]
            am_im = aqsm_im_ref[:, b][None, :]

        # mat(ig,igp) = conj(aqsm)*aqsn, pre-scaled by vcoul(ig)
        mat_re = an_re * am_re + an_im * am_im
        mat_im = an_im * am_re - an_re * am_im
        wre = vcoul * mat_re
        wim = vcoul * mat_im

        new_accs = []
        for iw in range(nw):
            wxv = wx_ref[b, iw]
            wd_re = wxv - wt_re
            wd_im = -wt_im
            wdiffr = wd_re * wd_re + wd_im * wd_im
            rden = 1.0 / wdiffr
            delw_re = (wt_re * wd_re + wt_im * wd_im) * rden
            delw_im = (wt_im * wd_re - wt_re * wd_im) * rden
            delwr = delw_re * delw_re + delw_im * delw_im
            cond1 = (wdiffr > LIMITTWO) & (delwr < LIMITONE)
            cond2 = (~cond1) & (delwr > TOL_ZERO)

            sch1_re = delw_re * eps_re - delw_im * eps_im
            sch1_im = delw_re * eps_im + delw_im * eps_re
            cden1_re = wxv * wxv - wt2_re
            cden1_im = -wt2_im
            c1sq = cden1_re * cden1_re + cden1_im * cden1_im
            r1 = 1.0 / c1sq
            ssx1_re = (om2_re * cden1_re + om2_im * cden1_im) * r1
            ssx1_im = (om2_im * cden1_re - om2_re * cden1_im) * r1

            f4_re = 4.0 * (delw_re + 0.5)
            f4_im = 4.0 * delw_im
            cd2_re = wt2_re * f4_re - wt2_im * f4_im
            cd2_im = wt2_re * f4_im + wt2_im * f4_re
            c2sq = cd2_re * cd2_re + cd2_im * cd2_im
            c2sq = jnp.where(c2sq == 0, 1.0, c2sq)
            n2_re = -(om2_re * delw_re - om2_im * delw_im)
            n2_im = -(om2_re * delw_im + om2_im * delw_re)
            r2 = 1.0 / c2sq
            ssx2_re = (n2_re * cd2_re + n2_im * cd2_im) * r2
            ssx2_im = (n2_im * cd2_re - n2_re * cd2_im) * r2

            sch_re = jnp.where(cond1, sch1_re, 0.0)
            sch_im = jnp.where(cond1, sch1_im, 0.0)
            ssx_re = jnp.where(cond1, ssx1_re, jnp.where(cond2, ssx2_re, 0.0))
            ssx_im = jnp.where(cond1, ssx1_im, jnp.where(cond2, ssx2_im, 0.0))

            da_re = jnp.sum(wre * sch_re - wim * sch_im)
            da_im = jnp.sum(wre * sch_im + wim * sch_re)
            dx_re = jnp.sum(wre * ssx_re - wim * ssx_im)
            dx_im = jnp.sum(wre * ssx_im + wim * ssx_re)
            a_re, a_im, x_re, x_im = accs[iw]
            new_accs.append((a_re + da_re, a_im + da_im,
                             x_re + dx_re, x_im + dx_im))
        return tuple(new_accs)

    zero = jnp.float32(0.0)
    init = tuple((zero, zero, zero, zero) for _ in range(nw))
    return jax.lax.fori_loop(0, cfg.blk_band, band_iter, init)


def _acc_tile(accs, nw: int):
    """Pack the per-iw (ach_re, ach_im, asx_re, asx_im) scalars into one
    (8, 128) f32 tile: row r holds quantity r, lane iw holds frequency iw.
    One full vreg is the smallest output block Mosaic tiles, and a vector
    store replaces 4*nw scalar stores."""
    rows = jax.lax.broadcasted_iota(jnp.int32, OUT_TILE, 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, OUT_TILE, 1)
    tile = jnp.zeros(OUT_TILE, jnp.float32)
    for iw in range(nw):
        for r, v in enumerate(accs[iw]):
            tile = jnp.where((rows == r) & (lanes == iw), v, tile)
    return tile


def _kernel(wt_re_ref, wt_im_ref, eps_re_ref, eps_im_ref,
            aqsn_re_ref, aqsn_im_ref, aqsm_re_ref, aqsm_im_ref,
            wx_ref, vcoul_ref, out_ref, *, cfg: BlockConfig, nw: int):
    """v6–v8: the output tile is revisited across band steps and
    read-modify-written in place (@pl.when(band_step == 0) zero-init)."""
    band_step = pl.program_id(2)

    @pl.when(band_step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    accs = _band_sweep(wt_re_ref, wt_im_ref, eps_re_ref, eps_im_ref,
                       aqsn_re_ref, aqsn_im_ref, aqsm_re_ref, aqsm_im_ref,
                       wx_ref, vcoul_ref, cfg=cfg, nw=nw)
    out_ref[...] += _acc_tile(accs, nw)


def _kernel_fused(wt_re_ref, wt_im_ref, eps_re_ref, eps_im_ref,
                  aqsn_re_ref, aqsn_im_ref, aqsm_re_ref, aqsm_im_ref,
                  wx_ref, vcoul_ref, out_ref, acc_ref,
                  *, cfg: BlockConfig, nw: int):
    """v9: partial sums accumulate in an (8, 128) VMEM scratch tile across
    the band steps; the output tile is written exactly once, on the last
    step. No output RMW per band step -> igp/ig can be `parallel`."""
    band_step = pl.program_id(2)

    @pl.when(band_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    accs = _band_sweep(wt_re_ref, wt_im_ref, eps_re_ref, eps_im_ref,
                       aqsn_re_ref, aqsn_im_ref, aqsm_re_ref, aqsm_im_ref,
                       wx_ref, vcoul_ref, cfg=cfg, nw=nw)
    acc_ref[...] += _acc_tile(accs, nw)

    @pl.when(band_step == pl.num_programs(2) - 1)
    def _flush():
        out_ref[...] = acc_ref[...]


def gpp_pallas(inputs: Dict, cfg: BlockConfig, *,
               interpret: Optional[bool] = None
               ) -> Tuple[jax.Array, jax.Array]:
    """Run the blocked GPP kernel. inputs: planar dict (problem.make_inputs).
    Returns (ach (nw,) complex64, asx (nw,) complex64).
    interpret=None defers to repro.backend (REPRO_INTERPRET override)."""
    interpret = backend.resolve_interpret(interpret)
    f = {k: jnp.asarray(v, jnp.float32) for k, v in inputs.items()}
    ncouls, ngpown = f["wtilde_re"].shape
    nw, nbands = f["wx"].shape
    assert nw <= OUT_TILE[1], nw

    assert ncouls % cfg.blk_ig == 0, (ncouls, cfg.blk_ig)
    assert ngpown % cfg.blk_igp == 0, (ngpown, cfg.blk_igp)
    assert nbands % cfg.blk_band == 0, (nbands, cfg.blk_band)
    n_ig = ncouls // cfg.blk_ig
    n_igp = ngpown // cfg.blk_igp
    n_b = nbands // cfg.blk_band

    aqsn_re = f["aqsn_re"].T            # (nbands, ncouls)
    aqsn_im = f["aqsn_im"].T
    if cfg.aqsm_transposed:
        aqsm_re = f["aqsm_re"].T        # (nbands, ngpown)
        aqsm_im = f["aqsm_im"].T
        aqsm_spec = pl.BlockSpec((cfg.blk_band, cfg.blk_igp),
                                 lambda i, j, b: (b, i))
    else:
        aqsm_re = f["aqsm_re"]          # (ngpown, nbands)
        aqsm_im = f["aqsm_im"]
        aqsm_spec = pl.BlockSpec((cfg.blk_igp, cfg.blk_band),
                                 lambda i, j, b: (i, b))
    wx = f["wx"].T                      # (nbands, nw)
    vcoul = f["vcoul"][:, None]         # (ncouls, 1)

    ig_igp = pl.BlockSpec((cfg.blk_ig, cfg.blk_igp), lambda i, j, b: (j, i))
    aqsn_spec = pl.BlockSpec((cfg.blk_band, cfg.blk_ig), lambda i, j, b: (b, j))
    wx_spec = pl.BlockSpec((cfg.blk_band, nw), lambda i, j, b: (b, 0))
    vc_spec = pl.BlockSpec((cfg.blk_ig, 1), lambda i, j, b: (j, 0))
    # squeezed (igp, ig) block dims: the kernel sees one (8, 128) tile
    out_spec = pl.BlockSpec((None, None) + OUT_TILE,
                            lambda i, j, b: (i, j, 0, 0))
    out_shape = jax.ShapeDtypeStruct((n_igp, n_ig) + OUT_TILE, jnp.float32)

    extra = {}
    if cfg.fused_acc:
        kern = functools.partial(_kernel_fused, cfg=cfg, nw=nw)
        extra["scratch_shapes"] = [pltpu.VMEM(OUT_TILE, jnp.float32)]
        # the band axis carries the scratch accumulator -> arbitrary; the
        # igp/ig axes have no cross-instance state -> parallel
        extra["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    else:
        kern = functools.partial(_kernel, cfg=cfg, nw=nw)
    out = pl.pallas_call(
        kern,
        grid=(n_igp, n_ig, n_b),
        in_specs=[ig_igp, ig_igp, ig_igp, ig_igp,
                  aqsn_spec, aqsn_spec, aqsm_spec, aqsm_spec,
                  wx_spec, vc_spec],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
        **extra,
    )(f["wtilde_re"], f["wtilde_im"], f["eps_re"], f["eps_im"],
      aqsn_re, aqsn_im, aqsm_re, aqsm_im, wx, vcoul)

    tot = jnp.sum(out, (0, 1))[:4, :nw]          # rows: ach re/im, asx re/im
    ach = tot[0] + 1j * tot[1]
    asx = tot[2] + 1j * tot[3]
    return ach.astype(jnp.complex64), asx.astype(jnp.complex64)


def hbm_traffic_model(size: GppSize, cfg: BlockConfig) -> float:
    """Exact HBM byte count for the Pallas pipeline (deterministic — the
    blocks a pipeline fetches are fully determined by the index maps):
      wtilde/eps: fetched once per (igp, ig) block  -> full arrays once
      aqsn: index (ig, band) — refetched per igp block
      aqsm: index (igp, band) — refetched per ig block
      wx/vcoul/output tiles: negligible (counted anyway)
    """
    n_ig = size.ncouls // cfg.blk_ig
    n_igp = size.ngpown // cfg.blk_igp
    b = 0.0
    b += 4 * 4 * size.ncouls * size.ngpown                 # wt/eps planes
    b += n_igp * 2 * 4 * size.ncouls * size.nbands         # aqsn
    b += n_ig * 2 * 4 * size.ngpown * size.nbands          # aqsm
    b += n_ig * n_igp * 4 * size.nw * size.nbands          # wx
    b += n_igp * 4 * size.ncouls                           # vcoul
    b += 4 * n_ig * n_igp * OUT_TILE[0] * OUT_TILE[1]      # output tiles
    return b
