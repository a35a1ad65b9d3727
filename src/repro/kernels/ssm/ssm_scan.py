"""Selective-scan (Mamba) Pallas TPU kernel — the Hymba §Perf hillclimb.

The XLA chunked scan (models/mamba.ssm_chunked) materializes ~6 (B,S,C,N)
f32 intermediates per chunk in HBM — measured 70s of hymba train_4k's 128s
memory term. This kernel is the Mamba paper's own "hardware-aware scan"
adapted to TPU: the recurrent state h (C_blk, N) lives in VMEM (registers
of the recurrence), x/dt stream through once, y streams out once — HBM
traffic collapses to the kernel's I/O (~0.4s modeled).

Grid: (B, cdiv(C, C_blk)). Block = the full time axis x (T, C_blk) slab
(T=4096, C_blk=128 -> 2 MiB f32, VMEM-resident), dt same, b/c (T, N).
The kernel fori-loops T steps, carrying h functionally. C_blk is the lane
dim of the x/dt/y blocks, so Mosaic needs it to be a multiple of 128 or
the whole of C. A C that 128 does not divide (hymba's 1600) takes a
ragged last block: channels never mix (every reduction runs over N), so
the padding lanes of that block compute garbage that is never written
back.

ref.py oracle = models/mamba.ssm_scan. Validated in interpret mode by
tests/test_ssm_kernel.py across shape sweeps.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import backend


def _kernel(x_ref, dt_ref, b_ref, c_ref, alog_ref, d_ref, h0_ref,
            y_ref, hT_ref, *, t_len: int):
    a = -jnp.exp(alog_ref[...].astype(jnp.float32))      # (C_blk, N)
    d = d_ref[...].astype(jnp.float32)                   # (C_blk, 1)

    def step(t, h):
        xt = x_ref[0, t, :].astype(jnp.float32)          # (C_blk,)
        dtt = dt_ref[0, t, :].astype(jnp.float32)
        bt = b_ref[0, t, :].astype(jnp.float32)          # (N,)
        ct = c_ref[0, t, :].astype(jnp.float32)
        da = jnp.exp(dtt[:, None] * a)                   # (C_blk, N)
        h = da * h + (dtt * xt)[:, None] * bt[None, :]
        y = jnp.sum(h * ct[None, :], axis=1) + d[:, 0] * xt
        y_ref[0, t, :] = y
        return h

    h = jax.lax.fori_loop(0, t_len, step, h0_ref[0].astype(jnp.float32))
    hT_ref[0] = h


def ssm_scan_pallas(x, dt, bmat, cmat, a_log, d, h0, *, blk_c: int = 128,
                    interpret: Optional[bool] = None):
    """Same contract as models/mamba.ssm_scan:
    x, dt: (B,T,C); bmat/cmat: (B,T,N); a_log: (C,N); d: (C,);
    h0: (B,C,N). Returns (y (B,T,C) f32, hT (B,C,N) f32).
    interpret=None defers to repro.backend (REPRO_INTERPRET override)."""
    interpret = backend.resolve_interpret(interpret)
    b, t, c = x.shape
    n = a_log.shape[1]
    blk_c = min(blk_c, c)
    n_c = pl.cdiv(c, blk_c)

    kern = functools.partial(_kernel, t_len=t)
    y, hT = pl.pallas_call(
        kern,
        grid=(b, n_c),
        in_specs=[
            pl.BlockSpec((1, t, blk_c), lambda i, j: (i, 0, j)),   # x
            pl.BlockSpec((1, t, blk_c), lambda i, j: (i, 0, j)),   # dt
            pl.BlockSpec((1, t, n), lambda i, j: (i, 0, 0)),       # b
            pl.BlockSpec((1, t, n), lambda i, j: (i, 0, 0)),       # c
            pl.BlockSpec((blk_c, n), lambda i, j: (j, 0)),         # a_log
            pl.BlockSpec((blk_c, 1), lambda i, j: (j, 0)),         # d
            pl.BlockSpec((1, blk_c, n), lambda i, j: (i, j, 0)),   # h0
        ],
        out_specs=[
            pl.BlockSpec((1, t, blk_c), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, blk_c, n), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, c), jnp.float32),
            jax.ShapeDtypeStruct((b, c, n), jnp.float32),
        ],
        interpret=interpret,
    )(x, dt, bmat, cmat, a_log, d[:, None], h0)
    return y, hT


def kernel_hbm_bytes(b: int, t: int, c: int, n: int) -> float:
    """Deterministic kernel I/O: x/dt in, y out (f32) + b/c + states."""
    return float((3 * b * t * c + 2 * b * t * n + 2 * b * c * n
                  + c * n + c) * 4)
