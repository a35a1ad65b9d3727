"""Plain float32 reference of the GPP (general plasmon pole) sum, and the
inputs it and the program share.

For each band, igp, ig and frequency iw (the paper's loop nest):
    wdiff = wx[iw, band] - wtilde[ig, igp]
    delw  = wtilde * conj(wdiff) / |wdiff|^2
    if |wdiff|^2 > limittwo and |delw|^2 < limitone:
        sch = delw * I_eps[ig, igp];  ssx = Omega2 / (wx^2 - wtilde^2)
    elif |delw|^2 > tol_zero:
        sch = 0;  ssx = -Omega2 * delw / (4 wtilde^2 (delw + 1/2))
    else: sch = ssx = 0
    mat = conj(aqsm[igp, band]) * aqsn[ig, band]
    achtemp[iw] += vcoul[ig] * mat * sch;  asxtemp[iw] += vcoul[ig] * mat * ssx
with Omega2 = wtilde^2 * I_eps, in complex64 arithmetic, one band at a
time. The inputs are made on the device from the seed, in the planar f32
layout the program takes, with the distributions of the program's own
problem generator (every branch is taken).

Control (`dtype=bfloat16`): the same sum with the per-element arithmetic
in bfloat16 and the sums in float32.
"""

from __future__ import annotations

import functools
from typing import Dict, Mapping

import jax
import jax.numpy as jnp
import numpy as np

LIMITONE = 1.0 / (0.25 * 0.25)
LIMITTWO = 0.25 * 0.25
TOL_ZERO = 1e-12


def key_data(seed: int, which: int) -> np.ndarray:
    s = np.random.SeedSequence([abs(int(seed)), int(int(seed) < 0), which])
    return s.generate_state(2, np.uint32)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def make_inputs(kd, nbands: int, ngpown: int, ncouls: int, nw: int
                ) -> Dict[str, jax.Array]:
    """One planar-f32 input set from raw key data."""
    ks = jax.random.split(jax.random.wrap_key_data(kd, impl="threefry2x32"), 10)
    n = lambda k, *s: jax.random.normal(k, s, jnp.float32)
    aq = 1.0 / np.sqrt(nbands)
    return {
        "wtilde_re": 0.5 * n(ks[0], ncouls, ngpown) + 1.0,
        "wtilde_im": 0.5 * n(ks[1], ncouls, ngpown),
        "eps_re": 0.3 * n(ks[2], ncouls, ngpown),
        "eps_im": 0.3 * n(ks[3], ncouls, ngpown),
        "aqsn_re": aq * n(ks[4], ncouls, nbands),
        "aqsn_im": aq * n(ks[5], ncouls, nbands),
        "aqsm_re": aq * n(ks[6], ngpown, nbands),
        "aqsm_im": aq * n(ks[7], ngpown, nbands),
        "wx": 1.5 * n(ks[8], nw, nbands) + 1.0,
        "vcoul": jax.random.uniform(ks[9], (ncouls,), jnp.float32) + 0.1,
    }


def _band(inp, b, dt):
    c = lambda re, im: jax.lax.complex(re.astype(dt).astype(jnp.float32),
                                       im.astype(dt).astype(jnp.float32))
    r = lambda x: x.astype(dt).astype(jnp.float32)
    rc = lambda z: jax.lax.complex(r(z.real), r(z.imag))
    wt = c(inp["wtilde_re"], inp["wtilde_im"])
    eps = c(inp["eps_re"], inp["eps_im"])
    aqsn = c(inp["aqsn_re"][:, b], inp["aqsn_im"][:, b])
    aqsm = c(inp["aqsm_re"][:, b], inp["aqsm_im"][:, b])
    vc = r(inp["vcoul"])
    mat = rc(jnp.conj(aqsm)[None, :] * (aqsn * vc)[:, None])
    om2 = rc(rc(wt * wt) * eps)
    ach, asx = [], []
    for iw in range(inp["wx"].shape[0]):
        wx = r(inp["wx"][iw, b])
        wd = rc(wx - wt)
        wdr = r(wd.real * wd.real + wd.imag * wd.imag)
        delw = rc(rc(wt * jnp.conj(wd)) / jnp.maximum(wdr, 1e-30))
        delwr = r(delw.real * delw.real + delw.imag * delw.imag)
        b1 = (wdr > LIMITTWO) & (delwr < LIMITONE)
        b2 = ~b1 & (delwr > TOL_ZERO)
        sch = jnp.where(b1, rc(delw * eps), 0)
        cden1 = rc(wx * wx - rc(wt * wt))
        cden2 = rc(4.0 * rc(wt * wt) * (delw + 0.5))
        ssx1 = rc(om2 / jnp.where(cden1 == 0, 1.0, cden1))
        ssx2 = rc(-om2 * delw / jnp.where(cden2 == 0, 1.0, cden2))
        ssx = jnp.where(b1, ssx1, jnp.where(b2, ssx2, 0))
        ach.append(jnp.sum(mat * sch))
        asx.append(jnp.sum(mat * ssx))
    return jnp.stack(ach), jnp.stack(asx)


@functools.partial(jax.jit, static_argnums=(1,))
def _gpp(inp, dt_name: str):
    dt = jnp.dtype(dt_name)

    def body(acc, b):
        a, s = _band(inp, b, dt)
        return (acc[0] + a, acc[1] + s), None

    nw = inp["wx"].shape[0]
    zero = jnp.zeros((nw,), jnp.complex64)
    (ach, asx), _ = jax.lax.scan(body, (zero, zero),
                                 jnp.arange(inp["wx"].shape[1]))
    return ach, asx


def gpp(inp: Mapping[str, jax.Array], dtype: str = "float32"):
    """(achtemp, asxtemp), each (nw,) complex64."""
    with jax.default_matmul_precision("highest"):
        return _gpp(dict(inp), dtype)


def rel_err(got, want) -> float:
    """Largest deviation over an output, relative to its largest entry."""
    got, want = np.asarray(got), np.asarray(want)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
