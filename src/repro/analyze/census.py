"""Static per-kernel census: lower a registered `(kernel, version, problem
shape)` to jaxpr and count what it would execute — WITHOUT running the
kernel (docs/analysis.md §Census).

This is the registry-wide generalization of the paper's Nsight census: the
analogue of its FMA-ratio, register-pressure and memory-traffic counters,
derived from the traced jaxpr instead of a profiler run:

  * `flops` / `dot_flops` — every floating/complex arithmetic primitive
    counted at 1 FLOP per output element (dots at 2·M·N·K), scaled through
    `scan` lengths and `pallas_call` grids;
  * `fma_fraction` — the fraction of FLOPs that can retire as mul+add FMA
    pairs (`2·min(mul, add/sub) / flops`), the paper's 58%-FMA lens; the
    `core.vpu_model` PASSES/FLOPS tables charge exactly these pairs 2
    FLOPs per VPU pass, so the census fraction is directly comparable to
    a version's OpMix (`fma·2 / flops`);
  * bytes per memory level — compulsory HBM traffic (top-level operand +
    result avals) and the Pallas VMEM block working set read off the
    kernel's BlockSpecs (double-buffered);
  * `bound_s` — the census-derived roofline lower bound
    `max(flops/ceiling, hbm_bytes/bw)` with the MXU/VPU customized ceiling,
    which the MODEL001 drift rule holds each kernel's declared
    `model_step_s` against;
  * structural counters — pallas grid instances, statically-unbounded
    `while` loops, duplicate (CSE-able) expensive equations.

Branches (`cond` / `pl.when`) are counted at their most expensive branch —
the census is an upper estimate there, which is why MODEL001 compares with
a tolerance instead of exact equality.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core.hw import TPU_V5E
from repro.core.roofline import customized_ceiling

# primitive classes (jaxpr primitive names). Everything here counts 1 FLOP
# per output element when any operand/result dtype is inexact; dots are
# counted at 2·result·contraction. Data-movement primitives are free.
_MUL_OPS = {"mul"}
_ADDSUB_OPS = {"add", "sub", "add_any"}
_EW_OPS = {
    "div", "rsqrt", "sqrt", "cbrt", "exp", "exp2", "expm1", "log", "log1p",
    "tanh", "logistic", "pow", "integer_pow", "erf", "erfc", "erf_inv",
    "sin", "cos", "tan", "atan2", "rem", "neg", "abs", "sign", "max", "min",
    "floor", "ceil", "round", "clamp", "nextafter", "select_n", "square",
    "ge", "gt", "le", "lt", "eq", "ne", "and", "or", "xor", "not",
    "is_finite", "real", "imag", "conj", "complex",
}
_REDUCE_OPS = {
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "argmax", "argmin", "cumsum", "cummax", "cummin",
    "cumprod", "cumlogsumexp",
}
# eqns cheaper than this many FLOPs are not fingerprinted for duplicates
DUP_MIN_FLOPS = 1024.0


def _aval_elems(aval) -> float:
    n = 1.0
    for d in getattr(aval, "shape", ()):
        n *= int(d)
    return n


def _aval_bytes(aval) -> float:
    try:
        return _aval_elems(aval) * np.dtype(aval.dtype).itemsize
    except Exception:
        return 0.0


def _is_inexact(aval) -> bool:
    dt = getattr(aval, "dtype", None)
    if dt is None:
        return False
    # jnp.issubdtype, not np: bfloat16 & friends are ml_dtypes extension
    # types outside numpy's hierarchy (np.issubdtype calls them exact)
    import jax.numpy as jnp
    return jnp.issubdtype(dt, jnp.inexact)


@dataclasses.dataclass
class JaxprCensus:
    """Raw counters accumulated by the jaxpr walk (all loop/grid-scaled)."""
    flops: float = 0.0
    dot_flops: float = 0.0
    mul_flops: float = 0.0
    addsub_flops: float = 0.0
    float_dtypes: set = dataclasses.field(default_factory=set)
    grid_instances: int = 0
    vmem_block_bytes: int = 0          # max working set over pallas_calls
    unbounded_loops: int = 0
    duplicate_eqns: int = 0
    duplicate_flops: float = 0.0

    @property
    def fma_flops(self) -> float:
        """FLOPs retiring in mul+add pairs: 2 per pairable (mul, add)."""
        return 2.0 * min(self.mul_flops, self.addsub_flops)

    @property
    def fma_fraction(self) -> float:
        return self.fma_flops / self.flops if self.flops > 0 else 0.0

    def _merge_max(self, other: "JaxprCensus") -> None:
        """Branch merge: numeric counters from the more expensive branch
        are already chosen by the caller; dtypes union unconditionally."""
        self.float_dtypes |= other.float_dtypes


def _eqn_flops(eqn) -> Tuple[float, float, str]:
    """(flops, dot_flops, klass) for one equation, unscaled."""
    name = eqn.primitive.name
    inexact = any(_is_inexact(v.aval) for v in eqn.invars
                  if hasattr(v, "aval")) or \
        any(_is_inexact(v.aval) for v in eqn.outvars)
    if not inexact:
        return 0.0, 0.0, "int"
    if name in ("dot_general",):
        out = eqn.outvars[0].aval
        (lhs_c, _), _ = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval
        contract = 1.0
        for d in lhs_c:
            contract *= int(lhs.shape[d])
        f = 2.0 * _aval_elems(out) * contract
        return f, f, "dot"
    if name in _MUL_OPS:
        return _aval_elems(eqn.outvars[0].aval), 0.0, "mul"
    if name in _ADDSUB_OPS:
        return _aval_elems(eqn.outvars[0].aval), 0.0, "addsub"
    if name in _EW_OPS:
        return _aval_elems(eqn.outvars[0].aval), 0.0, "ew"
    if name in _REDUCE_OPS:
        src = eqn.invars[0]
        n = _aval_elems(src.aval) if hasattr(src, "aval") else 0.0
        return n, 0.0, "addsub" if name in ("reduce_sum", "cumsum") else "ew"
    return 0.0, 0.0, "free"


def _sub_jaxprs(params: Dict) -> List[Tuple[Any, float]]:
    """(jaxpr, multiplier) pairs hidden in a primitive's params — the
    generic fallback for call-like primitives."""
    out = []
    for v in params.values():
        if hasattr(v, "jaxpr") and hasattr(v, "eqns") is False:
            out.append((v.jaxpr, 1.0))          # ClosedJaxpr
        elif hasattr(v, "eqns"):
            out.append((v, 1.0))                # raw Jaxpr
        elif isinstance(v, (tuple, list)):
            for item in v:
                if hasattr(item, "jaxpr"):
                    out.append((item.jaxpr, 1.0))
                elif hasattr(item, "eqns"):
                    out.append((item, 1.0))
    return out


def _dup_key(eqn):
    """Fingerprint for CSE-able duplicate detection: primitive + operand
    identities + shape. Two eqns with the same key recompute the same
    value (remat-style waste, the paper's duplicate-dot lens)."""
    ops = []
    for v in eqn.invars:
        if hasattr(v, "aval") and hasattr(v, "count"):
            ops.append(("v", id(v)))
        else:  # Literal
            ops.append(("l", str(getattr(v, "val", v))))
    shape = tuple(getattr(eqn.outvars[0].aval, "shape", ())) \
        if eqn.outvars else ()
    return (eqn.primitive.name, tuple(ops), shape)


def _census_branch(jaxpr, scale: float) -> JaxprCensus:
    c = JaxprCensus()
    _walk(jaxpr, scale, c)
    return c


def _walk(jaxpr, scale: float, out: JaxprCensus) -> None:
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)        # ClosedJaxpr -> Jaxpr
    seen: Dict[Any, int] = {}
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is not None and _is_inexact(aval):
                out.float_dtypes.add(str(aval.dtype))

        if name == "scan":
            length = float(eqn.params.get("length", 1))
            _walk(eqn.params["jaxpr"], scale * length, out)
            continue
        if name == "while":
            out.unbounded_loops += 1
            _walk(eqn.params["body_jaxpr"], scale, out)
            _walk(eqn.params["cond_jaxpr"], scale, out)
            continue
        if name == "cond":
            branches = [_census_branch(b, scale)
                        for b in eqn.params["branches"]]
            best = max(branches, key=lambda c: c.flops, default=None)
            if best is not None:
                for other in branches:
                    best._merge_max(other)
                out.flops += best.flops
                out.dot_flops += best.dot_flops
                out.mul_flops += best.mul_flops
                out.addsub_flops += best.addsub_flops
                out.float_dtypes |= best.float_dtypes
                out.grid_instances += best.grid_instances
                out.vmem_block_bytes = max(out.vmem_block_bytes,
                                           best.vmem_block_bytes)
                out.unbounded_loops += best.unbounded_loops
                out.duplicate_eqns += best.duplicate_eqns
                out.duplicate_flops += best.duplicate_flops
            continue
        if name == "pallas_call":
            gm = eqn.params.get("grid_mapping")
            grid = 1.0
            for g in getattr(gm, "grid", ()) or ():
                if isinstance(g, int):
                    grid *= g
            out.grid_instances += int(grid * scale)
            vmem = 0
            for bm in getattr(gm, "block_mappings", ()) or ():
                # block dims are Blocked(block_size=n); Squeezed dims hold
                # one element and are skipped
                blk = [d.block_size for d in bm.block_shape
                       if hasattr(d, "block_size")]
                if blk:
                    n = 1
                    for d in blk:
                        n *= d
                    itemsize = np.dtype(bm.array_aval.dtype).itemsize
                    vmem += 2 * n * itemsize               # dbl-buffer
            out.vmem_block_bytes = max(out.vmem_block_bytes, vmem)
            _walk(eqn.params["jaxpr"], scale * grid, out)
            continue
        subs = _sub_jaxprs(eqn.params)
        if subs:                                   # pjit / calls / custom_*
            for sub, mult in subs:
                _walk(sub, scale * mult, out)
            continue

        f, df, klass = _eqn_flops(eqn)
        if f <= 0.0:
            continue
        out.flops += f * scale
        out.dot_flops += df * scale
        if klass == "mul":
            out.mul_flops += f * scale
        elif klass == "addsub":
            out.addsub_flops += f * scale
        if f >= DUP_MIN_FLOPS:
            k = _dup_key(eqn)
            n = seen.get(k, 0)
            seen[k] = n + 1
            if n:
                out.duplicate_eqns += 1
                out.duplicate_flops += f * scale


def census_jaxpr(closed) -> JaxprCensus:
    """Walk a (Closed)Jaxpr and return the scaled counters."""
    c = JaxprCensus()
    _walk(closed, 1.0, c)
    return c


# ---------------------------------------------------------------------------
# per-kernel census
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class KernelCensus:
    """The auditor's per-(kernel, version, shape) record — the static
    analogue of one Nsight Compute profile (schema: docs/analysis.md)."""
    kernel: str
    version: str
    key_name: str
    key_dims: str
    flops: float
    dot_flops: float
    fma_flops: float
    fma_fraction: float
    hbm_bytes: float                    # compulsory: operands + results
    vmem_block_bytes: Optional[int]     # BlockSpec working set (pallas)
    vmem_config_bytes: Optional[int]    # the config's declared VMEM model
    arithmetic_intensity: float         # flops / hbm_bytes
    grid_instances: int
    unbounded_loops: int
    duplicate_eqns: int
    duplicate_flops: float
    float_dtypes: Tuple[str, ...]
    bound_s: float                      # census roofline lower bound
    model_s: Optional[float]            # declared model_step_s (if any)
    config: Optional[Dict] = None

    def row(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["float_dtypes"] = list(self.float_dtypes)
        return d


def resolve_config(k, version: str, key) -> Optional[Any]:
    """The config the auditor (and dispatch, absent a measured cache)
    charges this version with: the clamped static config when the version
    has one, else the model-ranked top candidate for tunable versions.
    Fully deterministic — never reads the tune cache, never measures."""
    cfg = k.static_config(key, version)
    if cfg is None and version in k.tunable:
        from repro.tune import tuner
        ranked = tuner.rank_kernel(k.name, key, version=version)
        if ranked:
            cfg = k.finalize_config(ranked[0][0], version)
    return cfg


def census_kernel(kernel, version: str, key, *, config: Any = None
                  ) -> KernelCensus:
    """Trace `(kernel, version, key)` to jaxpr and census it statically.

    Inputs come from the kernel's `make_example` (synthesis only — the
    traced function itself is never executed); `config=None` resolves via
    `resolve_config`. Works for every registered family, Pallas or
    pure-JAX.

    Example::

        from repro.analyze.census import census_kernel
        from repro.kernels import api
        from repro.kernels.gpp import problem
        c = census_kernel(api.get_kernel("gpp"), "v10", problem.TINY)
        c.flops > 0 and 0 <= c.fma_fraction <= 1    # True
    """
    from repro.kernels import api
    k = api.get_kernel(kernel) if isinstance(kernel, str) else kernel
    cfg = config if config is not None else resolve_config(k, version, key)
    args, kwargs = k.make_example(key)

    def traced(*a):
        return k.run(*a, version=version, config=cfg, interpret=True,
                     **kwargs)

    closed = jax.make_jaxpr(traced)(*args)
    jc = census_jaxpr(closed)

    hbm = sum(_aval_bytes(v.aval) for v in closed.jaxpr.invars)
    hbm += sum(_aval_bytes(v.aval) for v in closed.jaxpr.outvars)

    vmem_cfg = None
    if cfg is not None:
        clamped = k.clamp(cfg, key)
        vmem_cfg = k.config_vmem_bytes(clamped, key)

    model_s = None
    if cfg is not None:
        try:
            model_s = float(k.model_step_s(key, cfg, version))
        except Exception:
            model_s = None

    peak = customized_ceiling(jc.flops, jc.dot_flops)
    bound_s = max(jc.flops / peak if peak > 0 else 0.0,
                  hbm / TPU_V5E.hbm_bw)

    return KernelCensus(
        kernel=k.name,
        version=version,
        key_name=getattr(key, "name", "?"),
        key_dims=key.key_dims(),
        flops=jc.flops,
        dot_flops=jc.dot_flops,
        fma_flops=jc.fma_flops,
        fma_fraction=jc.fma_fraction,
        hbm_bytes=hbm,
        vmem_block_bytes=jc.vmem_block_bytes or None,
        vmem_config_bytes=vmem_cfg,
        arithmetic_intensity=jc.flops / hbm if hbm > 0 else 0.0,
        grid_instances=jc.grid_instances,
        unbounded_loops=jc.unbounded_loops,
        duplicate_eqns=jc.duplicate_eqns,
        duplicate_flops=jc.duplicate_flops,
        float_dtypes=tuple(sorted(jc.float_dtypes)),
        bound_s=bound_s,
        model_s=model_s,
        config=k.config_to_json(cfg) if cfg is not None else None,
    )
