"""The GPP optimization journey — reproduces the paper's Table I + roofline
trajectory (Figs. 1/3/5/6) on the TPU-v5e machine model, then extends it
beyond the paper: v9 (fused VMEM scratch accumulation + parallel grid
semantics) and v10 (v9 under the repro.tune autotuner's per-size pick).

Per version v0..v10 this harness reports:
  * correctness vs the complex128 oracle at TINY (the Pallas versions run
    compiled on a TPU and interpreted elsewhere, as repro.backend decides);
  * host wall-clock of the pure-JAX variants at BENCH size (measure_cpu;
    a CPU-run number — the Pallas versions are not timed here);
  * the modeled v5e roofline: VPU compute seconds from an instruction-class
    census (mul/add=1 pass, rcp=4, sqrt=8, div=8 — the TPU analogue of the
    paper's instruction-latency ledger), HBM seconds from each version's
    traffic model, plus grid/DMA issue overhead for the Pallas versions;
  * achieved TFLOP/s and the two ceilings the paper reports against:
    %-of-theoretical (VPU peak) and %-of-customized (pass-mix attainable,
    the FMA-ratio-ceiling analogue).

The machine model and instruction census live in core.vpu_model (shared
with the tuner); the names below are re-exported for compatibility.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.core import roofline, vpu_model
from repro.core.hw import TPU_V5E
# re-exports: the public model-constant surface predates vpu_model
from repro.core.vpu_model import (  # noqa: F401
    FLOP_PEAK, FLOPS, GRID_OVERHEAD_FUSED_S, GRID_OVERHEAD_S, OP_MIX, PASSES,
    PASS_RATE, SCAN_OVERHEAD_S, OpMix)
from repro.kernels import api
from repro.kernels.gpp import kernel_def, pallas_gpp, problem, ref, variants
from repro.tune import tuner

VERSIONS = ("v0", "v1", "v2", "v3", "v4", "v5", "v6", "v7", "v8", "v9",
            "v10")


def _igp_stream_bytes(s: problem.GppSize) -> float:
    """v0–v3 traffic: scan over igp re-reads aqsn (and wx) every step."""
    b = s.ngpown * (2 * 4 * s.ncouls * s.nbands)        # aqsn per igp step
    b += 2 * 4 * s.ncouls * s.ngpown * 2                # wt/eps once
    b += 2 * 4 * s.ngpown * s.nbands                    # aqsm once
    b += s.ngpown * 4 * s.nw * s.nbands                 # wx per step
    return float(b)


def _ideal_cache_bytes(s: problem.GppSize) -> float:
    """v4/v5 traffic: band-serial with (ig,igp) planes assumed cache-resident
    (ideal-cache model — the GPU's L2 gave the paper this for free; the
    Pallas versions below make the same reuse explicit and exact)."""
    return s.min_hbm_bytes()


def _version_config(version: str,
                    size: problem.GppSize) -> pallas_gpp.BlockConfig:
    """The BlockConfig a journey version runs under at `size`: static for
    v6–v9, the tuner's model-ranked pick for v10 (measurement is the ops
    dispatch path's job — the journey models sizes far beyond CPU timing)."""
    if version == "v10":
        return tuner.rank(size, version="v10")[0][0]
    return pallas_gpp.CONFIGS[version]


@dataclasses.dataclass
class JourneyRow:
    version: str
    cpu_ms: Optional[float]
    rel_err: float
    report: roofline.RooflineReport
    note: str = ""

    @property
    def modeled_tflops(self) -> float:
        t = self.report.modeled_step_s
        return (self.report.flops_per_chip / t / 1e12) if t else 0.0


def _model_report(version: str, size: problem.GppSize) -> roofline.RooflineReport:
    mix = OP_MIX[version]
    iters = size.inner_iters
    flops = iters * mix.flops
    compute_s = iters * mix.passes / PASS_RATE
    overhead_s = 0.0
    extra = {}

    if version in ("v0", "v1", "v2", "v3"):
        hbm = _igp_stream_bytes(size)
        overhead_s = size.ngpown * SCAN_OVERHEAD_S
    elif version in ("v4", "v5"):
        hbm = _ideal_cache_bytes(size)
        overhead_s = size.nbands * SCAN_OVERHEAD_S
    else:
        # the SAME model the tuner ranks with (incl. lane-fill): a config
        # selected under one model must be reported under it too
        cfg = _version_config(version, size)
        hbm = vpu_model.pallas_bytes(size, cfg)
        with_ovh, _, overhead_s = vpu_model.pallas_step_terms(size, cfg, mix)
        compute_s = with_ovh - overhead_s
        extra["block_config"] = (cfg.blk_ig, cfg.blk_igp, cfg.blk_band)

    # customized attainable ceiling = flops at the pass-mix rate
    attainable = flops / (iters * mix.passes / PASS_RATE)

    extra.update({
        "overhead_s": overhead_s, "passes_per_iter": mix.passes,
        "flops_per_iter": mix.flops,
        # hierarchical roofline: the VMEM level (the paper's L1/L2
        # analogue). per-iter VMEM traffic ~= operand reads + select
        # intermediates spilled to VMEM between VPU ops (~24 f32
        # touches) — constant across versions, so AI_VMEM tracks the
        # flops-per-iter; AI_HBM is what the blocking steps move.
        "vmem_bytes": iters * 24 * 4.0,
        "ai_vmem": flops / (iters * 24 * 4.0),
        "ai_hbm": flops / hbm})

    rep = roofline.RooflineReport(
        name=f"gpp-{version}-{size.name}",
        mesh_shape=(1,),
        chips=1,
        flops_per_chip=flops,
        bytes_per_chip=hbm,
        collective_bytes_per_chip=0.0,
        mxu_flops_per_chip=0.0,
        compute_s=compute_s + overhead_s,
        memory_s=hbm / TPU_V5E.hbm_bw,
        collective_s=0.0,
        customized_peak_flops=attainable,
        mxu_fraction=0.0,
        extra=extra,
    )
    return rep


def _run_version(version: str, inputs_bench, inputs_tiny, ref_tiny,
                 measure_cpu: bool = True):
    if version in variants.VARIANTS:
        fn = kernel_def.jitted_variant(version)   # cached — no re-jit
        runner = lambda x: fn(x)
        a, x = runner(inputs_tiny)
    else:
        # correctness at TINY: the version's blocks clamped to the problem
        # (whole-dim blocks, which Mosaic tiles for every version);
        # compiled or interpreted as repro.backend decides
        cfg = pallas_gpp.CONFIGS.get(version, pallas_gpp.V9)
        a, x = api.dispatch("gpp", inputs_tiny, version=version,
                            config=cfg.clamped(problem.TINY))
    ar, xr = ref_tiny
    rel = max(
        float(np.max(np.abs(np.asarray(a) - ar)) / np.max(np.abs(ar))),
        float(np.max(np.abs(np.asarray(x) - xr)) / np.max(np.abs(xr))))

    cpu_ms = None
    if measure_cpu and version in variants.VARIANTS:
        out = runner(inputs_bench)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        reps = 3
        for _ in range(reps):
            out = runner(inputs_bench)
            jax.block_until_ready(out)
        cpu_ms = (time.perf_counter() - t0) / reps * 1e3
    return rel, cpu_ms


NOTES = {
    "v0": "baseline: divides, abs(), 3-way branch, igp-stream",
    "v1": "divides -> reciprocals",
    "v2": "3-way branch -> masked selects",
    "v3": "abs() -> squared-magnitude compares",
    "v4": "serialize band: AI up (ideal-cache bytes)",
    "v5": "hoist mat across iw",
    "v6": "Pallas blocking, small blocks + wrong aqsm layout (regression)",
    "v7": "aqsm index swap (lane-aligned)",
    "v8": "block-size tuning (sweep): overhead amortized",
    "v9": "fused VMEM scratch accumulation + parallel grid dims",
    "v10": "autotuned v9 (repro.tune per-size pick)",
}


def run_journey(size_name: str = "si214", *, measure_cpu: bool = True,
                verbose: bool = True) -> List[JourneyRow]:
    """Replay the paper's v0–v10 optimization journey (Table I) at one
    problem size ("tiny" / "bench" / "si214" / "si510"): every version is
    verified against the numpy oracle at TINY size, modeled on the v5e
    roofline, and (measure_cpu=True) wall-clocked at BENCH size. Returns
    one JourneyRow per version with the modeled TFLOP/s and roofline
    terms; the README journey table and `benchmarks/run.py gpp_journey`
    are formatted from these rows.

    Example::

        import repro
        rows = repro.run_journey("si214", measure_cpu=False, verbose=False)
        rows[-1].version, rows[-1].modeled_tflops     # ('v10', 4.09...)
    """
    size = problem.SIZES[size_name]
    inputs_bench = problem.make_inputs(problem.BENCH)
    inputs_tiny = problem.make_inputs(problem.TINY)
    ref_tiny = ref.ref_numpy(inputs_tiny)

    rows = []
    for v in VERSIONS:
        rel, cpu_ms = _run_version(v, inputs_bench, inputs_tiny, ref_tiny,
                                   measure_cpu=measure_cpu)
        rep = _model_report(v, size)
        note = NOTES[v]
        cfg = pallas_gpp.CONFIGS.get(v)
        if cfg is not None and not cfg.mosaic_tileable(size):
            note += f"; not compiled on the chip at {size.name}: Mosaic " \
                    "cannot tile its blocks (modeled only)"
        rows.append(JourneyRow(v, cpu_ms, rel, rep, note))
        if verbose:
            r = rows[-1]
            print(f"{v}: err={rel:.1e} cpu={cpu_ms and f'{cpu_ms:.1f}ms'} "
                  f"compute={rep.compute_s:.3f}s mem={rep.memory_s*1e3:.1f}ms "
                  f"-> {r.modeled_tflops:.2f} TF/s ({NOTES[v]})")
    return rows


def sweep_blocks(size_name: str = "si214",
                 igs=(128, 256, 512, 1024), igps=(128, 256),
                 bbs=(8, 16, 32, 64, 128)) -> List[Dict]:
    """v8 tuning: evaluate the analytic model over a block-size grid.
    Returns rows sorted by modeled step time (the hillclimb artifact).
    Superseded by repro.tune (which generalizes the space to any size and
    adds the measurement pass) but kept as the paper-step artifact."""
    size = problem.SIZES[size_name]
    mix = OP_MIX["v8"]
    out = []
    for big in igs:
        for bigp in igps:
            for bb in bbs:
                if size.ncouls % big or size.ngpown % bigp or size.nbands % bb:
                    continue
                cfg = pallas_gpp.BlockConfig("sweep", big, bigp, bb, True)
                if cfg.vmem_bytes() > TPU_V5E.vmem_bytes:
                    continue
                hbm = vpu_model.pallas_bytes(size, cfg)
                n_inst = vpu_model.grid_instances(size, cfg)
                compute = size.inner_iters * mix.passes / PASS_RATE
                t = max(compute + n_inst * GRID_OVERHEAD_S,
                        hbm / TPU_V5E.hbm_bw)
                out.append({"blk_ig": big, "blk_igp": bigp, "blk_band": bb,
                            "vmem_mib": cfg.vmem_bytes() / 2**20,
                            "hbm_gib": hbm / 2**30, "instances": n_inst,
                            "modeled_s": t,
                            "tflops": size.inner_iters * mix.flops / t / 1e12})
    return sorted(out, key=lambda r: r["modeled_s"])


def format_journey(rows: List[JourneyRow], size_name: str) -> str:
    """Markdown table mirroring the paper's Table I."""
    lines = [
        f"GPP journey — {size_name} (modeled TPU v5e; CPU ms at BENCH size)",
        "| ver | CPU ms | rel err | compute_s | memory_s | dominant | "
        "modeled TF/s | %VPU peak | %customized | note |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        rep = r.report
        tf = r.modeled_tflops
        lines.append(
            f"| {r.version} | {f'{r.cpu_ms:.1f}' if r.cpu_ms else '—'} "
            f"| {r.rel_err:.1e} | {rep.compute_s:.3f} "
            f"| {rep.memory_s:.4f} | {rep.dominant} | {tf:.2f} "
            f"| {tf * 1e12 / FLOP_PEAK:.0%} "
            f"| {tf * 1e12 / rep.customized_peak_flops:.0%} | {r.note} |")
    return "\n".join(lines)
