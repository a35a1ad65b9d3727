"""Hardware model for the roofline analysis (target: TPU v5e).

The container is CPU-only; TPU v5e is the *target* machine. All roofline terms
are derived from compiled HLO artifacts against these constants, mirroring the
paper's use of machine peaks (V100: 6.7 TFLOP/s FP64 @ 1312 MHz, ~900 GB/s HBM)
as the denominators of its roofline charts.

The paper's "customized ceiling" (58% FMA ratio => 5.3 TFLOP/s attainable) is
generalized here to the MXU/VPU split: matmul FLOPs run at MXU peak, everything
else at VPU peak, and the attainable ceiling is the harmonic combination
(see roofline.customized_ceiling).
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    name: str
    # Peak matrix-unit throughput, FLOP/s (bf16 inputs, f32 accumulate).
    mxu_flops: float
    # Peak vector-unit throughput, FLOP/s (f32). Convention: 8x128 lanes x
    # 2 (FMA) x 4 ALU pipes x ~0.94 GHz ~= 7.7e12. Elementwise/reduction work
    # (e.g. the GPP kernel) is bounded by this roof, not the MXU roof --
    # this is the TPU analogue of the paper's FMA-ratio-customized peak.
    vpu_flops: float
    # HBM bandwidth per chip, bytes/s.
    hbm_bw: float
    # ICI link bandwidth, bytes/s per link (one direction).
    ici_bw: float
    # VMEM capacity per core, bytes (the "cache" level of the hierarchy).
    vmem_bytes: int
    # HBM capacity per chip, bytes.
    hbm_bytes: int
    # Data-path interconnect bandwidth between pods (DCN), bytes/s per host.
    dcn_bw: float = 25e9 / 8  # 25 Gb/s NIC per host, conservative

    @property
    def machine_balance(self) -> float:
        """FLOP/byte at which HBM bandwidth and MXU peak intersect.

        The paper quotes 7.4 FLOPs/Byte for V100 FP64; for v5e bf16 this is
        197e12 / 819e9 ~= 240 FLOPs/Byte -- the compute-bound bar is far
        higher on TPUs, which is why bandwidth terms dominate most of the
        baseline table.
        """
        return self.mxu_flops / self.hbm_bw

    @property
    def vpu_machine_balance(self) -> float:
        return self.vpu_flops / self.hbm_bw


# Per-chip numbers, TPU v5e (the assignment's stated constants).
TPU_V5E = HardwareSpec(
    name="tpu-v5e",
    mxu_flops=197e12,
    vpu_flops=7.7e12,
    hbm_bw=819e9,
    ici_bw=50e9,
    # the scoped-VMEM limit Mosaic gives one Pallas kernel by default on
    # v5e (the chip has 128 MiB of VMEM; a kernel may raise its own limit)
    vmem_bytes=16 * 2**20,
    hbm_bytes=16 * 2**30,
)

# The paper's machine, kept for the GPP journey's "paper units" columns.
NVIDIA_V100 = HardwareSpec(
    name="nvidia-v100",
    mxu_flops=6.7e12,   # FP64 theoretical peak @1312MHz (paper Sec. II-C)
    vpu_flops=6.7e12,   # no MXU/VPU split on V100 FP64
    hbm_bw=900e9,
    ici_bw=25e9,        # NVLink gen2 per link
    vmem_bytes=6 * 2**20,   # L2 size as the mid-hierarchy level
    hbm_bytes=16 * 2**30,
)

DTYPE_BYTES = {
    "pred": 1,
    "s4": 1, "u4": 1,
    "s8": 1, "u8": 1,
    "s16": 2, "u16": 2,
    "s32": 4, "u32": 4,
    "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3": 1, "f8e5m2fnuz": 1,
    "bf16": 2, "f16": 2,
    "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}
