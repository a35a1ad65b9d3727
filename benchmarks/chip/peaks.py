"""Peak rates of each accelerator the benchmark knows, keyed by the
`device_kind` string JAX reports. A device that is not in the table is an
error: a share of a peak is never computed against a guess.

TPU v5e ("TPU v5 lite"): Google Cloud documentation, "TPU v5e" (system
architecture page): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at
819 GB/s per chip.

The VPU f32 rate is not published. It is an assumed UPPER bound, so that
no honest kernel reads over 100% of it:
  clock: 197e12 / (4 MXUs x 128 x 128 MACs x 2 FLOP) = 1.503 GHz;
  vector unit: 8 sublanes x 128 lanes = 1024 f32 lanes;
  at most 4 vector ALU ops issued per cycle, each an FMA counted as 2 FLOP;
  1024 x 4 x 2 x 1.503e9 = 12.3e12 FLOP/s.
(The repo's machine model uses 7.7e12, from a 0.94 GHz clock; that is a
convention for its predictions, not a bound.)
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    kind: str
    bf16_flops: float        # MXU, dense bf16 matmul
    vpu_f32_flops: float     # vector unit, f32 (assumed upper bound)
    hbm_bw: float            # bytes/s
    hbm_bytes: float
    source: str
    assumed: tuple = ()


_V5E_CLOCK = 197e12 / (4 * 128 * 128 * 2)

PEAKS = {
    "TPU v5 lite": Peaks(
        kind="TPU v5 lite",
        bf16_flops=197e12,
        vpu_f32_flops=1024 * 4 * 2 * _V5E_CLOCK,
        hbm_bw=819e9,
        hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e' system architecture",
        assumed=("vpu_f32_flops",)),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
