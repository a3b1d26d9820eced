"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` string JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GiB of HBM at 819 GB/s per
chip.  A float32 matmul at JAX's default precision runs as one bf16 pass
on the TPU, so the bf16 figure is the peak for the configurations served
in float32 here.

A device that is not in the table is an error, never a default.
"""

from __future__ import annotations

from dataclasses import dataclass

SOURCE = "Google Cloud documentation, TPU v5e system architecture"


@dataclass(frozen=True)
class Peak:
    kind: str
    matmul_flops_per_s: float  # bf16 MXU peak
    hbm_bytes_per_s: float
    hbm_bytes: int


PEAKS = {
    "TPU v5 lite": Peak("TPU v5 lite", 197e12, 819e9, 16 * 2**30),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)} (add it to {__name__} with its source)"
        ) from None
