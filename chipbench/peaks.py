"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``. Source: Google Cloud documentation, "TPU v5e" (system
architecture page): 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s per chip.
A device that is not in the table is an error, never a default."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: dict) -> float:
    """Least time the chip could take for (flops, nbytes), over the time
    measured, in %."""
    least = max(flops / peaks["flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
