"""Published peaks of each accelerator the benchmark may run on.

Keyed by ``jax.Device.device_kind``.  A kind that is not here ends the
run as a failure: a roofline or utilization against a guessed peak is
not a measurement.
"""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (system architecture): per chip
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {
        "int8_ops_per_s": 393e12,
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peak row of ``device_kind``; KeyError names the known kinds."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def roofline_s(int8_ops: float, bytes_moved: float, peaks: dict) -> float:
    """Least time of a piece of int8 work: the larger of its operations
    over the int8 peak and its bytes over HBM bandwidth."""
    return max(int8_ops / peaks["int8_ops_per_s"],
               bytes_moved / peaks["hbm_bytes_per_s"])
