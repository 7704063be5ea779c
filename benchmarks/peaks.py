"""Published peaks of the devices the benchmark has run on, keyed by
``jax.devices()[0].device_kind``, and the arithmetic that turns a
step's shapes into the bytes it must move. A device that is not in the
table is an error, never a default."""

from __future__ import annotations

from typing import Dict

# Google Cloud documentation, "TPU v5e" (as quoted in the
# on-chip-measurement guide): 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}: add it "
            "to benchmarks/peaks.py with its source (known: "
            f"{sorted(PEAKS)})") from None


def train_step_min_bytes(distinct_rows: float, row_dim: int,
                         batch_bytes: float) -> float:
    """Bytes one sparse-Adagrad step must move at the least: every
    distinct row of the table and of the accumulator read once and
    written once (f32), plus the batch itself."""
    return distinct_rows * row_dim * 4 * 4 + batch_bytes


def roofline_share_pct(min_bytes: float, device_seconds: float,
                       device_kind: str) -> float:
    """Least time the bytes could take at the device's peak bandwidth
    over the time measured, in percent (bytes-bound by construction:
    the step has no matmul worth its FLOPs)."""
    if device_seconds <= 0:
        raise ValueError("device time must be positive")
    least = min_bytes / peaks_for(device_kind)["bytes_per_s"]
    return 100.0 * least / device_seconds
