"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``. A device that is not in the table is an error.

Source: Google Cloud documentation, "TPU v5e" (system architecture table):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.

The Pegasus work the benchmark counts (comparisons and adds on float32
values) has no float32 peak in that table; it is divided by the bf16 peak,
the fastest rate the chip offers for any floating-point work, so a share
of it is an upper bound on how much of the chip the work could use.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops": 197e12,            # bf16, the divisor for all float work
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
