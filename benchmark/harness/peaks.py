"""Published peaks of the chips the benchmark may run on, keyed by
``jax.devices()[0].device_kind``.  A device that is not here is an
error, never a default.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s
of chip-to-chip interconnect per chip.  (The program keeps its own copy
in ``training/memory.py:tpu_peaks``; this one is the benchmark's.)
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "cloud.google.com/tpu/docs/v5e (system architecture)",
    },
}
# The same chip under the names other runtimes give it.
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it "
            f"to benchmark/harness/peaks.py with its source (known: "
            f"{sorted(PEAKS)})") from None
