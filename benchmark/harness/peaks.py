"""Published peaks of the chips the benchmark may run on, keyed by JAX's
``device_kind``. A device that is not in the table is an error, never a
default: a share of an unknown peak is not a number."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" system architecture page: 197
    # TFLOP/s bf16 and 393 TOP/s int8 per chip, 16 GB of HBM2e at 819 GB/s,
    # 1,600 Gbit/s of chip-to-chip interconnect. (The program's own copies
    # are measure.py::PEAK_FLOPS and profiling/registry.py::
    # PEAK_HBM_BYTES_PER_S; the benchmark reads neither.)
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e system architecture)",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add a row "
            f"to benchmark/harness/peaks.py with its source (known: "
            f"{sorted(PEAKS)})"
        ) from None
