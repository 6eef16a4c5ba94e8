"""Published peaks of the chips the benchmark knows, keyed by ``device_kind``.

The yardstick is the benchmark's own copy: the program's table
(``paddle_tpu.cost_model``) may change with the program, this one may not.
A device that is not here is an error, never a default."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
    # 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s interconnect per chip.
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
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the table has {sorted(PEAKS)}")
    return PEAKS[device_kind]
