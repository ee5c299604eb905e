"""Peak rates of the chips the benchmark runs on, keyed by the
`device_kind` JAX reports. A device that is not listed is an error."""
from __future__ import annotations

PEAKS = {
    # Google Cloud TPU v5e documentation: 197 TFLOP/s bf16, 819 GB/s HBM
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud TPU v5e documentation"},
}


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no peak rates for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
