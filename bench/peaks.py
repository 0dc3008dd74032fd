"""Published peaks of the chips the benchmark may run on, keyed by
``device_kind`` as JAX reports it.

Source for TPU v5e: Google Cloud documentation, "TPU v5e" (197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip).  A device kind
that is not listed is an error, never a default: a roofline share
against the wrong chip's peak is a wrong number.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> dict:
    """The peak table row for ``device_kind``; raises :class:`UnknownDevice`."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(f"no published peaks for device kind "
                            f"{device_kind!r}; add them to bench/peaks.py "
                            f"with their source") from None
