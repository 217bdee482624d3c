"""Published peaks of the chips the benchmark runs on, keyed by
``device_kind`` as JAX reports it.  A kind that is not here is an error,
never a default: a share of an unknown peak means nothing."""
from __future__ import annotations

#: device_kind -> peaks of ONE chip.  Source for TPU v5e: Google Cloud
#: documentation, "TPU v5e" (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM
#: at 819 GB/s).
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_for(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; raises ``KeyError``
    naming the known kinds when it is not in the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
