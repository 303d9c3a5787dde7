"""Published per-chip peaks: the one table every utilization is over.

Keyed by what the chip itself says (`jax.devices()[0].device_kind`; a
v5e says "TPU v5 lite"), with both names where JAX knows two. A device
kind that is not here is an error on a TPU, not a default: a utilization
over an assumed peak is a number about some other chip.

Source: Google Cloud TPU documentation, system architecture pages
("TPU v4", "TPU v5e", "TPU v5p", "TPU v6e"), peak compute per chip, bf16.
On v4 and v5p a JAX device is a whole chip (megacore).
"""

from __future__ import annotations

from typing import Optional

PEAK_BF16_FLOPS_PER_S = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def device_report() -> dict:
    """The device this process computes on, as JAX reports it: the three
    keys every result names its device by."""
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count()}


def peak_flops_per_s(device) -> Optional[float]:
    """bf16 peak of one JAX device; None off the TPU (a CPU test mesh has
    no peak worth dividing by)."""
    if device.platform != "tpu":
        return None
    try:
        return PEAK_BF16_FLOPS_PER_S[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no published peak for device_kind {device.device_kind!r}: "
            "add it to ray_tpu/util/device_peaks.py with its source"
        ) from None
