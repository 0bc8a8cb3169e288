"""Published per-chip peaks: ONE table keyed by ``device_kind``, each row
with its source. ``bench.py`` and ``profiler/device_trace.py`` both read
it for the MFU denominator. A device that is not in the table is an
error, never a default: an MFU over somebody else's peak is wrong by an
unknown factor.
"""
from __future__ import annotations

from typing import NamedTuple


class ChipPeak(NamedTuple):
    bf16_flops: float        # dense bf16 FLOP/s per chip
    source: str


_CLOUD = "Google Cloud documentation, '{}' system architecture page"

#: keys are ``jax.devices()[0].device_kind`` as libtpu reports it, one
#: per generation ("TPU v5 lite" is the v5e and the one seen on this
#: installation; "TPU v5" is the v5p)
DEVICE_PEAKS = {
    "TPU v4": ChipPeak(275e12, _CLOUD.format("TPU v4")),
    "TPU v5 lite": ChipPeak(197e12, _CLOUD.format("TPU v5e")),
    "TPU v5": ChipPeak(459e12, _CLOUD.format("TPU v5p")),
    "TPU v6 lite": ChipPeak(918e12, _CLOUD.format("TPU v6e")),
}


def device_peak(device) -> ChipPeak:
    """The table row of a jax device; raises for anything not in it."""
    kind = getattr(device, "device_kind", None)
    if device.platform != "tpu" or kind not in DEVICE_PEAKS:
        raise ValueError(
            f"no published peak for device {device.platform!r} / "
            f"{kind!r}: add a sourced row to profiler/peaks.py "
            f"(known: {sorted(DEVICE_PEAKS)})")
    return DEVICE_PEAKS[kind]
