"""Published peaks of the devices the benchmark may run on, keyed by
``jax.devices()[0].device_kind``. One table; a device that is not in it is an
error, never a default."""

from __future__ import annotations

#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at
#: 819 GB/s, for one chip
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


class UnknownDevice(LookupError):
    """The device is not in the peaks table."""


def peaks_for(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; refuses an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"device_kind {device_kind!r} is not in the peaks table "
            f"({sorted(PEAKS)}): add it with its source, do not guess"
        ) from None
