"""Published peaks of the chips the benchmark may run on.

Keyed by ``jax.devices()[0].device_kind``. A device that is not in the
table is an error, never a default: a roofline or utilization share
against a guessed peak is not a measurement.
"""
from __future__ import annotations

import dataclasses

SOURCE = ('Google Cloud documentation, "TPU v5e" '
          '(cloud.google.com/tpu/docs/v5e): per chip')


@dataclasses.dataclass(frozen=True)
class Peak:
    bf16_flops: float        # FLOP/s, dense bf16 matrix units
    hbm_bytes_per_s: float   # HBM bandwidth
    hbm_bytes: float         # HBM capacity


PEAKS = {
    "TPU v5 lite": Peak(bf16_flops=197e12, hbm_bytes_per_s=819e9,
                        hbm_bytes=16e9),
}


class UnknownDevice(LookupError):
    """The device kind has no row in :data:`PEAKS`."""


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} ({SOURCE})") from None
