"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

The benchmark keeps its own table so that no change to the program can
move the yardstick.  A device kind that is not here is an error: a share
of a peak is never computed against another chip's numbers.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float  # FLOP/s
    int8_ops: float  # OP/s
    hbm_bytes: float  # bytes of device memory
    hbm_bytes_per_s: float
    ici_bytes_per_s: float  # chip-to-chip, all links together
    source: str


PEAKS: dict[str, Peaks] = {
    # TPU v5e reports its kind as "TPU v5 lite".
    "TPU v5 lite": Peaks(
        bf16_flops=197e12,
        int8_ops=393e12,
        hbm_bytes=16e9,
        hbm_bytes_per_s=819e9,
        ici_bytes_per_s=1600e9 / 8,
        source=(
            "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
            "393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI"
        ),
    ),
}


class UnknownDeviceError(KeyError):
    """The device kind has no published peaks in :data:`PEAKS`."""


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDeviceError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None
