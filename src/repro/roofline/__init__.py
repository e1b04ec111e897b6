"""Roofline analysis: HLO collective parsing + three-term model."""

from .analysis import (
    DEVICE_PEAKS,
    DevicePeaks,
    HBM_BW,
    ICI_BW,
    PEAK_FLOPS,
    Roofline,
    model_flops,
    roofline,
    device_peaks,
    slstm_extra_flops,
)
from .hlo import CollectiveStats, parse_collectives

__all__ = [
    "DEVICE_PEAKS",
    "DevicePeaks",
    "device_peaks",
    "Roofline",
    "roofline",
    "model_flops",
    "slstm_extra_flops",
    "parse_collectives",
    "CollectiveStats",
    "PEAK_FLOPS",
    "HBM_BW",
    "ICI_BW",
]
