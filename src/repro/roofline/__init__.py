from .hlo import HloCosts, analyze, parse_computations
from .terms import (
    PEAKS, TARGET_KIND, DevicePeaks, device_peaks, migration_transfer_s,
    model_flops, roofline_terms,
)

__all__ = [
    "HloCosts", "analyze", "parse_computations",
    "PEAKS", "TARGET_KIND", "DevicePeaks", "device_peaks",
    "migration_transfer_s", "model_flops", "roofline_terms",
]
