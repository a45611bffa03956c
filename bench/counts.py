"""The benchmark's yardstick: the published peaks of each chip, and the
least time of a state transfer.  A model's own operations and bytes are
counted by its reference (``bench/reference/<name>.py``) from the
configuration's sizes, never from the program's arrays or estimates.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterable, Tuple


@dataclass(frozen=True)
class Peaks:
    flops: float           # dense bf16 FLOP/s
    hbm_bw: float          # HBM bytes/s
    ici_link_bw: float     # bytes/s on one ICI link
    ici_links: int
    source: str


PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bw=819e9, ici_link_bw=50e9, ici_links=4,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip "
               "(4 links x 50 GB/s)"),
}


def peaks(device_kind: str) -> Peaks:
    """Peaks of a device kind; a kind missing from the table is an error."""
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}: add a row to bench/counts.py")
    return PEAKS[device_kind]


def transfer_least_s(moves: Iterable[Tuple[int, int, float]],
                     p: Peaks) -> float:
    """The least time to move ``(src_device, dst_device, bytes)`` items,
    taken as one phase (phases that run one after another only add).

    Per device: the bytes it reads and writes over HBM, and the bytes that
    leave or enter it over its ICI links together.  The least time is the
    largest of these over the devices.  (The per-device ICI bound uses all
    of its links: a route over one link alone is a guess about the
    runtime's routing, and would not bound the time from below.)"""
    hbm: Dict[int, float] = {}
    out: Dict[int, float] = {}
    inn: Dict[int, float] = {}
    for src, dst, nbytes in moves:
        hbm[src] = hbm.get(src, 0.0) + nbytes
        hbm[dst] = hbm.get(dst, 0.0) + nbytes
        if src != dst:
            out[src] = out.get(src, 0.0) + nbytes
            inn[dst] = inn.get(dst, 0.0) + nbytes
    ici = p.ici_link_bw * p.ici_links
    bounds = [b / p.hbm_bw for b in hbm.values()]
    bounds += [b / ici for b in list(out.values()) + list(inn.values())]
    return max(bounds, default=0.0)
