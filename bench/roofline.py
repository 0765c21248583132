"""The chips' peaks, and the least time a dispatch's work needs on them.

A dispatch's work, (FLOPs, bytes), is its configuration family's to count
(``decode_work`` and ``prefill_work`` in ``bench/families/<model_type>.py``),
from the published architecture and the live lengths, so it is the same
whatever implements it: empty slots, padding rows and pages, and copies of
pages gathered for attention are never work. FLOPs count 2 per
multiply-add; bytes count what the step has to move to and from HBM once.
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s
    hbm_bw: float            # bytes/s
    source: str


# Copied from the program's table (repro.obs.profile.DEVICE_PEAKS) so that
# a change to the program cannot move the yardstick.
DEVICE_PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, hbm_bw=819e9,
        source='Google Cloud documentation, "TPU v5e": per chip 197 '
               'TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s'),
}


def peaks(device_kind: str) -> Peaks:
    """Peaks of ``device_kind``; a kind not in the table is an error."""
    if device_kind not in DEVICE_PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(DEVICE_PEAKS)})")
    return DEVICE_PEAKS[device_kind]


def least_time(flops: int, byt: int, p: Peaks) -> float:
    """Seconds the chip needs at best: the larger of the two bounds."""
    return max(flops / p.bf16_flops, byt / p.hbm_bw)
