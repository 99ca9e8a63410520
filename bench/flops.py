"""Operations the algorithm needs, counted from shapes by each model
family (``families/<arch>.py``), and the chips' published peaks
(``peaks.json``)."""
from __future__ import annotations

import json
import os

import families

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def round_flops(cfg) -> int:
    """Training FLOPs of one integrated round, as the configuration's
    family counts them from shapes."""
    return families.of(cfg).round_flops(cfg)


def peak(device_kind: str, key: str = "bf16_flops_per_s") -> float:
    """A chip's published peak; a kind missing from the table is an
    error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}; add its row with a source")
    return float(table[device_kind][key])
