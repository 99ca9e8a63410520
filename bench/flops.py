"""Operations the algorithm needs, counted from shapes, and the chips'
published peaks (``peaks.json``)."""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def mlp_step_flops(in_dim: int, hidden: int, n_classes: int) -> int:
    """Matmul FLOPs of one training sample of the MLP in->hidden->classes:
    the forward pass (2 per weight), the weight gradients (2 per weight)
    and the gradient into the hidden layer (2 per second-layer weight).
    The gradient into the input is never needed and never computed."""
    w1, w2 = in_dim * hidden, hidden * n_classes
    return 2 * (w1 + w2) + 2 * (w1 + w2) + 2 * w2


def round_flops(cfg) -> int:
    """Training FLOPs of one integrated round: tau local steps of every
    client over its whole local set. The global-loss eval and the PoW
    hashes are left out."""
    m, rs = cfg["model"], cfg["round_spec"]
    return (mlp_step_flops(m["in_dim"], m["hidden"], m["n_classes"])
            * cfg["data"]["samples_per_client"] * rs["tau"] * rs["n_clients"])


def peak(device_kind: str, key: str = "bf16_flops_per_s") -> float:
    """A chip's published peak; a kind missing from the table is an
    error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}; add its row with a source")
    return float(table[device_kind][key])
