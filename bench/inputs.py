"""Inputs of a run, made from ``--seed`` by the benchmark itself, by the
family module that the configuration's ``model.arch`` names
(``families/<arch>.py``)."""
from __future__ import annotations

import families
from families import seed_key  # noqa: F401  (re-exported)


def build(cfg, seed: int):
    """Everything a cell's run needs from the seed, on the device, as the
    configuration's family builds it."""
    return families.of(cfg).build(cfg, seed)
