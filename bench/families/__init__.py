"""Model families of the benchmark, one module each, found by the name a
configuration states in ``model.arch``: ``bench/families/<arch>.py``.

A family module has three functions:

- ``build(cfg, seed) -> inputs``: everything a run needs, made on the
  device from the seed (any pytree, with ``key`` and ``run_key``);
- ``to_host(inputs) -> host_inputs``: what the family's plain reference
  takes on the host;
- ``round_flops(cfg) -> int``: the training FLOPs of one integrated
  round, counted from shapes.

``seed_key`` is the key every family's ``build`` starts from.
"""
from __future__ import annotations

import importlib
import os

import jax

HERE = os.path.dirname(os.path.abspath(__file__))


def seed_key(seed: int):
    """A PRNG key for any whole ``seed`` below 2**64. ``jax.random.key``
    keeps only the low 32 bits of a larger int without 64-bit mode, so the
    high word is folded in; seeds below 2**32 give ``jax.random.key(seed)``
    unchanged (the program's own key for that seed)."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"--seed must lie in [0, 2**64), got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    if seed >> 32:
        key = jax.random.fold_in(key, seed >> 32)
    return key


def of(cfg):
    """The family module of ``cfg["model"]["arch"]``. An arch with no
    module is an error that names the file looked for; nothing falls
    back to another family."""
    arch = cfg["model"]["arch"]
    path = os.path.join(HERE, f"{arch}.py")
    if not arch.isidentifier() or not os.path.isfile(path):
        raise ModuleNotFoundError(
            f"model.arch {arch!r} has no family module: {path} not found")
    return importlib.import_module(f"families.{arch}")
