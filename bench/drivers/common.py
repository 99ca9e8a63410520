"""What the drivers share: the RoundSpec a configuration states, and the
seeded sample of runs that the reference checks."""
from __future__ import annotations

import numpy as np

from repro.core import rounds, topology

# RoundSpec fields a configuration may set; "topology" is parsed by name
SPEC_FIELDS = ("n_clients", "tau", "eta", "n_lazy", "sigma2", "dp_sigma",
               "mine_attempts", "difficulty_bits", "eval_every",
               "use_kernel", "fused_mix", "fast_allreduce")


def round_spec(cfg) -> rounds.RoundSpec:
    rs = cfg["round_spec"]
    return rounds.RoundSpec(topology=topology.from_name(rs["topology"]),
                            **{k: rs[k] for k in SPEC_FIELDS})


def blocks_of(ledger, start=0):
    """The ledger's block headers from ``start`` as int tuples."""
    return [(b.index, b.prev_hash, b.model_digest, b.winner, b.nonce,
             b.pow_hash) for b in ledger.blocks[start:]]


class Reservoir:
    """A uniform sample of ``size`` items from a stream of unknown length,
    drawn from the seed (reservoir sampling)."""

    def __init__(self, size: int, seed: int):
        self.size = size
        self.rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 17])
        self.items = {}
        self.seen = 0

    def offer(self, make):
        """Consider the next item; ``make()`` builds it only if kept."""
        slot = self.seen if self.seen < self.size else int(
            self.rng.integers(0, self.seen + 1))
        self.seen += 1
        if slot < self.size:
            self.items[slot] = make()
