"""The numbers that decide ``correct``: what the timed path produced
against what the plain reference computes for the same inputs.

Float layers (training, lazy perturbation, mix, losses) are compared by
relative gaps. Integer layers (PoW race, hash link, ledger, cohort
membership) are compared exactly: the block digest hashes the float
broadcast set, so a last-ulp difference between the program and the
reference would give another digest and fork every later hash. The race and
the ledger are therefore replayed from the digests the program published:
given a digest, the winner, its nonce, its hash, the device hash link and
the sha256 header chain are pure integer functions, and the reference
recomputes each exactly.
"""
from __future__ import annotations

import numpy as np

import reference

# a leaf whose reference change is under this share of the median leaf's
# change moves by round-off alone and is left out of ``param_gap``
STILL_LEAF = 1e-3


def param_gap(prog, ref, init):
    """|(prog - init) - (ref - init)| / |ref - init| over the whole model:
    how far the program's change of the parameters lies from the
    reference's, in units of the change. Taken over all leaves together:
    per leaf, the 2560 weights of the output layer swing by 10x from seed
    to seed as single ReLU gates flip under rounding."""
    d_ref = {n: np.linalg.norm(ref[n] - init[n]) for n in ref}
    med = float(np.median(list(d_ref.values())))
    moving = [n for n in ref if d_ref[n] >= STILL_LEAF * med]
    gap = sum(np.sum(((prog[n] - init[n]) - (ref[n] - init[n])) ** 2)
              for n in moving)
    return float(np.sqrt(gap / sum(d_ref[n] ** 2 for n in moving)))


def loss_gap(prog_hist, ref_hist):
    """Largest relative gap of a round's global loss (the mean over
    clients of the mixed model's loss on their data). The mean local loss
    of the last local step is not compared: under rounding it swings as
    much as bf16 storage moves it."""
    return float(max(abs(p["global_loss"] - r["global_loss"])
                     / abs(r["global_loss"])
                     for p, r in zip(prog_hist, ref_hist)))


class Chain:
    """Replays the race and the ledger of consecutive rounds from the
    program's published digests and counts every field that differs."""

    def __init__(self, n_clients, attempts):
        self.n_clients, self.attempts = n_clients, attempts
        self.head = reference.GENESIS
        self.race_mismatches = 0
        self.ledger_mismatches = 0
        self.blocks = 0

    def replay(self, blocks):
        """``blocks``: the program's ledger blocks of one engine call, as
        (index, prev_hash, digest, winner, nonce, pow_hash). The device
        hash link starts from genesis at every call, as the engine does."""
        link = reference.GENESIS
        for r, (index, prev, dig, winner, nonce, pow_hash) in enumerate(blocks):
            w, n, h, link = reference.race(link, dig, r, self.n_clients,
                                           self.attempts)
            self.race_mismatches += (w, n, h) != (winner, nonce, pow_hash)
            want = (self.blocks, self.head, dig, w, n, h)
            self.ledger_mismatches += want != (index, prev, dig, winner,
                                               nonce, pow_hash)
            self.head = reference.header_hash(*want)
            self.blocks += 1


def judged(numbers, limits):
    """``{name: {"value", "limit"}}`` for every number, in the order of
    ``limits``, and whether all are within their limits."""
    out = {name: {"value": numbers[name], "limit": limits[name]}
           for name in limits}
    ok = all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in out.values())
    return out, ok
