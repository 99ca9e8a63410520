"""Run ``bench/run.py`` on the CPU for the benchmark's tests: the look
for a TPU is replaced by JAX's CPU devices, and ``--fault <name>`` breaks
the timed path underneath the harness first.

    JAX_PLATFORMS=cpu python3 bench/tests/cpu_run.py [--fault F] <run.py args>
"""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import run  # noqa: E402


def state_unchanged():
    """Every round hands back the parameters it was given."""
    from repro.core import rounds
    make = rounds.make_finalize

    def make_finalize(*args, **kwargs):
        finalize = make(*args, **kwargs)
        return lambda state, params, *rest: finalize(state, state.params,
                                                     *rest)
    rounds.make_finalize = make_finalize


def half_batch():
    """Each client's loss, and so its gradient, sees only the first half
    of its samples: the mean is taken over the rest."""
    from repro.models import mlp
    loss = mlp.mlp_loss

    def half(params, batch):
        m = batch["y"].shape[0] // 2
        return loss(params, {k: v[:m] for k, v in batch.items()})
    mlp.mlp_loss = half


def no_exchange():
    """The full-mesh mix skips the exchange between chips: each chip
    averages only its own clients."""
    from repro.core import aggregation
    mix = aggregation.mix_all_reduce

    def local(params, weights=None, *, axis_name=None, n_shards=1,
              full=None):
        return mix(params, weights)
    aggregation.mix_all_reduce = local


def altered_winner():
    """The PoW race names the next client after the true winner."""
    from repro.core import mining
    winner = mining.winner_of
    mining.winner_of = lambda h: (winner(h) + 1) % h.shape[0]


def altered_cohort():
    """Each round's cohort is shifted by one enrolled client."""
    from repro.core import topology
    cohort_at = topology.CohortSchedule.cohort_at

    def shifted(self, k_topo):
        return jnp.sort((cohort_at(self, k_topo) + 1) % self.n_enrolled)
    topology.CohortSchedule.cohort_at = shifted


FAULTS = {f.__name__: f for f in (state_unchanged, half_batch, no_exchange,
                                  altered_winner, altered_cohort)}


def main(argv):
    if argv[:1] == ["--fault"]:
        FAULTS[argv[1]]()
        argv = argv[2:]
    run.require_devices = lambda chips: jax.devices()[:chips]
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
