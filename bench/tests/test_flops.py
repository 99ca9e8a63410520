"""The FLOP count from shapes, against a hand count and XLA's own count
of one local step, and the peaks table."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

import flops
from families.mlp import mlp_step_flops
from repro.models.mlp import init_mlp, mlp_loss

PAPER = {"model": {"arch": "mlp", "in_dim": 784, "hidden": 256,
                   "n_classes": 10},
         "data": {"samples_per_client": 512},
         "round_spec": {"tau": 10, "n_clients": 20}}
CONFIGS = os.path.join(os.path.dirname(flops.__file__), "configs")
with open(os.path.join(CONFIGS, "blade_mlp_cohort_10k.json")) as f:
    COHORT = json.load(f)


@pytest.mark.parametrize("cfg,clients", [(PAPER, 20), (COHORT, 64)],
                         ids=["paper", "cohort"])
def test_hand_count(cfg, clients):
    # forward 2*(784*256 + 256*10); weight grads the same again; the
    # hidden-layer gradient 2*256*10; no gradient into the input
    per_sample = 2 * 203264 + 2 * 203264 + 2 * 2560
    assert mlp_step_flops(784, 256, 10) == per_sample == 818176
    assert flops.round_flops(cfg) == per_sample * 512 * 10 * clients


def test_matches_xla_cost_of_one_local_step():
    """XLA:CPU's count of one client's full-batch step (loss, gradient,
    update) exceeds the matmul count only by the elementwise and loss
    terms that the count leaves out: well under 2%."""
    params = init_mlp(jax.random.key(0))
    batch = {"x": jnp.zeros((512, 784), jnp.float32),
             "y": jnp.zeros((512,), jnp.int32)}

    def step(p, b):
        (loss, _), g = jax.value_and_grad(mlp_loss, has_aux=True)(p, b)
        return jax.tree.map(lambda w, d: w - 0.05 * d, p, g), loss

    cost = jax.jit(step).lower(params, batch).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    hand = mlp_step_flops(784, 256, 10) * 512
    assert hand <= cost["flops"] <= 1.02 * hand


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        flops.peak("TPU v99 imaginary")


def test_v5e_peak():
    assert flops.peak("TPU v5 lite") == 197e12
