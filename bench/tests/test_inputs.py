"""The benchmark's inputs are the ones ``repro.launch.train`` builds for
the same seed: the same draws, labels and partition exactly. The benchmark
builds them in one jitted call, where XLA fuses the float transforms of the
draws (scaling, sigmoid) that the CLI runs op by op, so some floats differ
in the last bits."""
import json
import os

import jax
import numpy as np
import pytest

import inputs
from repro.data.pipeline import FLDataSource
from repro.models.mlp import init_mlp

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def assert_params_close(got, want):
    assert sorted(got) == sorted(want)
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 5])
def test_paper_inputs_equal_the_cli_inputs(seed):
    cfg = config("blade_mlp_mnist_c20")
    got = inputs.build(cfg, seed)
    key = jax.random.key(seed)
    src = FLDataSource(key, 20, 512, 0.5, seed=seed)
    want = src.static_batch()
    np.testing.assert_array_equal(np.asarray(got["batch"]["y"]),
                                  np.asarray(want["y"]))
    np.testing.assert_allclose(np.asarray(got["batch"]["x"]),
                               np.asarray(want["x"]), rtol=1e-6, atol=1e-7)
    assert_params_close(got["params"], init_mlp(jax.random.fold_in(key, 1)))
    np.testing.assert_array_equal(
        jax.random.key_data(got["run_key"]),
        jax.random.key_data(jax.random.fold_in(key, 2)))


def test_cohort_params_equal_the_cli_params():
    got = inputs.build(config("blade_mlp_cohort_10k"), 7)
    assert_params_close(got["params"],
                        init_mlp(jax.random.fold_in(jax.random.key(7), 1)))


def test_seeds_past_32_bits_stay_distinct():
    a = jax.random.key_data(inputs.seed_key(5))
    b = jax.random.key_data(inputs.seed_key(2 ** 32 + 5))
    assert not np.array_equal(a, b)
    with pytest.raises(ValueError):
        inputs.seed_key(-1)
