"""The main path's Pallas kernels compile natively for a TPU v5e.

Interpret mode runs a kernel body on the CPU and accepts layouts the chip's
compiler (Mosaic) refuses. These tests compile each kernel of the engine's
chip path, and the whole single-chip scan with the kernels on, for a v5e
that is described and not attached: nothing runs, so they check lowering,
layout and fit, not results or times (tests/test_kernels.py checks results
in interpret mode). They skip where no v5e topology can be described.

The topology is described inside a module fixture, never at import: only one
process may load the TPU library at a time, so every test of this kind stays
in this one file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import rounds
from repro.kernels.fedavg.kernel import (digest_div_flat, fedavg_flat,
                                         mix_rows_flat)
from repro.kernels.pow_hash.kernel import pow_race_kernel
from repro.models.mlp import init_mlp, mlp_loss

# the paper's §7.1 width: MLP 784-256-10, N=20 clients x 512 samples,
# t_sum=100 and beta=10 give tau=10 and 10240 PoW attempts, K=5
C, SAMPLES, TAU, ATTEMPTS, K = 20, 512, 10, 10240, 5
W1 = 784 * 256                     # widest leaf, flattened


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("attempts,chunk,clients", [
    (ATTEMPTS, 1024, C),           # the engine's budget and chunk
    (3000, 1024, C),               # budget that does not divide the chunk
    (ATTEMPTS, 1000, 5),           # 4-way shard's client block, odd chunk
])
def test_pow_race_kernel_compiles(one_chip, attempts, chunk, clients):
    u32 = jnp.uint32
    compiled = _compile(
        lambda ph, p, off: pow_race_kernel(ph, p, off, attempts, block=chunk,
                                           interpret=False),
        _sds(one_chip, (), u32), _sds(one_chip, (clients,), u32),
        _sds(one_chip, (), u32))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", [C, C // 4],
                         ids=["whole_row_block", "4way_shard_rows"])
def test_mix_rows_flat_compiles(one_chip, rows):
    compiled = _compile(lambda w, x: mix_rows_flat(w, x, interpret=False),
                        _sds(one_chip, (rows, C)), _sds(one_chip, (C, W1)))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("width", [W1, 10])
def test_digest_div_flat_compiles(one_chip, width):
    compiled = _compile(lambda x: digest_div_flat(x, interpret=False),
                        _sds(one_chip, (C, width)))
    assert "tpu_custom_call" in compiled.as_text()


def test_fedavg_flat_compiles(one_chip):
    compiled = _compile(lambda x, w: fedavg_flat(x, w, interpret=False),
                        _sds(one_chip, (C, W1)), _sds(one_chip, (C,)))
    assert "tpu_custom_call" in compiled.as_text()


def test_kernel_scan_compiles_for_one_chip(one_chip):
    """The whole K-round scan at paper width with both kernel tiers on."""
    spec = rounds.RoundSpec(n_clients=C, tau=TAU, eta=0.05,
                            mine_attempts=ATTEMPTS, difficulty_bits=4,
                            use_kernel=True, fused_mix=True,
                            kernel_interpret=False)
    state = jax.eval_shape(
        lambda: rounds.init_state(init_mlp(jax.random.key(0)),
                                  jax.random.key(1), C))
    state = jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype), state)
    batch = {"x": _sds(one_chip, (C, SAMPLES, 784)),
             "y": _sds(one_chip, (C, SAMPLES), jnp.int32)}
    runner = rounds._scan_runner(mlp_loss, spec, K, False)
    text = runner.lower(state, batch).compile().as_text()
    assert "tpu_custom_call" in text
