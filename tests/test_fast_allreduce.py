"""The opt-in psum fast path (``RoundSpec.fast_allreduce``) under the
tolerance equivalence tier.

Three layers of coverage:

  * harness unit tests — ``tests/equivalence.py`` itself (ULP mapping,
    pass/fail behavior) plus the ``PSUM`` lowering dispatch;
  * single-device tolerance suites — fast-vs-default engines share one
    device, so they exercise the reassociated *math* without collectives;
  * 4-device tolerance suites — psum-vs-gather over full K≥10-round
    sharded runs, params/metrics within rtol=1e-5, plus the explicit test
    that the ledger hashes FORK under the flag (expected behavior: both
    chains self-validate, they just aren't the same chain).

The 4-device cases skip without devices; the CI multidevice lane runs them
under ``XLA_FLAGS=--xla_force_host_platform_device_count=4``, and the slow
subprocess test at the bottom gives the default single-device tier-1 run
the same coverage.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import aggregation, rounds, topology
from repro.data.pipeline import FLDataSource
from repro.models.mlp import init_mlp, mlp_loss

from equivalence import (assert_trees_close, assert_leaves_close, tree_max_ulp,
                         ulp_diff)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

needs4 = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs 4 host devices (CI multidevice lane sets "
           "XLA_FLAGS=--xla_force_host_platform_device_count=4)")


def _params(key, c=8):
    k1, k2 = jax.random.split(key)
    return {"w1": jax.random.normal(k1, (c, 6, 5)),
            "b": jax.random.normal(k2, (c, 5))}


# ---------------------------------------------------------------------------
# The harness itself
# ---------------------------------------------------------------------------


def test_ulp_diff_counts_representable_steps():
    x = np.float32(1.0)
    up = np.nextafter(x, np.float32(2.0), dtype=np.float32)
    assert ulp_diff(np.array([x]), np.array([x]))[0] == 0
    assert ulp_diff(np.array([up]), np.array([x]))[0] == 1
    # the mapping crosses zero without a discontinuity: -0.0 == +0.0
    assert ulp_diff(np.array([-0.0], np.float32),
                    np.array([0.0], np.float32))[0] == 0
    tiny = np.nextafter(np.float32(0.0), np.float32(-1.0), dtype=np.float32)
    assert ulp_diff(np.array([tiny]), np.array([0.0], np.float32))[0] == 1


def test_ulp_diff_float64_opposite_extremes_saturate_not_wrap():
    """Regression: float64 ordered ints span the full int64 range, so the
    distance between opposite-sign extremes overflows the int64 subtraction
    — it must saturate to int64 max, never wrap to a small value that would
    let assert_trees_close(ulp=...) accept maximally distant bit patterns."""
    neg = np.array([np.uint64(0xFFFFFFFFFFFFFFFF)]).view(np.float64)
    pos = np.array([np.uint64(0x7FFFFFFFFFFFFFFF)]).view(np.float64)
    assert ulp_diff(neg, pos)[0] == np.iinfo(np.int64).max
    with pytest.raises(AssertionError):
        assert_leaves_close(neg, pos, ulp=1 << 40)
    # large-but-representable distances still compute exactly
    assert ulp_diff(np.array([-1.0]), np.array([1.0]))[0] == \
        int(ulp_diff(np.array([-1.0]), np.array([0.0]))[0]) * 2


def test_ulp_diff_rejects_mixed_dtypes():
    with pytest.raises(TypeError):
        ulp_diff(np.zeros(2, np.float32), np.zeros(2, np.float64))
    with pytest.raises(TypeError):
        ulp_diff(np.zeros(2, np.int32), np.zeros(2, np.int32))


def test_assert_trees_close_tiers():
    a = {"w": jnp.ones((3,), jnp.float32)}
    b = {"w": jnp.asarray(np.nextafter(np.ones(3, np.float32),
                                       np.float32(2.0)))}
    assert_trees_close(a, a, ulp=0)                    # bitwise degenerate
    assert_trees_close(a, b, ulp=1)                    # one-ulp drift OK
    with pytest.raises(AssertionError):
        assert_trees_close(a, b, ulp=0)                # ...but not bitwise
    assert_trees_close(a, b, rtol=1e-6)                # rtol tier
    with pytest.raises(AssertionError):
        assert_trees_close(a, {"w": jnp.full((3,), 1.1)}, rtol=1e-3)
    with pytest.raises(AssertionError):                # structure mismatch
        assert_trees_close(a, {"v": a["w"]})
    assert tree_max_ulp(a, b) == 1


def test_assert_leaves_close_nan_semantics():
    nan = np.array([np.nan, 1.0], np.float32)
    assert_leaves_close(nan, nan, rtol=1e-6)           # NaN matches NaN
    with pytest.raises(AssertionError):
        assert_leaves_close(nan, np.array([1.0, 1.0], np.float32), rtol=1e-6)


# ---------------------------------------------------------------------------
# PSUM lowering dispatch
# ---------------------------------------------------------------------------


def test_psum_lowering_is_opt_in():
    assert topology.FullMesh().lowering(8).kind == topology.ALL_REDUCE
    assert topology.FullMesh().lowering(
        8, fast_allreduce=True).kind == topology.PSUM
    # stochastic / non-uniform-row matrices keep the gather kind (the engine
    # routes them through mix_psum_dense under the flag instead)
    assert topology.RandomGraph(0.5).lowering(
        8, fast_allreduce=True).kind == topology.GATHER
    assert topology.PartialParticipation(3).lowering(
        8, fast_allreduce=True).kind == topology.GATHER
    assert topology.LinkQualitySchedule().lowering(
        8, fast_allreduce=True).kind == topology.GATHER
    # permute lowerings are already O(window) + bitwise: flag is a no-op
    assert topology.Ring(neighbors=1).lowering(
        8, fast_allreduce=True).kind == topology.NEIGHBOR_PERMUTE
    assert topology.GossipRotation().lowering(
        8, fast_allreduce=True).kind == topology.NEIGHBOR_PERMUTE


def test_uniform_row_detection():
    row = topology.FullMesh().uniform_row(4)
    np.testing.assert_allclose(row, np.full(4, 0.25), atol=0)
    assert topology.Ring(neighbors=1).uniform_row(8) is None
    assert topology.RandomGraph(0.5).uniform_row(8) is None
    assert topology.Topology().uniform_row(8) is None  # abstract matrix


class _UniformRows(topology.Topology):
    """Non-mesh rank-1 topology: every client adopts the same non-uniformly
    weighted average (W = 1 rᵀ)."""

    def matrix(self, n_clients, *, key=None, round_idx=None):
        r = np.linspace(1.0, 2.0, n_clients).astype(np.float32)
        r /= r.sum()
        return jnp.asarray(np.tile(r, (n_clients, 1)))


def test_custom_uniform_row_topology_advertises_psum():
    topo = _UniformRows()
    assert topo.lowering(6).kind == topology.GATHER
    low = topo.lowering(6, fast_allreduce=True)
    assert low.kind == topology.PSUM
    row = topo.uniform_row(6)
    np.testing.assert_allclose(row.sum(), 1.0, rtol=1e-6)


# ---------------------------------------------------------------------------
# mix_psum / mix_psum_dense vs their gathered twins (tolerance tier)
# ---------------------------------------------------------------------------


def _one_device_mesh():
    return Mesh(np.array(jax.devices()[:1]), ("data",))


@pytest.mark.tolerance
def test_mix_psum_dense_mode_close_to_fedavg():
    p = _params(jax.random.key(0))
    got = aggregation.mix_psum(p)
    assert_trees_close(got, aggregation.fedavg(p), rtol=1e-6, atol=1e-7)
    w = jnp.arange(1.0, 9.0)
    got_w = aggregation.mix_psum(p, w)
    assert_trees_close(got_w, aggregation.fedavg(p, w), rtol=1e-6, atol=1e-7)


@pytest.mark.tolerance
def test_mix_psum_dense_variant_unsharded_is_mix():
    p = _params(jax.random.key(1))
    w = topology.RandomGraph(0.5).matrix(8, key=jax.random.key(3))
    got = aggregation.mix_psum_dense(p, w)
    assert_trees_close(got, aggregation.mix(p, w), ulp=0)  # delegates to mix


@pytest.mark.tolerance
def test_mix_psum_sharded_close_to_all_reduce():
    p = _params(jax.random.key(2))
    mesh = _one_device_mesh()
    got = jax.jit(jax.shard_map(
        lambda q: aggregation.mix_psum(q, axis_name="data", n_shards=1),
        mesh=mesh, in_specs=P("data"), out_specs=P("data"),
        check_vma=False))(p)
    assert_trees_close(got, aggregation.mix_all_reduce(p), rtol=1e-6,
                       atol=1e-7)


@pytest.mark.tolerance
def test_mix_psum_dense_sharded_close_to_mix_gather():
    p = _params(jax.random.key(4))
    w = topology.LinkQualitySchedule(fading_period=2).matrix(
        8, round_idx=jnp.int32(1))
    weights = jnp.arange(1.0, 9.0)
    got = jax.jit(jax.shard_map(
        lambda q: aggregation.mix_psum_dense(q, w, weights, axis_name="data",
                                             n_shards=1),
        mesh=_one_device_mesh(), in_specs=P("data"), out_specs=P("data"),
        check_vma=False))(p)
    assert_trees_close(got, aggregation.mix(p, w, weights), rtol=1e-6,
                       atol=1e-7)


@pytest.mark.tolerance
def test_client_divergence_psum_matches_gathered():
    p = _params(jax.random.key(5))
    got = aggregation.client_divergence_psum(p)
    want = aggregation.client_divergence(p)
    assert_leaves_close(got, want, rtol=1e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# End-to-end K-round runs, single device: fast flag vs default
# ---------------------------------------------------------------------------


def _run_pair(topo, extra, *, mesh=None, c=8, k=10, seed=0):
    key = jax.random.key(seed)
    src = FLDataSource(key, c, samples_per_client=16, seed=seed)
    params = init_mlp(jax.random.fold_in(key, 1))
    batch = src.static_batch()
    rk = jax.random.fold_in(key, 2)
    out = []
    for fast in (False, True):
        spec = rounds.RoundSpec(n_clients=c, tau=2, eta=0.1, mine_attempts=32,
                                difficulty_bits=2, topology=topo,
                                fast_allreduce=fast, **extra)
        out.append(rounds.run_blade_fl_scan(mlp_loss, spec, params, batch,
                                            rk, k, mesh=mesh))
    return out


_DENSE_CASES = [
    ("full_mesh", topology.FullMesh(), {}),
    ("full_mesh_weighted", topology.FullMesh(),
     dict(data_weights=tuple(float(i + 1) for i in range(8)))),
    ("full_mesh_lazy_dp", topology.FullMesh(),
     dict(n_lazy=1, sigma2=0.02, dp_sigma=0.01)),
    ("random_graph", topology.RandomGraph(p_link=0.6), {}),
    ("partial", topology.PartialParticipation(n_active=3), {}),
    ("snr_schedule", topology.LinkQualitySchedule(fading_period=3), {}),
    ("alt_schedule_stochastic", topology.AlternatingSchedule(
        ((topology.RandomGraph(p_link=0.6), 1), (topology.FullMesh(), 1))),
     {}),
]


def _metric_histories_close(h_ref, h_fast):
    """Loss-path metrics must agree to tolerance; mining metrics (winner /
    nonce / pow_hash / digest) legitimately differ because the digest bits
    fork, so they are excluded by construction."""
    for ref, fast in zip(h_ref, h_fast):
        for name in ("local_loss_mean", "divergence", "global_loss"):
            if name in ref:
                assert_leaves_close(
                    np.float32(fast[name]), np.float32(ref[name]),
                    rtol=1e-4, atol=1e-5, err_msg=name)


@pytest.mark.tolerance
@pytest.mark.parametrize("name,topo,extra", _DENSE_CASES,
                         ids=[c[0] for c in _DENSE_CASES])
def test_fast_allreduce_single_device_tolerance(name, topo, extra):
    (st_ref, h_ref, l_ref), (st_fast, h_fast, l_fast) = _run_pair(topo, extra)
    assert_trees_close(st_fast.params, st_ref.params, rtol=1e-5, atol=1e-6)
    _metric_histories_close(h_ref, h_fast)
    assert l_ref.validate_chain() and l_fast.validate_chain()


# ---------------------------------------------------------------------------
# End-to-end K-round runs, 4 devices: psum vs gather (the real fast path)
# ---------------------------------------------------------------------------


def _mesh4():
    return Mesh(np.array(jax.devices()[:4]).reshape(4), ("data",))


@needs4
@pytest.mark.tolerance
@pytest.mark.parametrize("name,topo,extra", _DENSE_CASES,
                         ids=[c[0] for c in _DENSE_CASES])
def test_fast_allreduce_4device_psum_vs_gather(name, topo, extra):
    """Acceptance pin: with the flag on, psum-vs-gather end-of-run params
    agree within rtol=1e-5 over K=10 rounds on 4 fake devices, loss-path
    metrics track, and both engines produce self-validating chains."""
    (st_g, h_g, l_g), (st_p, h_p, l_p) = _run_pair(topo, extra,
                                                   mesh=_mesh4())
    assert_trees_close(st_p.params, st_g.params, rtol=1e-5, atol=1e-6)
    _metric_histories_close(h_g, h_p)
    assert l_g.validate_chain() and l_p.validate_chain()
    assert len(l_p.blocks) == 10


@needs4
@pytest.mark.tolerance
def test_fast_allreduce_default_off_stays_bitwise_sharded():
    """fast_allreduce=False sharded remains bit-for-bit the single-device
    scan — the flag's default must not perturb the bitwise contract."""
    topo = topology.FullMesh()
    key = jax.random.key(7)
    src = FLDataSource(key, 8, samples_per_client=16, seed=7)
    params = init_mlp(jax.random.fold_in(key, 1))
    batch = src.static_batch()
    rk = jax.random.fold_in(key, 2)
    spec = rounds.RoundSpec(n_clients=8, tau=2, eta=0.1, mine_attempts=32,
                            difficulty_bits=2, topology=topo)
    st1, h1, l1 = rounds.run_blade_fl_scan(mlp_loss, spec, params, batch,
                                           rk, 5)
    st2, h2, l2 = rounds.run_blade_fl_scan(mlp_loss, spec, params, batch,
                                           rk, 5, mesh=_mesh4())
    assert_trees_close(st2.params, st1.params, ulp=0)
    assert [b.header_hash for b in l1.blocks] == \
        [b.header_hash for b in l2.blocks]


@needs4
@pytest.mark.tolerance
def test_fast_allreduce_hash_fork_is_expected_behavior():
    """The documented trade of the fast flag: the psum'd digest reassociates
    fp32, so the sharded fast engine's hash chain FORKS from the bitwise
    engine's — from the very first block (the round-1 digest is already
    psum'd) — while each chain stays internally valid. Reproducibility of
    the ledger under the flag means re-running the SAME engine config, not
    cross-checking against the bitwise chain."""
    (st_g, h_g, l_g), (st_p, h_p, l_p) = _run_pair(
        topology.FullMesh(), {}, mesh=_mesh4())
    assert l_g.validate_chain() and l_p.validate_chain()
    heads_g = [b.header_hash for b in l_g.blocks]
    heads_p = [b.header_hash for b in l_p.blocks]
    assert heads_g != heads_p                      # the fork
    assert heads_g[0] != heads_p[0]                # already at block 0
    # ...and the fork is deterministic: the fast engine re-run reproduces
    # its own chain exactly.
    (_, _, _), (_, _, l_p2) = _run_pair(topology.FullMesh(), {},
                                        mesh=_mesh4())
    assert heads_p == [b.header_hash for b in l_p2.blocks]


# ---------------------------------------------------------------------------
# Tier-1 coverage for single-device default runs: the whole tolerance suite
# under 4 fake devices, in a subprocess (XLA_FLAGS must precede jax import)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_tolerance_suite_on_4_devices_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-m", "tolerance",
         os.path.abspath(__file__)],
        capture_output=True, text=True, env=env, timeout=900,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
