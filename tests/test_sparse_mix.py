"""The sparse segment-mix path (``aggregation.mix_segment`` + the
``SparseLowering``/``ExplicitSparse`` surface) under the repo's two
equivalence tiers.

What is pinned where (docs/architecture.md §Sparse lowering):

  * **tolerance** — sparse-vs-dense agreement: ``mix_segment`` computes the
    same row-stochastic mix as the dense ``[C, C]`` matmul but associates
    fp32 differently (scatter-add vs row contraction), so they agree to
    ``assert_trees_close`` rtol, never bitwise. Property-tested over random
    graphs including padding rows and degree-1 isolates (hypothesis when
    installed, a seeded grid otherwise — same generators either way).
  * **bitwise** — the claims that ARE exact: ``segment_sum`` equals an
    explicit fp32 accumulation over the edge list in ascending edge order;
    degree-1 rows equal the dense matmul row exactly (one nonzero term, and
    adding the zero products of a dense row changes nothing); eager equals
    jit; and the sharded ``mix_segment`` equals the single-device one
    (per-row reductions are shard-local, nothing reassociates).

Plus the dispatch seam: ``rounds.segment_lowering`` / ``RoundSpec.
sparse_mix`` (auto degree threshold, forced-sparse errors, forced-dense),
and the ``ExplicitSparse`` topology running the real engine.
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
from repro.models.mlp import init_mlp, mlp_loss

from equivalence import assert_trees_close

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

needs4 = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs 4 host devices (CI cohort lane sets "
           "XLA_FLAGS=--xla_force_host_platform_device_count=4)")

# rtol of the tolerance tier's sparse-vs-dense claim: both sides sum the
# same <= C fp32 terms per row, just in different orders
RTOL, ATOL = 2e-6, 1e-7


def _rand_sparse(seed: int, c: int, dmax: int,
                 isolate_rows=()) -> topology.SparseLowering:
    """Random row-stochastic sparse lowering with real padding: every row
    draws its own degree in [1, dmax] (rows beyond their degree carry
    weight-0 self-edges), and ``isolate_rows`` are forced to degree-1
    self-loops with weight 1."""
    rng = np.random.default_rng(seed)
    idx = np.empty((c, dmax), np.int32)
    w = np.zeros((c, dmax), np.float32)
    for i in range(c):
        if i in isolate_rows:
            deg = 1
            cols = np.array([i])
        else:
            deg = int(rng.integers(1, dmax + 1))
            cols = np.sort(rng.choice(c, size=deg, replace=False))
        raw = rng.uniform(0.1, 1.0, deg)
        idx[i, :deg] = cols
        idx[i, deg:] = i                       # padding: self-edges
        w[i, :deg] = (raw / raw.sum()).astype(np.float32)
    return topology.SparseLowering(idx, w)


def _rand_params(seed: int, c: int):
    k1, k2 = jax.random.split(jax.random.key(seed))
    return {"w": jax.random.normal(k1, (c, 5, 3)),
            "b": jax.random.normal(k2, (c, 3))}


def _dense_mix(params, w):
    w = jnp.asarray(w, jnp.float32)
    return jax.tree.map(
        lambda x: jnp.tensordot(w, x, axes=([1], [0])).astype(x.dtype),
        params)


# ---------------------------------------------------------------------------
# Property tests: sparse vs dense (tolerance tier)
# ---------------------------------------------------------------------------

_GRID = [(seed, c, dmax)
         for seed in range(6)
         for c, dmax in ((2, 1), (3, 3), (7, 2), (12, 5), (17, 17))]


def _check_matches_dense(seed, c, dmax):
    sp = _rand_sparse(seed, c, dmax, isolate_rows={0, c - 1})
    params = _rand_params(seed, c)
    got = aggregation.mix_segment(params, jnp.asarray(sp.neighbor_idx),
                                  jnp.asarray(sp.edge_w))
    want = _dense_mix(params, sp.to_dense())
    assert_trees_close(got, want, rtol=RTOL, atol=ATOL)
    # degree-1 isolates are BITWISE equal to the dense matmul row: one
    # nonzero term, and the dense row's zero products add nothing
    for leaf_g, leaf_w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(leaf_g[0]),
                                      np.asarray(leaf_w[0]))
        np.testing.assert_array_equal(np.asarray(leaf_g[-1]),
                                      np.asarray(leaf_w[-1]))


@pytest.mark.parametrize("seed,c,dmax", _GRID)
def test_mix_segment_matches_dense_grid(seed, c, dmax):
    _check_matches_dense(seed, c, dmax)


if HAVE_HYPOTHESIS:
    @settings(max_examples=40, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), c=st.integers(2, 24),
           frac=st.floats(0.05, 1.0))
    def test_mix_segment_matches_dense_hypothesis(seed, c, frac):
        _check_matches_dense(seed, c, max(1, int(frac * c)))


def test_segment_sum_is_ordered_edge_accumulation_bitwise():
    """The bitwise contract the sparse path's determinism rests on: the
    ``segment_sum`` over the flattened edge list equals an explicit fp32
    accumulation over the SAME edges in ascending flattened order. (This is
    why sparse runs are reproducible: re-running the same lowering re-adds
    the same terms in the same order.)"""
    for seed, c, dmax in ((0, 9, 4), (1, 16, 7), (2, 5, 5)):
        sp = _rand_sparse(seed, c, dmax)
        x = np.asarray(jax.random.normal(jax.random.key(seed), (c, 6)),
                       np.float32)
        got = np.asarray(aggregation.mix_segment(
            {"x": jnp.asarray(x)}, jnp.asarray(sp.neighbor_idx),
            jnp.asarray(sp.edge_w))["x"])
        want = np.zeros((c, 6), np.float32)
        for i in range(c):
            for d in range(dmax):           # ascending edge order per row
                want[i] = want[i] + \
                    sp.edge_w[i, d] * x[sp.neighbor_idx[i, d]]
        np.testing.assert_array_equal(got, want)


def test_mix_segment_eager_equals_jit_bitwise():
    sp = _rand_sparse(3, 10, 4)
    params = _rand_params(3, 10)
    idx, w = jnp.asarray(sp.neighbor_idx), jnp.asarray(sp.edge_w)
    eager = aggregation.mix_segment(params, idx, w)
    jitted = jax.jit(aggregation.mix_segment)(params, idx, w)
    for a, b in zip(jax.tree.leaves(eager), jax.tree.leaves(jitted)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_mix_segment_padding_rows_are_inert():
    """Weight-0 padding self-edges must contribute exactly nothing: a padded
    lowering and its depadded-then-repadded twin agree bitwise."""
    sp = _rand_sparse(4, 8, 3)
    params = _rand_params(4, 8)
    base = aggregation.mix_segment(params, jnp.asarray(sp.neighbor_idx),
                                   jnp.asarray(sp.edge_w))
    # re-point every zero-weight edge at a DIFFERENT row: 0 * other row
    # must still contribute exactly +0.0
    idx2 = np.where(sp.edge_w == 0.0,
                    (sp.neighbor_idx + 1) % 8, sp.neighbor_idx)
    repad = aggregation.mix_segment(params, jnp.asarray(idx2),
                                    jnp.asarray(sp.edge_w))
    for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(repad)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# SparseLowering / sparse_from_dense surface
# ---------------------------------------------------------------------------


def test_sparse_from_dense_round_trip_exact():
    w = np.asarray(topology.Ring(neighbors=2).matrix(11), np.float32)
    sp = topology.sparse_from_dense(w)
    np.testing.assert_array_equal(sp.to_dense().astype(np.float32), w)
    assert sp.max_degree == 5                 # 4 neighbors + self


def test_sparse_lowering_validation():
    with pytest.raises(ValueError):           # shape mismatch
        topology.SparseLowering(np.zeros((3, 2), np.int32),
                                np.zeros((3, 3), np.float32))
    with pytest.raises(ValueError):           # index out of range
        topology.SparseLowering(np.full((3, 1), 7, np.int32),
                                np.ones((3, 1), np.float32))
    with pytest.raises(ValueError):           # zero degree
        topology.SparseLowering(np.zeros((3, 0), np.int32),
                                np.zeros((3, 0), np.float32))


def test_to_dense_guard_refuses_population_scale():
    c = topology.DENSIFY_MAX_CLIENTS + 1
    sp = topology.SparseLowering(
        np.arange(c, dtype=np.int32)[:, None],
        np.ones((c, 1), np.float32))
    with pytest.raises(ValueError, match="refusing to densify"):
        sp.to_dense()
    # explicit opt-up still works
    assert sp.to_dense(max_clients=c).shape == (c, c)


def test_reweighted_renormalizes_rows():
    sp = _rand_sparse(5, 6, 3)
    weights = np.linspace(1.0, 2.0, 6, dtype=np.float32)
    rw = sp.reweighted(weights)
    np.testing.assert_allclose(np.asarray(rw.edge_w).sum(1),
                               np.ones(6), rtol=1e-6)
    # zero-weight padding stays zero
    assert np.all(np.asarray(rw.edge_w)[sp.edge_w == 0.0] == 0.0)


# ---------------------------------------------------------------------------
# ExplicitSparse topology + dispatch seam
# ---------------------------------------------------------------------------


def test_explicit_sparse_validation():
    with pytest.raises(ValueError):           # empty row
        topology.ExplicitSparse(neighbors=((0,), ()))
    with pytest.raises(ValueError):           # index out of range
        topology.ExplicitSparse(neighbors=((0, 5), (0, 1)))
    with pytest.raises(ValueError):           # weight shape mismatch
        topology.ExplicitSparse(neighbors=((0,), (1,)),
                                weights=((1.0, 1.0), (1.0,)))
    with pytest.raises(ValueError):           # negative weight
        topology.ExplicitSparse(neighbors=((0, 1), (0, 1)),
                                weights=((-1.0, 2.0), (1.0, 1.0)))


def test_explicit_sparse_advertises_segment_kind():
    topo = topology.ExplicitSparse(neighbors=topology.ring_neighbors(8, 1))
    assert topo.lowering(8).kind == topology.SEGMENT
    assert rounds.dispatch_plan(
        rounds.RoundSpec(n_clients=8, tau=1, eta=0.1, topology=topo),
        None, 2)["mix"] == "segment"


def test_ring_neighbors_matches_ring_matrix():
    topo = topology.ExplicitSparse(neighbors=topology.ring_neighbors(9, 2))
    np.testing.assert_allclose(np.asarray(topo.matrix(9)),
                               np.asarray(topology.Ring(neighbors=2).matrix(9)),
                               atol=1e-7)


def test_segment_lowering_auto_threshold():
    """Auto dispatch takes the sparse path only when the degree is well
    below C (max_degree * 8 <= C) — so every shipped small-C config keeps
    its dense bitwise mix."""
    def spec_at(c, n_active):
        return rounds.RoundSpec(
            n_clients=c, tau=1, eta=0.1,
            topology=topology.PartialParticipation(n_active=n_active))
    assert rounds.segment_lowering(spec_at(64, 4)) is not None   # 32 <= 64
    assert rounds.segment_lowering(spec_at(20, 4)) is None       # 32 > 20
    # forced off beats auto
    spec = rounds.RoundSpec(
        n_clients=64, tau=1, eta=0.1, sparse_mix=False,
        topology=topology.PartialParticipation(n_active=4))
    assert rounds.segment_lowering(spec) is None
    # never preempt the opt-in fast tiers
    spec = rounds.RoundSpec(
        n_clients=64, tau=1, eta=0.1, fast_allreduce=True,
        topology=topology.PartialParticipation(n_active=4))
    assert rounds.segment_lowering(spec) is None


def test_segment_lowering_forced_sparse_errors_when_unavailable():
    spec = rounds.RoundSpec(n_clients=8, tau=1, eta=0.1, sparse_mix=True,
                            topology=topology.RandomGraph(p_link=0.5))
    with pytest.raises(ValueError, match="sparse lowering"):
        rounds.segment_lowering(spec)


def test_forced_sparse_full_mesh_matches_dense_engine():
    """sparse_mix=True reroutes ANY static topology through mix_segment —
    full mesh included (degree C, no saving: the point is the seam, not the
    speed). Tolerance tier vs the same spec mixed densely."""
    c, k = 8, 3
    key = jax.random.key(0)
    params = init_mlp(jax.random.fold_in(key, 1), in_dim=12, hidden=6)
    batch = {"x": jax.random.normal(jax.random.fold_in(key, 3), (c, 4, 12)),
             "y": jax.random.randint(jax.random.fold_in(key, 4),
                                     (c, 4), 0, 10)}
    outs = {}
    for sparse in (True, False):
        spec = rounds.RoundSpec(n_clients=c, tau=2, eta=0.1,
                                mine_attempts=16, difficulty_bits=1,
                                sparse_mix=sparse,
                                topology=topology.FullMesh())
        outs[sparse] = rounds.run_blade_fl(
            mlp_loss, spec, params, batch, jax.random.fold_in(key, 2), k)
    st_s, hist_s, led_s = outs[True]
    st_d, hist_d, led_d = outs[False]
    assert_trees_close(st_s.params, st_d.params, rtol=1e-5, atol=1e-6)
    # digests are computed pre-mix from the broadcast set: round 1 agrees
    # BITWISE, later rounds may fork deterministically (mixed params feed
    # round 2's training)
    assert led_s.blocks[0].model_digest == led_d.blocks[0].model_digest
    assert led_s.validate_chain() and led_d.validate_chain()


def test_explicit_sparse_scan_vs_loop_bitwise():
    """The sparse mix inside the engine obeys the same scan==loop bitwise
    contract as every other lowering."""
    c, k = 6, 3
    key = jax.random.key(1)
    topo = topology.ExplicitSparse(neighbors=topology.ring_neighbors(c, 1))
    spec = rounds.RoundSpec(n_clients=c, tau=2, eta=0.1, mine_attempts=16,
                            difficulty_bits=1, topology=topo)
    params = init_mlp(jax.random.fold_in(key, 1), in_dim=12, hidden=6)
    batch = {"x": jax.random.normal(jax.random.fold_in(key, 3), (c, 40, 12)),
             "y": jax.random.randint(jax.random.fold_in(key, 4),
                                     (c, 40), 0, 10)}
    st_a, hist_a, led_a = rounds.run_blade_fl(
        mlp_loss, spec, params, batch, jax.random.fold_in(key, 2), k)
    st_b, hist_b, led_b = rounds.run_blade_fl(
        mlp_loss, spec, params, lambda i: batch,  # callable -> loop driver
        jax.random.fold_in(key, 2), k)
    for a, b in zip(jax.tree.leaves(st_a.params),
                    jax.tree.leaves(st_b.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert [b_.header_hash for b_ in led_a.blocks] == \
           [b_.header_hash for b_ in led_b.blocks]


# ---------------------------------------------------------------------------
# Sharded mix_segment (bitwise vs single device)
# ---------------------------------------------------------------------------


@needs4
def test_mix_segment_sharded_bitwise():
    """Per-row segment reductions are shard-local (each shard owns its row
    block and gathers the full broadcast set), so the sharded mix is
    bit-for-bit the single-device one — the BITWISE tier, unlike psum."""
    c = 8
    sp = _rand_sparse(7, c, 3)
    params = _rand_params(7, c)
    idx, w = jnp.asarray(sp.neighbor_idx), jnp.asarray(sp.edge_w)
    want = aggregation.mix_segment(params, idx, w)
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    fn = jax.shard_map(
        lambda p: aggregation.mix_segment(p, idx, w, axis_name="data",
                                          n_shards=4),
        mesh=mesh, in_specs=(P("data"),), out_specs=P("data"),
        check_vma=False)
    got = fn(params)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_sparse_suite_on_4_devices_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-k", "sharded",
         os.path.abspath(__file__)],
        capture_output=True, text=True, env=env, timeout=900,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
