"""Hypothesis property tests on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis", reason="optional dev dep (requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.core import aggregation, allocation, bounds, chain, lazy, mining

SETTINGS = dict(max_examples=30, deadline=None)


# ---------------------------------------------------------------------------
# Resource allocation (eq. 3)
# ---------------------------------------------------------------------------


@settings(**SETTINGS)
@given(t_sum=st.floats(10, 1000), k=st.integers(1, 50),
       alpha=st.floats(0.1, 10), beta=st.floats(0.1, 20))
def test_allocation_never_overspends(t_sum, k, alpha, beta):
    tau = allocation.tau_from_budget(t_sum, k, alpha, beta)
    assert tau >= 0
    if tau >= 1:
        assert k * (tau * alpha + beta) <= t_sum + 1e-6


@settings(**SETTINGS)
@given(t_sum=st.floats(20, 500), alpha=st.floats(0.1, 5), beta=st.floats(0.1, 10))
def test_tau_monotone_decreasing_in_k(t_sum, alpha, beta):
    taus = [allocation.tau_from_budget(t_sum, k, alpha, beta)
            for k in range(1, 20)]
    assert all(a >= b for a, b in zip(taus, taus[1:]))


# ---------------------------------------------------------------------------
# Bounds (Theorems 1-4)
# ---------------------------------------------------------------------------


@settings(**SETTINGS)
@given(eta=st.floats(0.001, 0.05), L=st.floats(1.0, 15.0),
       delta=st.floats(0.05, 2.0), beta=st.floats(1.0, 20.0))
def test_lazy_bound_dominates_clean(eta, L, delta, beta):
    p = bounds.BoundParams(eta=eta, L=L, xi=1.0, delta=delta, alpha=1.0,
                           beta=beta, t_sum=200.0)
    for k in (1, 3, 5):
        if bounds.gamma(p, k) / k < 1:
            continue
        assert bounds.loss_bound(p, k, M=5, N=20, theta=0.3, sigma2=0.2) >= \
            bounds.loss_bound(p, k)


@settings(**SETTINGS)
@given(eta=st.floats(0.001, 0.02), beta=st.floats(1.0, 15.0))
def test_kstar_closed_form_positive_and_feasible_scale(eta, beta):
    p = bounds.BoundParams(eta=eta, L=8.0, xi=1.0, delta=0.5, alpha=1.0,
                           beta=beta, t_sum=300.0)
    k = bounds.k_star_closed_form(p)
    assert 0 < k < p.t_sum / beta  # mining alone must fit the budget


# ---------------------------------------------------------------------------
# Aggregation
# ---------------------------------------------------------------------------


@settings(**SETTINGS)
@given(c=st.integers(2, 8), n=st.integers(1, 40), seed=st.integers(0, 10_000),
       a=st.floats(-3, 3), b=st.floats(-3, 3))
def test_fedavg_linearity(c, n, seed, a, b):
    x = jax.random.normal(jax.random.key(seed), (c, n))
    lhs = aggregation.fedavg({"w": a * x + b})["w"]
    rhs = a * aggregation.fedavg({"w": x})["w"] + b
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs),
                               atol=1e-4, rtol=1e-4)


@settings(**SETTINGS)
@given(c=st.integers(2, 8), n=st.integers(1, 40), seed=st.integers(0, 10_000))
def test_fedavg_preserves_mean(c, n, seed):
    x = jax.random.normal(jax.random.key(seed), (c, n))
    out = aggregation.fedavg({"w": x})["w"]
    np.testing.assert_allclose(np.asarray(out.mean(0)),
                               np.asarray(x.mean(0)), atol=1e-5)


# ---------------------------------------------------------------------------
# Lazy clients
# ---------------------------------------------------------------------------


@settings(**SETTINGS)
@given(n=st.integers(2, 32), data=st.data())
def test_plagiarism_sources_always_honest(n, data):
    m = data.draw(st.integers(0, n - 1))
    src = lazy.plagiarism_sources(n, m)
    assert all(src[i] >= m for i in range(m))
    assert all(src[i] == i for i in range(m, n))


@settings(**SETTINGS)
@given(n=st.integers(2, 8), seed=st.integers(0, 1000), data=st.data())
def test_lazy_preserves_honest_clients(n, seed, data):
    m = data.draw(st.integers(1, n - 1))
    x = jax.random.normal(jax.random.key(seed), (n, 12))
    out = lazy.apply_lazy({"w": x}, jax.random.key(seed + 1), n, m, 0.01)["w"]
    np.testing.assert_array_equal(np.asarray(out[m:]), np.asarray(x[m:]))


# ---------------------------------------------------------------------------
# Mining / chain
# ---------------------------------------------------------------------------


@settings(**SETTINGS)
@given(a=st.integers(0, 2**32 - 1), b=st.integers(0, 2**32 - 1))
def test_mix_hash_bit_sensitivity(a, b):
    h1 = int(mining.mix_hash(jnp.uint32(a), jnp.uint32(b), jnp.uint32(0)))
    h2 = int(mining.mix_hash(jnp.uint32(a ^ 1), jnp.uint32(b), jnp.uint32(0)))
    assert h1 != h2


@settings(**SETTINGS)
@given(digests=st.lists(st.integers(0, 2**32 - 1), min_size=1, max_size=8))
def test_chain_roundtrip_and_tamper(digests):
    led = chain.Ledger()
    for i, d in enumerate(digests):
        led.append(chain.make_block(i, led.head_hash, d, 0, i, i))
    assert led.validate_chain()
    if len(digests) > 1:
        bad = led.tampered_copy(0, model_digest=digests[0] ^ 0xFFFF)
        assert not bad.validate_chain()


# ---------------------------------------------------------------------------
# Topology mixing (Steps 2+5 generalized)
# ---------------------------------------------------------------------------


@settings(**SETTINGS)
@given(c=st.integers(2, 10), seed=st.integers(0, 1000),
       n_leaves=st.integers(1, 3), weighted=st.booleans())
def test_mix_full_mesh_equals_fedavg_on_random_pytrees(c, seed, n_leaves,
                                                       weighted):
    """aggregation.mix with the full-mesh W reproduces fedavg on arbitrary
    random pytrees, with and without |D_i| weights."""
    from repro.core import topology

    key = jax.random.key(seed)
    keys = jax.random.split(key, n_leaves + 1)
    shapes = [(c, 3), (c, 2, 4), (c, 5, 1, 2)]
    p = {f"l{i}": jax.random.normal(keys[i], shapes[i % 3])
         for i in range(n_leaves)}
    w = jnp.abs(jax.random.normal(keys[-1], (c,))) + 0.1 if weighted else None
    got = aggregation.mix(p, topology.FullMesh().matrix(c), weights=w)
    want = aggregation.fedavg(p, weights=w)
    for k in p:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(c=st.sampled_from([2, 4, 6, 8]), shift=st.integers(0, 25),
       seed=st.integers(0, 1000))
def test_shift_halo_rolls_and_dense_mix_agree(c, shift, seed):
    """For ANY static shift s (wrapping included: s >= C) and client count,
    the three PairShift mix forms agree: the sharded block-ppermute halo
    (`mix_shift_halo` under shard_map) is BITWISE the dense roll form
    (`mix_rolls`), and both match the dense matrix mix (`aggregation.mix`
    with PairShift(s).matrix) to float tolerance (matmul reassociates)."""
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.core import topology

    x = jax.random.normal(jax.random.key(seed), (c, 3, 2))
    p = {"w": x}
    offsets = (0, shift)
    rolls = aggregation.mix_rolls(p, offsets, 0.5)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    halo = jax.jit(jax.shard_map(
        lambda q: aggregation.mix_shift_halo(q, offsets, 0.5, "data"),
        mesh=mesh, in_specs=P("data"), out_specs=P("data"),
        check_vma=False))(p)
    np.testing.assert_array_equal(np.asarray(halo["w"]),
                                  np.asarray(rolls["w"]))
    dense = aggregation.mix(p, topology.PairShift(shift=shift).matrix(c))
    np.testing.assert_allclose(np.asarray(rolls["w"]),
                               np.asarray(dense["w"]), atol=1e-6)


@settings(**SETTINGS)
@given(c=st.integers(2, 12), seed=st.integers(0, 1000),
       ring_k=st.integers(1, 4), p_link=st.floats(0.0, 1.0))
def test_shipped_topologies_row_stochastic(c, seed, ring_k, p_link):
    from repro.core import topology

    topos = [topology.FullMesh(), topology.Ring(min(ring_k, max(c // 2, 1))),
             topology.RandomGraph(p_link),
             topology.PartialParticipation(n_active=max(c // 2, 1)),
             topology.PairShift(shift=seed % (c + 2)),
             topology.GossipRotation(step=1 + seed % 3),
             topology.AlternatingSchedule((
                 (topology.Ring(neighbors=1), 1 + seed % 3),
                 (topology.RandomGraph(p_link), 1),
                 (topology.FullMesh(), 1))),
             topology.LinkQualitySchedule(fading_period=1 + seed % 5)]
    for t in topos:
        w = np.asarray(t.matrix(c, key=jax.random.key(seed),
                                round_idx=jnp.int32(seed % 7)))
        assert (w >= 0).all()
        np.testing.assert_allclose(w.sum(axis=1), np.ones(c), atol=1e-5)


# ---------------------------------------------------------------------------
# Robust consensus reducers (aggregation.robust_*)
# ---------------------------------------------------------------------------


def _client_stack(c, p, seed, spread):
    x = jax.random.normal(jax.random.key(seed), (c, p)) * spread
    return {"w": x, "b": jax.random.normal(jax.random.key(seed + 1), (c, 3))}


@settings(**SETTINGS)
@given(c=st.integers(3, 10), p=st.integers(1, 17), seed=st.integers(0, 500),
       spread=st.floats(0.1, 100.0), perm_seed=st.integers(0, 500))
def test_robust_reducers_permutation_invariant(c, p, seed, spread, perm_seed):
    """Order statistics cannot depend on WHO holds each model: permuting
    the client axis leaves the sorting reducers' aggregate BITWISE
    unchanged (sort canonicalizes the order before any arithmetic), and
    the Weiszfeld geometric median unchanged to float tolerance (its
    weighted sums run in client order, so a permutation reassociates
    fp32 — value-invariant, not bit-invariant)."""
    full = _client_stack(c, p, seed, spread)
    perm = np.asarray(jax.random.permutation(
        jax.random.key(perm_seed), c))
    shuffled = jax.tree.map(lambda l: l[perm], full)
    for reduce_full in (aggregation.robust_median,
                        lambda t: aggregation.robust_trimmed(t, (c - 1) // 2)):
        a = reduce_full(full)
        b = reduce_full(shuffled)
        for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(la)[0],
                                          np.asarray(lb)[0])
    a = aggregation.robust_geomedian(full, 8)
    b = aggregation.robust_geomedian(shuffled, 8)
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(la)[0], np.asarray(lb)[0],
                                   rtol=1e-5, atol=1e-6)


@settings(**SETTINGS)
@given(c=st.integers(2, 10), p=st.integers(1, 17), seed=st.integers(0, 500))
def test_robust_reducers_agree_with_mean_on_identical_rows(c, p, seed):
    """Full consensus input (every client broadcasts the same model) is a
    fixed point of every aggregator — robust or linear."""
    row = {"w": jax.random.normal(jax.random.key(seed), (p,)),
           "b": jax.random.normal(jax.random.key(seed + 1), (3,))}
    full = jax.tree.map(
        lambda l: jnp.broadcast_to(l[None], (c,) + l.shape), row)
    for reduce_full in (aggregation.robust_median,
                        lambda t: aggregation.robust_trimmed(t, (c - 1) // 2),
                        lambda t: aggregation.robust_geomedian(t, 8)):
        out = reduce_full(full)
        for got, want in zip(jax.tree.leaves(out), jax.tree.leaves(full)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-6, atol=1e-7)


@settings(**SETTINGS)
@given(c=st.integers(2, 12), p=st.integers(1, 33), seed=st.integers(0, 500),
       spread=st.floats(0.1, 1000.0))
def test_trimmed_zero_is_the_mean_to_ulp(c, p, seed, spread):
    """trimmed(0) IS the arithmetic mean up to fp32 reassociation of the
    sorted sum. Two-tier claim, pinned so neither bound silently grows:
    on same-sign data (condition number ~1) the two agree to <= 16 ULP;
    on centered data cancellation makes a relative bound meaningless, and
    the error obeys the classic backward bound
    ``(c-1) * eps * sum_i |x_i| / c`` per coordinate (x2 margin)."""
    from equivalence import tree_max_ulp

    x = jax.random.normal(jax.random.key(seed), (c, p)) * spread

    pos = {"w": x + 4.0 * spread}      # same sign: well-conditioned sum
    trimmed = aggregation.robust_trimmed(pos, 0)
    mean = jax.tree.map(
        lambda l: jnp.broadcast_to(jnp.mean(l.astype(jnp.float32), axis=0),
                                   l.shape), pos)
    assert tree_max_ulp(trimmed, mean) <= 16

    t0 = np.asarray(aggregation.robust_trimmed({"w": x}, 0)["w"][0])
    m0 = np.asarray(jnp.mean(x.astype(jnp.float32), axis=0))
    bound = (c - 1) * np.finfo(np.float32).eps \
        * np.abs(np.asarray(x)).sum(axis=0) / c
    assert (np.abs(t0 - m0) <= 2.0 * bound + 1e-30).all()
