"""MixLowering dispatch + dense/lowered mixing equivalence (fast lane).

The sharded paths run under shard_map on a 1-device mesh here — that
exercises the collective code (all_gather / ppermute / local-rows slice)
without subprocesses; real >=4-device coverage is the slow
tests/test_multidevice_scan.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import aggregation, topology
from repro.sharding import plans

from conftest import make_fake_mesh


def _params(key, c=8):
    k1, k2 = jax.random.split(key)
    return {"w1": jax.random.normal(k1, (c, 6, 5)),
            "b": jax.random.normal(k2, (c, 5))}


def _one_device_mesh():
    return Mesh(np.array(jax.devices()[:1]), ("data",))


# ---------------------------------------------------------------------------
# Lowering dispatch
# ---------------------------------------------------------------------------


def test_lowering_dispatch_kinds():
    assert topology.FullMesh().lowering(8).kind == topology.ALL_REDUCE
    assert topology.RandomGraph(0.5).lowering(8).kind == topology.GATHER
    assert topology.PartialParticipation(3).lowering(8).kind == topology.GATHER
    low = topology.Ring(neighbors=2).lowering(8)
    assert low.kind == topology.NEIGHBOR_PERMUTE
    assert low.offsets == (-2, -1, 0, 1, 2)
    assert low.weight == pytest.approx(0.2)
    # base Topology defaults to the gather fallback
    assert topology.Topology().lowering(8).kind == topology.GATHER


def test_ring_degenerate_window_falls_back_to_gather():
    # 2k+1 > C: the wrap-around window needs the dedup'd matrix
    assert topology.Ring(neighbors=3).lowering(4).kind == topology.GATHER
    assert topology.Ring(neighbors=2).lowering(5).kind == \
        topology.NEIGHBOR_PERMUTE


def test_schedule_lowering_dispatch():
    # rotation: round-dependent neighbor_permute offsets, one pair per phase
    low = topology.GossipRotation().lowering(8)
    assert low.kind == topology.NEIGHBOR_PERMUTE
    assert low.weight == pytest.approx(0.5)
    assert len(low.offsets_table) == 7
    assert low.offsets_table[0] == (0, 1) and low.offsets_table[6] == (0, 7)
    # pair shift: static neighbor_permute at any shift
    assert topology.PairShift(shift=5).lowering(8).offsets == (0, 5)
    # other schedules: gather fallback (static table / keyed draw)
    alt = topology.AlternatingSchedule(
        ((topology.Ring(neighbors=1), 2), (topology.FullMesh(), 1)))
    assert alt.lowering(8).kind == topology.GATHER
    assert topology.LinkQualitySchedule().lowering(8).kind == topology.GATHER


# ---------------------------------------------------------------------------
# Dense paths
# ---------------------------------------------------------------------------


def test_mix_all_reduce_dense_is_fedavg_bitwise():
    p = _params(jax.random.key(0))
    got = aggregation.mix_all_reduce(p)
    want = aggregation.fedavg(p)
    for k in p:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]))


def test_mix_rolls_matches_ring_matrix():
    c = 8
    p = _params(jax.random.key(1), c=c)
    for k_n in (1, 2, 3):
        low = topology.Ring(neighbors=k_n).lowering(c)
        got = aggregation.mix_rolls(p, low.offsets, low.weight)
        want = aggregation.mix(p, topology.Ring(neighbors=k_n).matrix(c))
        for key in p:
            # same mix, different fp32 association (roll-sum vs matmul)
            np.testing.assert_allclose(np.asarray(got[key]),
                                       np.asarray(want[key]), atol=1e-5)


def test_mix_rolls_identity_offset_is_noop():
    p = _params(jax.random.key(2), c=4)
    got = aggregation.mix_rolls(p, offsets=(0,), weight=1.0)
    for k in p:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(p[k]))


# ---------------------------------------------------------------------------
# Sharded paths (shard_map, 1-device mesh) == dense paths, bitwise
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("topo", [
    topology.FullMesh(),
    topology.Ring(neighbors=1),
    topology.Ring(neighbors=2),
    topology.RandomGraph(p_link=0.6),
    topology.PartialParticipation(n_active=3),
], ids=lambda t: type(t).__name__ + str(vars(t) or ""))
def test_sharded_mix_bitwise_equals_dense(topo):
    c = 8
    p = _params(jax.random.key(3), c=c)
    w = topo.matrix(c, key=jax.random.key(7), round_idx=jnp.int32(0))
    low = topo.lowering(c)
    mesh = _one_device_mesh()

    def dense(params):
        if low.kind == topology.ALL_REDUCE:
            return aggregation.mix_all_reduce(params)
        if low.kind == topology.NEIGHBOR_PERMUTE:
            return aggregation.mix_rolls(params, low.offsets, low.weight)
        return aggregation.mix_gather(params, w)

    def sharded(params):
        if low.kind == topology.ALL_REDUCE:
            return aggregation.mix_all_reduce(params, axis_name="data")
        if low.kind == topology.NEIGHBOR_PERMUTE:
            return aggregation.mix_neighbor_halo(params, low.offsets,
                                                 low.weight, "data")
        return aggregation.mix_gather(params, w, axis_name="data", n_shards=1)

    want = jax.jit(dense)(p)
    got = jax.jit(jax.shard_map(sharded, mesh=mesh, in_specs=P("data"),
                                out_specs=P("data"), check_vma=False))(p)
    for k in p:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]))


@pytest.mark.parametrize("shift", [0, 1, 3, 5, 7, 9])
def test_mix_shift_halo_matches_rolls_bitwise(shift):
    """The arbitrary-shift halo (block ppermutes + static slice) equals the
    dense roll form bit for bit, for shifts beyond one block and wrapping."""
    c = 8
    p = _params(jax.random.key(5), c=c)
    offsets = (0, shift)
    mesh = _one_device_mesh()
    want = jax.jit(lambda q: aggregation.mix_rolls(q, offsets, 0.5))(p)
    got = jax.jit(jax.shard_map(
        lambda q: aggregation.mix_shift_halo(q, offsets, 0.5, "data"),
        mesh=mesh, in_specs=P("data"), out_specs=P("data"),
        check_vma=False))(p)
    for k in p:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]))


def test_mix_shift_halo_dense_mode_is_rolls():
    p = _params(jax.random.key(6), c=4)
    got = aggregation.mix_shift_halo(p, (0, 2), 0.5, None)
    want = aggregation.mix_rolls(p, (0, 2), 0.5)
    for k in p:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]))


@pytest.mark.parametrize("sched", [
    topology.GossipRotation(),
    topology.AlternatingSchedule(
        ((topology.Ring(neighbors=1), 2), (topology.FullMesh(), 1))),
    topology.AlternatingSchedule(
        ((topology.RandomGraph(p_link=0.6), 1), (topology.FullMesh(), 1))),
    topology.LinkQualitySchedule(fading_period=3),
], ids=lambda t: type(t).__name__)
def test_sharded_schedule_mix_bitwise_equals_dense(sched):
    """Per-phase: the schedule's sharded mix (switch over permute branches /
    table-indexed gather) equals the dense matrix mix bitwise at every
    round of a period."""
    c = 8
    p = _params(jax.random.key(7), c=c)
    mesh = _one_device_mesh()
    low = sched.lowering(c)
    for t in range(sched.period(c)):
        key = jax.random.key(13)
        w = sched.matrix(c, key=key if sched.stochastic else None,
                         round_idx=jnp.int32(t))
        if low.offsets_table:
            offs = low.offsets_table[t]
            want = aggregation.mix_rolls(p, offs, low.weight)
            sharded = lambda q: aggregation.mix_shift_halo(  # noqa: E731
                q, offs, low.weight, "data")
        else:
            want = aggregation.mix(p, w)
            sharded = lambda q: aggregation.mix_gather(  # noqa: E731
                q, w, axis_name="data", n_shards=1)
        got = jax.jit(jax.shard_map(sharded, mesh=mesh, in_specs=P("data"),
                                    out_specs=P("data"), check_vma=False))(p)
        for k in p:
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(want[k]))


def test_client_gather_slice_roundtrip_under_shard_map():
    c = 8
    p = _params(jax.random.key(4), c=c)
    mesh = _one_device_mesh()

    def f(params):
        full = aggregation.client_all_gather(params, "data")
        return aggregation.client_local_rows(full, "data", n_shards=1)

    got = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                                out_specs=P("data"), check_vma=False))(p)
    for k in p:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(p[k]))


# ---------------------------------------------------------------------------
# Scan-carry plan
# ---------------------------------------------------------------------------


def test_scan_carry_plan_validates():
    mesh = _one_device_mesh()
    plan = plans.scan_carry_plan(mesh, 8)
    assert plan.n_shards == 1 and plan.clients_per_shard == 8
    assert plan.client_spec() == P(("data",))
    assert plan.batch_spec(stacked=False) == P(("data",))
    assert plan.batch_spec(stacked=True) == P(None, ("data",))
    with pytest.raises(ValueError):
        plans.scan_carry_plan(mesh, 8, client_axes=("model",))


def test_scan_carry_plan_divisibility():
    # fake 16x16 mesh: extent of ('data',) is 16; C must divide over it
    mesh = make_fake_mesh()
    with pytest.raises(ValueError):
        plans.scan_carry_plan(mesh, 20)          # 20 % 16 != 0
    plan = plans.scan_carry_plan(mesh, 32)
    assert plan.n_shards == 16 and plan.clients_per_shard == 2
    plan2 = plans.scan_carry_plan(mesh, 256, client_axes=("data", "model"))
    assert plan2.n_shards == 256


def test_run_blade_fl_rejects_mesh_with_callable_batches():
    from repro.core import rounds
    from repro.models.mlp import init_mlp, mlp_loss

    key = jax.random.key(0)
    params = init_mlp(key)
    spec = rounds.RoundSpec(n_clients=2, tau=1, eta=0.1, mine_attempts=8)
    with pytest.raises(ValueError, match="static batch"):
        rounds.run_blade_fl(mlp_loss, spec, params, lambda k: {}, key, 1,
                            mesh=_one_device_mesh())
