"""Two-level aggregation + single-dispatch-surface regressions.

Host side: ``dispatch_plan``'s reported ``mix_mode`` is pinned to the mode
``make_communicate`` actually executes, for every shipped topology crossed
with every mix-relevant ``RoundSpec`` flag — both read the SAME
``topology.resolve_mix_plan``, so report/trace drift (the duplicated
weighted-reroute bug this PR deleted) cannot reappear.

Subprocess side (8 fake devices, 2x4 ``('pod', 'data')`` mesh): the
linearized multi-axis halo lowerings equal dense ``mix_rolls`` bitwise for
shift grids that cross the pod seam and wrap the population, and
``mix_cluster``'s aligned in-pod + cross-pod path equals its dense
``kron(B, J/S)`` math bitwise.
"""
import itertools
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

from repro.core import rounds, topology  # noqa: E402

C = 8

TOPOLOGIES = [
    topology.FullMesh(),
    topology.Ring(neighbors=1),
    topology.Ring(neighbors=2),
    topology.RandomGraph(p_link=0.6),
    topology.PartialParticipation(n_active=3),
    topology.PairShift(shift=5),
    topology.ClusterTopology(n_clusters=2),
    topology.ClusterTopology(n_clusters=4, inter_weight=0.5),
    topology.ExplicitSparse(neighbors=tuple(
        (i, (i + 1) % C) for i in range(C))),
    topology.GossipRotation(),
    topology.AlternatingSchedule(
        ((topology.Ring(neighbors=1), 2), (topology.FullMesh(), 1))),
    topology.LinkQualitySchedule(fading_period=3),
]

FLAG_GRID = list(itertools.product(
    (False, True),                                   # fast_allreduce
    (False, True),                                   # fused_mix
    (None, True),                                    # sparse_mix
    (None, tuple(float(i + 1) for i in range(C))),   # data_weights
    (None, "median", "trimmed:2", "geomed:4"),       # robust_agg
))


def _spec(topo, fast, fused, sparse, weights, robust=None):
    return rounds.RoundSpec(
        n_clients=C, tau=1, eta=0.1, mine_attempts=8, difficulty_bits=1,
        topology=topo, fast_allreduce=fast, fused_mix=fused,
        sparse_mix=sparse, data_weights=weights, robust_agg=robust)


@pytest.mark.parametrize("topo", TOPOLOGIES,
                         ids=lambda t: type(t).__name__)
def test_dispatch_report_matches_executed_mode(topo):
    """plan['mix_mode'] (the report) == communicate.plan.mode (the trace)
    for every flag combination — one resolver, zero drift."""
    import jax.numpy as jnp
    batch = {"x": jnp.zeros((C, 4, 3)), "y": jnp.zeros((C, 4), jnp.int32)}
    for fast, fused, sparse, weights, robust in FLAG_GRID:
        spec = _spec(topo, fast, fused, sparse, weights, robust)
        try:
            reported = rounds.dispatch_plan(spec, batch, 3)["mix_mode"]
        except ValueError:
            # resolver rejected the combo (e.g. sparse_mix=True on a
            # stochastic graph, or a robust override crossed with a
            # linear fast path) — the executor must reject it identically
            with pytest.raises(ValueError):
                rounds.make_communicate(spec)
            continue
        executed = rounds.make_communicate(spec).plan.mode
        assert reported == executed, (
            type(topo).__name__, fast, fused, sparse,
            weights is not None, robust, reported, executed)


def test_dispatch_grid_covers_every_executor_mode():
    """The topology x flag grid above actually exercises the whole executor
    surface — if a new EXEC_* mode ships without a topology that reaches
    it, this fails and the grid must grow."""
    seen = set()
    for topo in TOPOLOGIES:
        for fast, fused, sparse, weights, robust in FLAG_GRID:
            spec = _spec(topo, fast, fused, sparse, weights, robust)
            try:
                seen.add(rounds.make_communicate(spec).plan.mode)
                # sharded resolve: EXEC_HALO degrades to EXEC_SHIFT_HALO
                # when the shift window outgrows the per-shard block
                seen.add(rounds.make_communicate(
                    spec, axis_name=("pod", "data"), n_shards=8,
                    axis_sizes=(2, 4)).plan.mode)
            except ValueError:
                continue  # resolver-rejected combo (covered above)
    all_modes = {getattr(topology, n) for n in dir(topology)
                 if n.startswith("EXEC_")}
    assert seen == all_modes, (sorted(seen), sorted(all_modes))


@pytest.mark.slow
def test_multi_axis_halo_and_cluster_grid_subprocess():
    """On the 2x4 ('pod', 'data') mesh the linearized halo lowerings match
    dense mix_rolls bitwise for every offset grid — windows inside one
    block, shifts across the pod seam (device 3 -> 4), and full wraps — and
    mix_cluster's aligned and unaligned shardings match its dense path."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax, numpy as np, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.core import aggregation

        C = 16
        mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("pod", "data"))
        axes = ("pod", "data")
        key = jax.random.key(11)
        tree = {
            "m2": jax.random.normal(key, (C, 37), jnp.float32),
            "m3": jax.random.normal(jax.random.fold_in(key, 1),
                                    (C, 5, 7), jnp.float32),
        }

        def sharded(fn):
            wrapped = jax.shard_map(fn, mesh=mesh, in_specs=P(axes),
                                    out_specs=P(axes), check_vma=False)
            return jax.jit(wrapped)

        def bitwise(a, b):
            return all(bool((np.asarray(x) == np.asarray(y)).all())
                       for x, y in zip(jax.tree.leaves(a),
                                       jax.tree.leaves(b)))

        out = {}
        # local block is C/8 = 2 rows: (-2..2) is the one-block halo
        # window; the rest exercise mix_shift_halo's q-block decomposition
        halo_grids = [(-1, 0, 1), (-2, -1, 0, 1, 2)]
        shift_grids = [(5,), (-7,), (0, 8), (3, 13), (1, 6, 11)]
        for offs in halo_grids:
            dense = aggregation.mix_rolls(tree, offs, 1.0 / len(offs))
            halo = sharded(lambda t: aggregation.mix_neighbor_halo(
                t, offs, 1.0 / len(offs), axes))(tree)
            out[f"halo{offs}"] = bitwise(dense, halo)
        for offs in halo_grids + shift_grids:
            dense = aggregation.mix_rolls(tree, offs, 1.0 / len(offs))
            shift = sharded(lambda t: aggregation.mix_shift_halo(
                t, offs, 1.0 / len(offs), axes))(tree)
            out[f"shift{offs}"] = bitwise(dense, shift)
        for g in (2, 4):   # pod-aligned and unaligned cluster counts
            dense = aggregation.mix_cluster(tree, g, 0.3)
            shard = sharded(lambda t: aggregation.mix_cluster(
                t, g, 0.3, axes, n_shards=8))(tree)
            out[f"cluster_g{g}"] = bitwise(dense, shard)
        print(json.dumps(out))
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res and all(res.values()), res
