"""Flat gather vs two-level hierarchical aggregation on a multi-pod mesh.

Runs the SAME ``ClusterTopology`` config through the K-round scan engine
twice on 8 host devices and compares rounds/sec plus the analytic
per-device receive volume of the communicate stage:

  * ``flat``    — single-axis ``('data',)`` mesh: the resolver cannot align
    clusters to pods, so the mix falls back to the gathered dense path —
    every device receives the other shards' client blocks,
    ``(C - L) * model`` bytes per round (``L`` = local client rows).
  * ``cluster`` — ``make_cluster_mesh``'s 2-D ``('pod', 'data')`` mesh with
    the pod extent equal to ``n_clusters``: the resolver lowers to in-pod
    aggregation + a narrow cross-pod halo — one in-pod all-gather of the
    other ``S - L`` cluster rows plus TWO model-sized cross-pod
    ``ppermute``s of the cluster mean, ``(S - L + 2) * model`` bytes.

Both layouts produce bitwise-identical params/ledgers (the engine contract;
tests/test_multidevice_scan.py), so the bytes column is a pure
communication-volume win: at equal C the hierarchical lowering moves
strictly fewer bytes whenever ``C - C/D > C/G - C/D + 2`` models, i.e. for
any C comfortably above the pod count. ``bench()`` asserts that inequality
on the analytic numbers it reports.

Same caveat as bench_multidevice: host "devices" are threads sharing one
memory system, so read rounds/sec as the lowering's overhead curve — the
bytes ratio is the quantity that transfers to a real multi-pod ICI mesh.

  PYTHONPATH=src python -m benchmarks.bench_hierarchy [--clusters 2]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks import common  # noqa: E402

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_CHILD = textwrap.dedent("""
    import os, sys, json, time
    layout = sys.argv[1]; n_clusters = int(sys.argv[2])
    n_dev = int(sys.argv[3]); n_rounds = int(sys.argv[4])
    n_clients = int(sys.argv[5]); samples = int(sys.argv[6])
    tau = int(sys.argv[7]); reps = int(sys.argv[8])
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_dev}")
    import jax
    from repro.core import rounds, topology
    from repro.data.pipeline import FLDataSource
    from repro.launch.mesh import make_client_mesh, make_cluster_mesh
    from repro.models.mlp import init_mlp, mlp_loss
    from repro.sharding import plans

    key = jax.random.key(0)
    src = FLDataSource(key, n_clients, samples, seed=0)
    params = init_mlp(jax.random.fold_in(key, 1))
    spec = rounds.RoundSpec(
        n_clients=n_clients, tau=tau, eta=0.05, n_lazy=2, sigma2=0.01,
        mine_attempts=256, difficulty_bits=2,
        topology=topology.ClusterTopology(n_clusters=n_clusters))
    if layout == "cluster":
        mesh = make_cluster_mesh(n_clusters, n_dev)
        plan = plans.scan_carry_plan(mesh, n_clients,
                                     client_axes=("pod", "data"))
    else:
        mesh = make_client_mesh(n_dev)
        plan = plans.scan_carry_plan(mesh, n_clients)
    batch, rk = src.static_batch(), jax.random.fold_in(key, 2)

    # analytic per-device receive bytes of the communicate collectives
    model_bytes = 4 * sum(x.size for x in jax.tree.leaves(params))
    local = n_clients // n_dev
    cluster_rows = n_clients // n_clusters
    if layout == "cluster":
        # in-pod all-gather of the other S - L cluster rows + two
        # cross-pod ppermutes of the model-sized cluster mean
        mix_bytes = (cluster_rows - local + 2) * model_bytes
    else:
        # flat fallback: all-gather every other shard's client block
        mix_bytes = (n_clients - local) * model_bytes

    def run():
        return rounds.run_blade_fl_scan(mlp_loss, spec, params, batch, rk,
                                        n_rounds, mesh=mesh, plan=plan)

    run()                                  # warm: compile
    t0 = time.time()
    for _ in range(reps):
        state, hist, ledger = run()
    wall = (time.time() - t0) / reps
    mesh_axes = tuple(zip(mesh.axis_names, mesh.devices.shape))
    mix_mode = topology.resolve_mix_plan(spec, mesh_axes).mode
    print(json.dumps({"platform": jax.devices()[0].platform,
                      "layout": layout, "devices": n_dev,
                      "n_clusters": n_clusters, "mix_mode": mix_mode,
                      "rounds_per_s": n_rounds / wall, "wall_s": wall,
                      "model_bytes": model_bytes,
                      "est_mix_bytes_per_round": mix_bytes,
                      "chain_valid": ledger.validate_chain(),
                      "final_global_loss": hist[-1]["global_loss"]}))
""")


def bench(n_clusters: int = 2, n_dev: int = 8, n_rounds: int = 16,
          n_clients: int = 16, samples: int = 64, tau: int = 4,
          reps: int = 3) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    # host placeholder devices only: on a chip host the parent may hold the
    # chip, and a child on one TPU device would not be this bench's mesh
    env["JAX_PLATFORMS"] = "cpu"
    out = {}
    for layout in ("flat", "cluster"):
        proc = subprocess.run(
            [sys.executable, "-c", _CHILD, layout, str(n_clusters),
             str(n_dev), str(n_rounds), str(n_clients), str(samples),
             str(tau), str(reps)],
            capture_output=True, text=True, env=env, timeout=900)
        if proc.returncode != 0:
            raise RuntimeError(f"hierarchy {layout} child failed: "
                               f"{proc.stderr[-500:]}")
        res = json.loads(proc.stdout.strip().splitlines()[-1])
        out[layout] = res
        common.csv_line(
            f"hierarchy_{layout}_G{n_clusters}_D{n_dev}_C{n_clients}",
            res["wall_s"] / n_rounds * 1e6,
            f"platform={res['platform']};"
            f"rounds_per_s={res['rounds_per_s']:.1f};"
            f"mix_bytes={res['est_mix_bytes_per_round']:.0f}")
    if "flat" in out and "cluster" in out:
        flat_b = out["flat"]["est_mix_bytes_per_round"]
        hier_b = out["cluster"]["est_mix_bytes_per_round"]
        if hier_b >= flat_b:
            # the whole point of the two-level lowering: strictly fewer
            # bytes than the flat gather at equal C
            raise ValueError(
                f"hierarchical bytes {hier_b} not < flat {flat_b}")
        out["flat_vs_cluster_bytes_ratio"] = flat_b / hier_b
        out["cluster_vs_flat_speedup"] = (
            out["cluster"]["rounds_per_s"] / out["flat"]["rounds_per_s"])
    return out


def run():
    return bench()


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--clusters", type=int, default=2)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=16)
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--samples", type=int, default=64)
    ap.add_argument("--tau", type=int, default=4)
    ap.add_argument("--reps", type=int, default=3)
    a = ap.parse_args()
    print(json.dumps(bench(a.clusters, a.devices, a.rounds, a.clients,
                           a.samples, a.tau, a.reps), indent=1))
