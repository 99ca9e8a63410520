"""Rounds/sec of the client-sharded K-round scan engine vs device count,
gather-side all-reduce (bitwise) vs the opt-in psum fast path side by side.

Each device count runs in its own subprocess because
``--xla_force_host_platform_device_count`` must be set before the first jax
import — the same trick the dry-run and the multi-device tests use. The
child runs the identical config through ``run_blade_fl_scan`` with a
``make_client_mesh`` of that size (1 device = the plain single-device scan)
and reports warm rounds/sec, once per mix lowering mode:

  * ``gather`` — the default bitwise engine (all-gather the broadcast set,
    replicated full-width math);
  * ``psum``   — ``RoundSpec.fast_allreduce=True``: one model-sized
    ``lax.psum`` mixes the clients and the digest/divergence diagnostics
    psum local partials (tolerance tier, hashes fork; see
    docs/architecture.md §The tolerance tier);
  * ``kernel`` — the Pallas tier (``use_kernel + fused_mix``,
    ``kernel_interpret=True`` on host devices): the 2-D PoW grid race
    (bitwise) plus the fused row-select mix matmul and one-sweep
    digest/divergence (tolerance). Same all-gather as ``gather``, but the
    mix writes only the C/D LOCAL rows and the diagnostics sweep the
    broadcast set once instead of twice — the bytes column records that.
    Interpret-mode wall-clock prices the grid's structure, not TPU time.

Alongside rounds/sec each child reports ``est_mix_bytes_per_round`` — the
analytic per-device receive volume of the communicate stage's collectives
(all-gather of C−C/D client models vs a ring all-reduce of ONE model,
2·(D−1)/D·model) — so the JSON records the gather-vs-psum bytes-moved ratio
the fast path is buying, even on host "devices" where wall-clock barely
moves (threads share one memory system; the ratio is what transfers to a
real ICI mesh).

This bench sweeps FLAT single-axis meshes; ``bench_hierarchy`` runs the
same engine on a 2-D ``('pod', 'data')`` mesh and prices the two-level
cluster lowering (in-pod aggregation + cross-pod halo) against the flat
gather measured here.

Read CPU numbers as the COST CURVE of the sharded lowering, not a speedup
claim: host "devices" are threads carved out of the same CPU, so the
per-client math gets no new FLOPs and the all-gathers/ppermutes are pure
overhead. What the curve shows is that overhead staying small (the engine's
collectives are O(1) per round), which is the quantity that transfers to a
real mesh where D devices DO bring D× the compute. The engine's bitwise
contract (tests/test_multidevice_scan.py) holds within a process; ACROSS
the child processes here the loss values can drift in the last ulps,
because ``--xla_force_host_platform_device_count`` changes XLA:CPU's
intra-op thread partitioning and with it the association of large
reductions — the per-run ``chain_valid`` is the correctness signal.

  PYTHONPATH=src python -m benchmarks.bench_multidevice [--devices 1,2,4,8]
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
    n_dev = int(sys.argv[1]); n_rounds = int(sys.argv[2])
    n_clients = int(sys.argv[3]); samples = int(sys.argv[4])
    tau = int(sys.argv[5]); reps = int(sys.argv[6])
    mode = sys.argv[7]
    if n_dev > 1:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={n_dev}")
    import jax
    from repro.core import rounds
    from repro.data.pipeline import FLDataSource
    from repro.launch.mesh import make_client_mesh
    from repro.models.mlp import init_mlp, mlp_loss

    key = jax.random.key(0)
    src = FLDataSource(key, n_clients, samples, seed=0)
    params = init_mlp(jax.random.fold_in(key, 1))
    spec = rounds.RoundSpec(n_clients=n_clients, tau=tau, eta=0.05,
                            n_lazy=2, sigma2=0.01, mine_attempts=256,
                            difficulty_bits=2,
                            fast_allreduce=(mode == "psum"),
                            use_kernel=(mode == "kernel"),
                            fused_mix=(mode == "kernel"),
                            kernel_interpret=True if mode == "kernel"
                            else None)
    mesh = make_client_mesh(n_dev) if n_dev > 1 else None
    batch, rk = src.static_batch(), jax.random.fold_in(key, 2)

    # analytic per-device receive bytes of the communicate-stage collectives
    model_bytes = 4 * sum(x.size for x in jax.tree.leaves(params))
    local = n_clients // n_dev
    if n_dev == 1:
        mix_bytes = 0.0
    elif mode == "psum":
        # ring all-reduce of ONE model (reduce-scatter + all-gather)
        mix_bytes = 2.0 * (n_dev - 1) / n_dev * model_bytes
    else:
        # all-gather of every other shard's client blocks (the kernel tier
        # gathers identically; its win is rows written + diag sweeps)
        mix_bytes = (n_clients - local) * model_bytes
    # model-bytes the mix + diagnostics WRITE/SWEEP per device per round:
    # fused kernel writes only the local rows and sweeps the broadcast set
    # once; the jnp path writes all C rows and sweeps twice.
    if mode == "kernel":
        hot_bytes = (n_clients + local) * model_bytes + n_clients * model_bytes
    else:
        hot_bytes = 2 * n_clients * model_bytes + 2 * n_clients * model_bytes

    def run():
        return rounds.run_blade_fl_scan(mlp_loss, spec, params, batch, rk,
                                        n_rounds, mesh=mesh)

    run()                                  # warm: compile
    t0 = time.time()
    for _ in range(reps):
        state, hist, ledger = run()
    wall = (time.time() - t0) / reps
    print(json.dumps({"platform": jax.devices()[0].platform,
                      "devices": n_dev, "mode": mode,
                      "rounds_per_s": n_rounds / wall, "wall_s": wall,
                      "model_bytes": model_bytes,
                      "est_mix_bytes_per_round": mix_bytes,
                      "est_mix_diag_local_bytes": hot_bytes,
                      "interpret": mode == "kernel",
                      "chain_valid": ledger.validate_chain(),
                      "final_global_loss": hist[-1]["global_loss"]}))
""")


def bench(device_counts=(1, 2, 4, 8), n_rounds: int = 16, n_clients: int = 16,
          samples: int = 64, tau: int = 4, reps: int = 3) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    # host placeholder devices only: on a chip host the parent may hold the
    # chip, and a child on one TPU device would not be this bench's mesh
    env["JAX_PLATFORMS"] = "cpu"
    out = {}
    for d in device_counts:
        if n_clients % d:
            print(f"# skip devices={d}: {n_clients} clients not divisible")
            continue
        modes = {}
        for mode in ("gather", "psum", "kernel"):
            proc = subprocess.run(
                [sys.executable, "-c", _CHILD, str(d), str(n_rounds),
                 str(n_clients), str(samples), str(tau), str(reps),
                 mode],
                capture_output=True, text=True, env=env, timeout=900)
            if proc.returncode != 0:
                raise RuntimeError(f"devices={d} {mode} child failed: "
                                   f"{proc.stderr[-500:]}")
            res = json.loads(proc.stdout.strip().splitlines()[-1])
            modes[mode] = res
            note = (f"platform={res['platform']};"
                    f"rounds_per_s={res['rounds_per_s']:.1f}")
            if res.get("interpret"):
                note += ";interpret=True"
            common.csv_line(
                f"multidevice_scan_{mode}_D{d}_K{n_rounds}_C{n_clients}",
                res["wall_s"] / n_rounds * 1e6, note)
        if not modes:
            continue
        if "gather" in modes and "psum" in modes:
            g, p = modes["gather"], modes["psum"]
            modes["psum_vs_gather_speedup"] = (
                p["rounds_per_s"] / g["rounds_per_s"])
            if p["est_mix_bytes_per_round"]:
                modes["gather_vs_psum_bytes_ratio"] = (
                    g["est_mix_bytes_per_round"]
                    / p["est_mix_bytes_per_round"])
        if "gather" in modes and "kernel" in modes:
            g, k = modes["gather"], modes["kernel"]
            modes["kernel_vs_gather_speedup"] = (
                k["rounds_per_s"] / g["rounds_per_s"])
            modes["gather_vs_kernel_local_bytes_ratio"] = (
                g["est_mix_diag_local_bytes"]
                / k["est_mix_diag_local_bytes"])
        out[d] = modes
    if 1 in out and "gather" in out[1]:
        base = out[1]["gather"]["rounds_per_s"]
        for d, modes in out.items():
            for mode in ("gather", "psum", "kernel"):
                if mode in modes:
                    modes[mode]["vs_single_device_gather"] = (
                        modes[mode]["rounds_per_s"] / base)
    return out


def run():
    return bench()


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", default="1,2,4,8",
                    help="comma list of host-device counts to sweep")
    ap.add_argument("--rounds", type=int, default=16)
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--samples", type=int, default=64)
    ap.add_argument("--tau", type=int, default=4)
    ap.add_argument("--reps", type=int, default=3)
    a = ap.parse_args()
    counts = tuple(int(x) for x in a.devices.split(","))
    print(json.dumps(bench(counts, a.rounds, a.clients, a.samples, a.tau,
                           a.reps), indent=1))
