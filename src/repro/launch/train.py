"""End-to-end BLADE-FL training driver.

Runs real integrated rounds (training + lazy + mining + chain) either:
  * paper-scale: --arch mlp  — the §7 substrate (MLP, synthetic non-IID
    MNIST proxy, N=20 clients); used by benchmarks/examples/chip_smoke.py;
  * cohort-scale: --arch mlp --enrolled N --cohort A — a cohort of A
    clients per round out of N enrolled;
  * arch-scale: --arch <assigned id> --smoke — reduced config of the same
    family, a few clients, synthetic token streams (CPU-runnable).

Each ``run_*`` function returns a :class:`RunOutput`; ``main`` prints its
``result`` as JSON.

Example:
  PYTHONPATH=src python -m repro.launch.train --arch mlp --rounds 10 --k 5
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-32b --smoke --rounds 3

Multi-device (client-sharded scan engine; the K-round carry never leaves the
devices, and results are bit-for-bit the single-device run — see
docs/architecture.md):

  XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
    PYTHONPATH=src python -m repro.launch.train --arch mlp --devices 4
"""
from __future__ import annotations

import argparse
import contextlib
import json
import time
from typing import Any, List, NamedTuple, Optional

import jax

from repro.configs import BladeConfig, ShapeConfig, get_smoke_arch
from repro.core import allocation, attacks, chain, rounds, spectral, topology
from repro.data.pipeline import CohortDataSource, FLDataSource, LMDataSource
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_client_mesh, make_cluster_mesh
from repro.models import registry
from repro.models.mlp import init_mlp, mlp_loss
from repro.sharding import plans
from repro.training.metrics import MetricLogger


class RunOutput(NamedTuple):
    """What one ``run_*`` call produced."""
    result: dict                # the JSON summary ``main`` prints
    spec: rounds.RoundSpec
    state: Any                  # final RoundState (cohort: PopulationStore)
    history: List[dict]         # per-round metrics
    ledger: chain.Ledger
    batch: Any = None           # the static batch the scan ran over


def spectral_fields(spec: rounds.RoundSpec, run_key, n_rounds: int) -> dict:
    """1 - lambda_2(W) diagnostics for the run's topology/schedule: the
    per-round gap stats plus the ergodic (product-matrix) gap. Stochastic
    topologies replay the run's exact per-round key stream."""
    keys = (rounds.topology_keys(run_key, n_rounds)
            if spec.topology.stochastic else None)
    rep = spectral.gap_report(spec.topology, spec.n_clients, n_rounds,
                              keys=keys)
    return {"spectral_gap_mean": rep["gap_mean"],
            "spectral_gap_min": rep["gap_min"],
            "ergodic_gap": rep["ergodic_gap"],
            "predicted_consensus_rate": rep["predicted_consensus_rate"]}


def adversary_fields(args) -> dict:
    """``RoundSpec`` kwargs for the Byzantine scenario axis: ``--attack``
    (parsed by ``attacks.from_name`` with ``--attackers`` adversarial
    clients) and ``--robust`` (the aggregator override string the resolver
    parses; ``mean`` keeps the linear mix). Shared by every run path so the
    flags mean the same thing at paper scale, cohort scale and arch
    scale."""
    out = {}
    if args.attack:
        out["attack"] = attacks.from_name(args.attack, args.attackers)
    if args.robust:
        out["robust_agg"] = args.robust
    return out


def run_mlp(args) -> RunOutput:
    blade = BladeConfig(n_clients=args.clients, n_lazy=args.lazy,
                        sigma2=args.sigma2, t_sum=args.t_sum,
                        alpha=args.alpha, beta=args.beta, eta=args.eta,
                        K=args.k, dp_sigma=args.dp_sigma, seed=args.seed)
    tau = allocation.tau_from_budget(blade.t_sum, blade.K, blade.alpha, blade.beta)
    spec = rounds.RoundSpec(
        n_clients=blade.n_clients, tau=max(tau, 1), eta=blade.eta,
        n_lazy=blade.n_lazy, sigma2=blade.sigma2, dp_sigma=blade.dp_sigma,
        mine_attempts=allocation.mining_iterations(blade.beta),
        difficulty_bits=4, eval_every=args.eval_every,
        topology=topology.from_name(args.topology),
        fast_allreduce=args.fast_allreduce, use_kernel=args.kernels,
        fused_mix=args.fused_mix, **adversary_fields(args))
    key = jax.random.key(blade.seed)
    src = FLDataSource(key, blade.n_clients, blade.samples_per_client,
                       blade.dirichlet_alpha, seed=blade.seed)
    params = init_mlp(jax.random.fold_in(key, 1))
    log = MetricLogger(args.out_dir, "blade_mlp")
    # --clusters lays the mesh out hierarchically: one 'pod' row per
    # cluster, clients sharded over BOTH axes, so ClusterTopology's
    # in-cluster mean stays intra-pod and only the cluster ring crosses pods
    if args.clusters:
        mesh = make_cluster_mesh(args.clusters, args.devices)
        plan = plans.scan_carry_plan(mesh, blade.n_clients,
                                     client_axes=("pod", "data"))
    else:
        mesh = make_client_mesh(args.devices) if args.devices else None
        plan = None
    run_key = jax.random.fold_in(key, 2)
    batch = src.static_batch()
    t0 = time.perf_counter()
    # static batch -> compiled scan engine (K rounds, one dispatch);
    # --devices shards the client axis of the whole scan over the mesh
    with _profiled(args):
        state, hist, ledger = rounds.run_blade_fl(
            mlp_loss, spec, params, batch, run_key, blade.K, mesh=mesh,
            plan=plan)
        wall_s = _blocked_seconds(state, t0)
    # final eval on held-out data with the aggregated model
    from repro.core.aggregation import aggregate_once
    final = aggregate_once(state.params)
    loss, metrics = mlp_loss(final, src.eval_data)
    for i, h in enumerate(hist):
        log.log(i, **h)
    result = {
        "K": blade.K, "tau": spec.tau, "final_eval_loss": float(loss),
        "final_eval_acc": float(metrics["accuracy"]),
        "final_global_loss": hist[-1].get("global_loss"),
        "chain_valid": ledger.validate_chain(), "blocks": len(ledger.blocks),
        "devices": mesh.devices.size if mesh is not None else 1,
        "fast_allreduce": spec.fast_allreduce,
        "dispatch": dict(rounds.LAST_DISPATCH),
        "wall_s": wall_s,
        **spectral_fields(spec, run_key, blade.K),
    }
    return RunOutput(result, spec, state, hist, ledger, batch)


def run_cohort(args) -> RunOutput:
    """Cohort-sampled population run: ``--enrolled`` clients of which a
    cohort of ``--cohort`` participates per round (``--cohort-bias``
    selects the sampling weights). The round engine runs at cohort size —
    devices never see an array shaped by the enrolled count, which is what
    makes ``--enrolled 10000`` runnable on one CPU."""
    blade = BladeConfig(n_clients=args.cohort, n_lazy=args.lazy,
                        sigma2=args.sigma2, t_sum=args.t_sum,
                        alpha=args.alpha, beta=args.beta, eta=args.eta,
                        K=args.k, dp_sigma=args.dp_sigma, seed=args.seed)
    tau = allocation.tau_from_budget(blade.t_sum, blade.K, blade.alpha, blade.beta)
    cohort = topology.CohortSchedule.from_spec(
        args.enrolled, args.cohort, args.cohort_bias)
    spec = rounds.RoundSpec(
        n_clients=args.cohort, tau=max(tau, 1), eta=blade.eta,
        n_lazy=blade.n_lazy, sigma2=blade.sigma2, dp_sigma=blade.dp_sigma,
        mine_attempts=allocation.mining_iterations(blade.beta),
        difficulty_bits=4, eval_every=args.eval_every,
        topology=topology.from_name(args.topology),
        fast_allreduce=args.fast_allreduce, use_kernel=args.kernels,
        fused_mix=args.fused_mix, **adversary_fields(args))
    key = jax.random.key(blade.seed)
    src = CohortDataSource(key, blade.samples_per_client,
                           blade.dirichlet_alpha)
    params = init_mlp(jax.random.fold_in(key, 1))
    mesh = make_client_mesh(args.devices) if args.devices else None
    plan = (plans.cohort_carry_plan(mesh, args.enrolled, args.cohort)
            if mesh is not None else None)
    log = MetricLogger(args.out_dir, "blade_cohort")
    run_key = jax.random.fold_in(key, 2)
    t0 = time.perf_counter()
    with _profiled(args):
        store, hist, ledger = rounds.run_blade_fl_cohort(
            mlp_loss, spec, params, src.cohort_batch, run_key, blade.K,
            cohort, mesh=mesh, plan=plan)
    wall_s = time.perf_counter() - t0    # the store is host-resident
    # final eval: aggregate the LAST round's cohort (the freshest models)
    from repro.core.aggregation import aggregate_once
    final = aggregate_once(store.gather(hist[-1]["cohort"]))
    loss, metrics = mlp_loss(final, src.eval_data)
    for i, h in enumerate(hist):
        log.log(i, **{k: v for k, v in h.items() if k != "cohort"})
    result = {
        "enrolled": args.enrolled, "cohort": args.cohort,
        "cohort_bias": args.cohort_bias, "K": blade.K, "tau": spec.tau,
        "touched": store.touched,
        "store_mb": round(store.materialized_bytes() / 1e6, 3),
        "final_eval_loss": float(loss),
        "final_eval_acc": float(metrics["accuracy"]),
        "final_global_loss": hist[-1].get("global_loss"),
        "chain_valid": ledger.validate_chain(), "blocks": len(ledger.blocks),
        "devices": mesh.devices.size if mesh is not None else 1,
        "dispatch": dict(rounds.LAST_DISPATCH),
        "wall_s": wall_s,
        # intra-cohort mixing diagnostics at size A (the enrolled graph is
        # never materialized — that is the point)
        **spectral_fields(spec, run_key, blade.K),
    }
    return RunOutput(result, spec, store, hist, ledger)


def run_arch_smoke(args) -> RunOutput:
    cfg = get_smoke_arch(args.arch)
    shape = ShapeConfig("smoke", args.seq, args.clients * args.per_client, "train")
    spec = rounds.RoundSpec(n_clients=args.clients, tau=2, eta=1e-2,
                            n_lazy=args.lazy, sigma2=args.sigma2,
                            mine_attempts=256, difficulty_bits=2,
                            eval_every=args.eval_every,
                            topology=topology.from_name(args.topology),
                            fast_allreduce=args.fast_allreduce,
                            use_kernel=args.kernels,
                            fused_mix=args.fused_mix,
                            **adversary_fields(args))
    src = LMDataSource(cfg, shape, args.clients, seed=args.seed)
    key = jax.random.key(args.seed)
    params = registry.init_model(key, cfg)

    def loss_fn(p, b):
        return registry.loss_fn(p, cfg, b, remat=False)

    mesh = make_client_mesh(args.devices) if args.devices else None
    run_key = jax.random.fold_in(key, 2)
    batches = src.stacked_batches(args.rounds)
    t0 = time.perf_counter()
    # stacked [K, C, ...] token streams -> compiled scan engine;
    # --devices shards the client axis over the mesh, same as the mlp path
    with _profiled(args):
        state, hist, ledger = rounds.run_blade_fl(
            loss_fn, spec, params, batches, run_key, args.rounds,
            stacked=True, mesh=mesh)
        wall_s = _blocked_seconds(state, t0)
    result = {
        "arch": cfg.name, "rounds": args.rounds,
        "loss_curve": [h["global_loss"] for h in hist],
        "chain_valid": ledger.validate_chain(),
        "devices": mesh.devices.size if mesh is not None else 1,
        "fast_allreduce": spec.fast_allreduce,
        "dispatch": dict(rounds.LAST_DISPATCH),
        "wall_s": wall_s,
        **spectral_fields(spec, run_key, args.rounds),
    }
    return RunOutput(result, spec, state, hist, ledger, batches)


def _profiled(args):
    """The profiler session ``--trace-dir`` asks for around the engine
    call, else nothing. The engine's ``blade.*`` spans and stage scopes
    land in its trace (``python3 bench/program_trace.py <dir>`` tabulates
    them)."""
    if not args.trace_dir:
        return contextlib.nullcontext()
    return jax.profiler.trace(args.trace_dir)


def _blocked_seconds(state, t0: float) -> float:
    """Seconds since ``t0`` once the final carry is on the device (the
    engine returns before the device finishes)."""
    jax.block_until_ready(state)
    return time.perf_counter() - t0


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    """Parse and cross-check the CLI (``argv=None`` reads ``sys.argv``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mlp")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--k", type=int, default=5)
    ap.add_argument("--clients", type=int, default=20)
    ap.add_argument("--per-client", type=int, default=2)
    ap.add_argument("--lazy", type=int, default=0)
    ap.add_argument("--sigma2", type=float, default=0.0)
    ap.add_argument("--dp-sigma", type=float, default=0.0)
    ap.add_argument("--t-sum", type=float, default=100.0)
    ap.add_argument("--alpha", type=float, default=1.0)
    ap.add_argument("--beta", type=float, default=10.0)
    ap.add_argument("--eta", type=float, default=0.05)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--topology", default="full",
                    help="Steps 2+5 mixing: full | ring[:k] | random[:p] | "
                         "partial:n | shift[:s] | cluster:g[:a] "
                         "(core/topology.py)")
    ap.add_argument("--schedule", default=None,
                    help="time-varying topology schedule (overrides "
                         "--topology): rotate[:step] | alt[:k[:m]] | "
                         "snr[:period] (core/topology.py Schedules)")
    ap.add_argument("--enrolled", type=int, default=0,
                    help="cohort mode (mlp arch): total enrolled clients; a "
                         "cohort of --cohort participates per round. Devices "
                         "scale with the cohort, not this count — tens of "
                         "thousands run on one CPU (core/rounds.py "
                         "run_blade_fl_cohort)")
    ap.add_argument("--cohort", type=int, default=64,
                    help="active cohort size A per round (with --enrolled)")
    ap.add_argument("--cohort-bias", default="uniform",
                    help="cohort sampling weights: uniform | pareto[:alpha] "
                         "| prefix (core/topology.py CohortSchedule)")
    ap.add_argument("--eval-every", type=int, default=1,
                    help="global-loss eval stride (NaN on skipped rounds)")
    ap.add_argument("--attack", default=None,
                    help="Byzantine attack stage on the pre-broadcast "
                         "params: signflip[:scale] | noise[:sigma2[:scale]] "
                         "| alie[:z] | replace[:boost] (core/attacks.py); "
                         "the first --attackers clients are adversarial")
    ap.add_argument("--attackers", type=int, default=1,
                    help="adversarial client count for --attack (first-M "
                         "convention, like --lazy)")
    ap.add_argument("--robust", default=None,
                    help="Byzantine-robust aggregation override: mean | "
                         "median | trimmed[:t] | geomed[:iters] — order "
                         "statistics over the full broadcast set instead "
                         "of the linear mix; tolerance tier on the mesh "
                         "(docs/architecture.md Robust aggregation)")
    ap.add_argument("--fast-allreduce", action="store_true",
                    help="opt-in psum fast path for dense mixes: ~C/D x less "
                         "data moved, fp32 reassociated — tolerance tier, "
                         "ledger hashes fork from the bitwise engine (see "
                         "docs/architecture.md)")
    ap.add_argument("--kernels", action="store_true",
                    help="run the Steps 3+4 PoW race on the Pallas 2-D "
                         "(clients x nonce-chunk) grid (kernels/pow_hash). "
                         "Bitwise-identical results and ledger; "
                         "run_blade_fl's auto dispatch skips the kernel for "
                         "tiny mining budgets (docs/architecture.md "
                         "Kernel dispatch)")
    ap.add_argument("--fused-mix", action="store_true",
                    help="fuse dense mixes + the digest/divergence "
                         "diagnostics into Pallas kernels (kernels/fedavg): "
                         "one sweep of the broadcast set instead of two. "
                         "Tolerance tier like --fast-allreduce: ledger "
                         "hashes fork deterministically")
    ap.add_argument("--devices", type=int, default=0,
                    help="shard the client axis of the scan engine over this "
                         "many devices (0 = single-device; requires "
                         "clients %% devices == 0; see docs/architecture.md)")
    ap.add_argument("--clusters", type=int, default=0,
                    help="hierarchical two-level layout (mlp arch): a "
                         "('pod', 'data') mesh with one pod row per cluster "
                         "(launch/mesh.py make_cluster_mesh), clients "
                         "sharded over both axes. Defaults --topology to "
                         "cluster:<g> so the mix is the in-cluster mean + "
                         "cluster-ring exchange")
    ap.add_argument("--out-dir", default=None)
    ap.add_argument("--trace-dir", default=None,
                    help="run the engine call under jax.profiler.trace(DIR): "
                         "host spans blade.* and device ops named by stage "
                         "(core/telemetry.py); the first call of a process "
                         "also traces its compile")
    args = ap.parse_args(argv)
    if args.schedule:
        args.topology = args.schedule
    if args.clusters:
        if args.arch != "mlp" or args.enrolled > 0:
            ap.error("--clusters hierarchical mode runs the mlp substrate")
        if args.topology == "full" and not args.schedule:
            args.topology = f"cluster:{args.clusters}"
    if args.enrolled > 0 and args.arch != "mlp":
        ap.error("--enrolled cohort mode runs the mlp substrate")
    return args


def run(args: argparse.Namespace) -> RunOutput:
    """Dispatch parsed arguments to the run path they select."""
    if args.enrolled > 0:
        return run_cohort(args)
    if args.arch == "mlp":
        return run_mlp(args)
    return run_arch_smoke(args)


def main(argv: Optional[List[str]] = None):
    enable_compile_cache()
    print(json.dumps(run(parse_args(argv)).result, indent=1))


if __name__ == "__main__":
    main()
