"""BLADE-FL integrated round (paper §3.1, Fig. 1) as a single compiled step.

One integrated round =
  Step 1  local training: tau full-batch GD iterations per client
          (lazy clients instead plagiarize + add noise — eq. 7)
  Step 2  model broadcast & verification (digital signature -> digest here)
  Step 3  mining: per-client PoW nonce race over a calibrated attempt budget
  Step 4  block validation: winner's block appended (hash-linked)
  Step 5  local updating: every client adopts the aggregate

On the production mesh the client axis C is sharded over 'data' (x 'pod');
local iterations are collective-free across clients (vmap), the aggregate is
one all-reduce, plagiarism is a collective-permute, and the PoW race is an
argmin over the client axis. The same engine drives the paper-scale MLP
simulation (C=20 on one CPU device) and the 10 assigned architectures on the
512-chip dry-run mesh.

Two multi-round driver paths share the single-round engine:

  * ``run_blade_fl_scan`` — the compiled path. All K integrated rounds run
    inside one ``jax.jit(lax.scan)``; the ``RoundState`` carry (params, PRNG
    key, round counter, prev-hash) never leaves the device (donated),
    per-round metrics and block-header fields come back stacked ``[K]``,
    and the host sees exactly one end-of-run transfer.
    ``chain.ledger_from_scan`` then replays the stacked headers through the
    validating ledger, so Steps 2-5 blockchain semantics are preserved
    bit-for-bit against the Python loop. Requires the batch to be a static
    pytree — either one ``[C, ...]`` batch reused every round (the paper's
    full-batch GD) or a ``[K, C, ...]`` stack (``stacked=True``, built by
    ``data/pipeline.py`` sources).
  * the Python loop inside ``run_blade_fl`` — one jitted round per
    iteration, a host sync per metric per round. Kept for arbitrary
    per-round batch *callables* (data that cannot be materialized up front)
    and for ``jit=False`` debugging.

``run_blade_fl`` is the single entry point: it dispatches to the scan engine
whenever the batch argument is a static pytree and falls back to the Python
loop for callables. Both paths return the same ``(state, history, ledger)``.

Stage pipeline + topology architecture
--------------------------------------

The integrated round is composed from five named stage functions, each built
once per ``RoundSpec`` by its ``make_*`` factory and individually jittable /
testable:

  ``local_train``   Step 1: tau collective-free GD iterations per client
  ``perturb``       Step 1 (lazy, eq. 7) + §6 DP noise on the broadcast set
  ``communicate``   Steps 2+5: header digest, optional plagiarism screening,
                    divergence diagnostic, then the topology mix
  ``mine``          Steps 3+4: PoW race over the client axis + hash link
  ``finalize``      metrics assembly, strided global-loss eval, next carry

``make_integrated_round`` is now just the composition of those stages — add
a scenario by swapping a stage, not by editing a 70-line closure.

Each stage function runs under ``jax.named_scope`` of its stage, and the
drivers open ``blade.*`` host spans around their phases (plan, init,
dispatch, fetch, history, ledger; cohort, data and store per cohort round),
so a profiler trace splits device time by stage and idle time by host
phase (``core/telemetry.py``).

The communication pattern of Steps 2+5 is pluggable via
``RoundSpec.topology`` (``core/topology.py``): a ``Topology`` yields a
row-stochastic mixing matrix ``W [C, C]`` per round and the communicate
stage applies ``aggregation.mix(params, W)``. The default ``FullMesh`` — the
paper's "broadcast to all, everyone adopts the aggregate" — short-circuits
to ``aggregation.fedavg`` so the baseline stays bit-for-bit identical to the
pre-topology engine; ``Ring``, ``RandomGraph`` (per-round i.i.d. link
dropout) and ``PartialParticipation`` open the partial-connectivity regimes
of arXiv:2012.02044 / arXiv:2406.00752. Both driver paths derive the
per-round graph from the same fold of the carried PRNG key, so scan and
Python loop stay exactly equivalent for every topology.

Time-varying ``Schedule`` topologies (gossip rotations, epoch-alternating
overlays, SNR link-quality fading) compile into the same single scan with
no retrace across K — ``topology.resolve_mix_plan`` is the single surface
that picks the executor mode ``make_communicate`` runs — and
``RoundSpec.data_weights`` threads |D_i| row reweighting
into every dense mix. ``core/spectral.py`` turns any topology/schedule
into its consensus-rate diagnostic (1 - |lambda_2(W)|, ergodic gap).

``RoundSpec.eval_every`` strides the in-scan global-loss eval: rounds where
``(round_idx + 1) % eval_every != 0`` skip the eval vmap via ``lax.cond``
and report NaN, so the history keeps a static ``[K]`` layout. The default
``eval_every=1`` keeps the exact pre-stride computation (no cond in the
jaxpr). Both drivers force an eval on the LAST round even when
``K % eval_every != 0``, so ``history[-1]["global_loss"]`` is always
finite and best-K selection never compares against NaN.

Client-sharded execution (mesh + plan)
--------------------------------------

``run_blade_fl_scan(..., mesh=..., plan=...)`` runs the SAME K-round scan
client-sharded over a device mesh: the whole ``lax.scan`` executes inside a
``shard_map`` whose carry layout comes from
``sharding.plans.scan_carry_plan`` — params and batch split along the
client axis over the plan's mesh axes, PRNG key / round counter / prev-hash
(the ledger link) replicated — so the donated carry never leaves the
devices for the whole horizon and the end-of-run metrics transfer is still
the only host sync. Every stage factory takes ``axis_name``/``n_shards``:
with ``axis_name=None`` (the default) each stage is exactly the
single-device computation; with a mesh axis, per-client work (local GD, the
PoW race) runs on local client blocks and every cross-client step goes
through the collectives in ``core/aggregation`` — the mix via the
``MixLowering`` the topology advertises, the digest / divergence /
global-loss reductions via all-gather + replicated full-width math. That
discipline (never psum partial fp32 sums) is what makes the sharded engine
bit-for-bit equal to the single-device scan — same params, same metrics,
same hash-linked ledger — as ``tests/test_multidevice_scan.py`` asserts on
a 4-device host mesh for every shipped topology. (The bitwise claim is for
a fixed backend; CPU↔TPU still differ, and TPU tiling may reorder
per-client matmuls.)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import (aggregation, attacks as attacks_lib, chain,
                        detection, dp as dp_lib, lazy as lazy_lib, mining,
                        telemetry, topology as topology_lib)
from repro.sharding import plans as plans_lib

LossFn = Callable[[Any, Any], Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]]


@dataclasses.dataclass(frozen=True)
class RoundSpec:
    """Static configuration of one integrated round."""
    n_clients: int
    tau: int                    # local GD iterations (eq. 3)
    eta: float                  # learning rate
    n_lazy: int = 0
    sigma2: float = 0.0         # lazy artificial-noise variance
    dp_sigma: float = 0.0       # DP Gaussian mechanism (§6)
    mine_attempts: int = 1024   # calibrated from beta (allocation.mining_iterations)
    difficulty_bits: int = 8
    microbatches: int = 1       # grad accumulation inside each local iteration
    eval_global_loss: bool = True
    # eval stride: compute global_loss only on rounds with
    # (round_idx + 1) % eval_every == 0 (NaN elsewhere); 1 = every round.
    eval_every: int = 1
    # Steps 2+5 communication pattern (core/topology.py). FullMesh is the
    # paper baseline and dispatches to aggregation.fedavg bit-for-bit.
    # Schedules (time-varying topologies) are topologies too — the
    # communicate stage compiles their period into the scan.
    topology: topology_lib.Topology = topology_lib.FullMesh()
    # |D_i| data sizes (length n_clients); reweight each mix row as
    # W'[i, j] ∝ W[i, j] * data_weights[j] (aggregation.mix weights). A
    # tuple so the spec stays hashable; None = unweighted (paper baseline).
    data_weights: Optional[Tuple[float, ...]] = None
    # beyond-paper (§8 future work): flag near-duplicate broadcast models
    # before aggregation (core/detection.py); adds n_suspects to metrics.
    detect_lazy: bool = False
    detect_threshold: float = 0.2
    # opt-in fast path: lower dense mixes to true in-mesh psums of locally
    # pre-weighted rows (aggregation.mix_psum / mix_psum_dense) and finish
    # the digest/divergence diagnostics with psums instead of the broadcast
    # gather. Moves ~C/D× less data for FullMesh but REASSOCIATES fp32:
    # results hold to the tolerance tier (rtol ≈ 1e-5 over a K-round run,
    # tests/test_fast_allreduce.py), not the bitwise contract, and the
    # sharded ledger hashes fork from the single-device chain (both chains
    # still self-validate). Default False keeps every path bit-for-bit.
    fast_allreduce: bool = False
    # Pallas kernel tier (docs/architecture.md §Kernel dispatch):
    #   use_kernel — Steps 3+4 PoW race runs on the kernels/pow_hash 2-D
    #     (clients × nonce-chunk) grid instead of the per-client
    #     vmap(fori_loop). Bitwise-identical (best_hash, best_nonce, winner,
    #     ledger hashes) at every (mine_attempts, mine_chunk) — same budget
    #     masking, same client_salt nonce spaces — so the ledger does NOT
    #     fork. run_blade_fl's auto dispatch downgrades it below
    #     _KERNEL_MIN_ATTEMPTS where grid overhead beats the fori_loop.
    #   fused_mix — dense mixes contract through the fused kernels/fedavg
    #     row-block matmul (mix_gather / mix_psum_dense use_kernel=True) and
    #     the digest + divergence diagnostics share ONE fused sweep of the
    #     broadcast set. Tolerance tier like fast_allreduce: tile-partial
    #     fp32 sums reassociate the digest, so ledger hashes fork
    #     deterministically (both chains still self-validate).
    #   kernel_interpret — None runs Pallas natively on TPU backends and in
    #     interpret mode everywhere else; tests pin True for the CPU
    #     equivalence sweeps.
    #   mine_chunk — nonce chunk (fori_loop) / grid tile (kernel) size,
    #     shared so both paths charge identical budget masks; results are
    #     chunk-invariant (running min + first-tie argmin == full argmin).
    use_kernel: bool = False
    fused_mix: bool = False
    kernel_interpret: Optional[bool] = None
    mine_chunk: int = 1024
    # Sparse mix dispatch (docs/architecture.md §Sparse lowering):
    #   None (auto) — GATHER-kind topologies whose exported SparseLowering
    #     has padded max degree ≪ C (max_degree * topology
    #     .SEGMENT_DEGREE_FACTOR <= n_clients) reroute their mix through
    #     aggregation.mix_segment —
    #     O(C·deg) gather + segment_sum instead of the dense O(C²) matmul.
    #     ExplicitSparse topologies (SEGMENT kind) always mix here. Every
    #     shipped small-C config keeps its dense path (and its bits).
    #   True — force the segment mix (ValueError when the topology exports
    #     no static sparse form). Sparse-vs-dense agreement is tolerance
    #     tier (segment_sum's scatter order replaces the matmul's
    #     contraction order), so forcing it forks ledger hashes
    #     deterministically, like fast_allreduce.
    #   False — never, even for ExplicitSparse (its small-C dense fallback).
    sparse_mix: Optional[bool] = None
    # Byzantine attack stage (core/attacks.py; CLI --attack/--attackers):
    # a pure keyed transform on the pre-broadcast params — the adversary's
    # first-M clients replace their broadcasts (sign-flip, scaled noise,
    # ALIE, model replacement) right after the perturb stage, so the
    # digest / detection / mix all see what a real adversary publishes.
    # None (no attack) is the exact baseline computation.
    attack: Optional[attacks_lib.Attack] = None
    # Byzantine-robust aggregation (docs/architecture.md §Robust
    # aggregation; CLI --robust): override the topology's linear mix with a
    # robust consensus reducer over the full broadcast set — "median" |
    # "trimmed[:t]" | "geomed[:iters]" (topology.parse_robust; "mean"/None
    # keep the linear mix). Breakdown point ⌊(C-1)/2⌋ for median/geomed, t
    # per tail for trimmed — versus 0 for every linear mix. Robust
    # reductions are not psum-associative, so sharded execution agrees with
    # single-device to the TOLERANCE tier (rtol ≈ 1e-5), not bitwise, and
    # the linear-only flags (fast_allreduce / fused_mix / sparse_mix=True /
    # data_weights) are rejected by the resolver.
    robust_agg: Optional[str] = None


class RoundState(NamedTuple):
    params: Any                 # pytree, leading client axis C
    key: jax.Array
    round_idx: jnp.ndarray      # int32
    prev_hash: jnp.ndarray      # uint32


def init_state(params_single, key, n_clients: int) -> RoundState:
    return RoundState(
        params=aggregation.replicate(params_single, n_clients),
        key=key,
        round_idx=jnp.int32(0),
        prev_hash=jnp.uint32(chain.GENESIS_HASH),
    )


def _microbatched_grad(loss_fn: LossFn, n_mb: int):
    """grad of the mean loss over n_mb microbatches (axis-0 split), with
    per-microbatch remat so activation memory is O(batch / n_mb)."""

    def split(batch):
        return jax.tree.map(
            lambda x: x.reshape((n_mb, x.shape[0] // n_mb) + x.shape[1:]), batch)

    @functools.partial(jax.checkpoint, static_argnums=())
    def one_mb(params, mb):
        loss, _ = loss_fn(params, mb)
        return loss

    def grad_fn(params, batch):
        mbs = split(batch)

        def body(acc, mb):
            l, g = jax.value_and_grad(one_mb)(params, mb)
            return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None

        zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, params))
        (loss, grads), _ = jax.lax.scan(body, zero, mbs)
        scale = 1.0 / n_mb
        return loss * scale, jax.tree.map(lambda g: g * scale, grads)

    return grad_fn


# fold_in salt deriving the topology key from k_dp — a fresh stream for
# stochastic topologies that leaves the lazy/DP streams (and therefore the
# FullMesh baseline results) untouched.
_TOPOLOGY_SALT = 0x746F706F  # "topo"


def topology_keys(key, n_rounds: int):
    """Host-side replica of the engine's per-round topology PRNG stream.

    Returns the list of ``k_topo`` keys rounds ``0..n_rounds-1`` fold their
    stochastic graphs from, given the run key passed to the drivers — the
    same split chain the round body performs, so diagnostics
    (``core/spectral.py``) can reconstruct the EXACT per-round mixing
    matrices a stochastic topology/schedule used in a run."""
    out = []
    for _ in range(int(n_rounds)):
        key, _k_lazy, k_dp = jax.random.split(key, 3)
        out.append(jax.random.fold_in(k_dp, _TOPOLOGY_SALT))
    return out


def make_local_train(loss_fn: LossFn, spec: RoundSpec, n_shards: int = 1):
    """Step 1 stage factory: tau local GD iterations per client, eq. 3.

    Returns ``local_train(params, batch) -> (params, local_losses)``. Both
    inputs carry a leading client axis — the full ``C`` single-device, or
    this shard's ``C / n_shards`` block inside ``shard_map`` — and the stage
    is collective-free either way: clients never talk during Step 1, which
    is exactly why the client axis shards cleanly. Each iteration is one
    full-batch ``value_and_grad`` per client (``spec.microbatches > 1``
    splits it into remat'd grad-accumulation microbatches); the carried
    per-client loss is the one observed at the last iteration (free —
    ``value_and_grad`` computes it anyway)."""
    if spec.microbatches > 1:
        grad_fn = _microbatched_grad(loss_fn, spec.microbatches)
    else:
        def grad_fn(params, batch):
            (loss, _), grads = jax.value_and_grad(
                lambda p, b: loss_fn(p, b), has_aux=True)(params, batch)
            return loss, grads

    per_client_grad = jax.vmap(grad_fn)
    n_local = spec.n_clients // n_shards

    @telemetry.stage("local_train")
    def local_train(params, batch):
        def local_iter(_, carry):
            p, _ = carry
            # pin the iteration inputs: without this, XLA fuses the
            # batch-mean inside value_and_grad with whatever surrounds the
            # loop (the scan engine peels its first iteration), and the
            # materialized per-client loss drifts a ULP between the scan
            # and per-round engines on lane-vectorized CPU builds
            p, b = jax.lax.optimization_barrier((p, batch))
            losses, grads = per_client_grad(p, b)
            p = jax.tree.map(lambda w, g: w - spec.eta * g.astype(w.dtype),
                             p, grads)
            return (p, losses)

        loss0 = jnp.zeros((n_local,), jnp.float32)
        return jax.lax.fori_loop(0, spec.tau, local_iter, (params, loss0))

    return local_train


def make_perturb(spec: RoundSpec, axis_name=None, n_shards: int = 1):
    """Step 1 tail stage factory: what each client broadcasts instead of its
    honest model.

    Returns ``perturb(params, k_lazy, k_dp)``: lazy clients plagiarize their
    source client's fresh model and add N(0, sigma^2) disguise noise
    (eq. 7), then every client optionally adds §6 DP Gaussian noise to the
    model it is about to broadcast. With ``n_lazy == 0`` and
    ``dp_sigma == 0`` the stage is the identity.

    Sharded, plagiarism is a cross-shard gather (a lazy client's source may
    live on another device) and the noise draws must equal the
    single-device ones — so the stage all-gathers the client axis, applies
    the IDENTICAL full-``[C, ...]`` transform (same per-leaf key split, same
    noise shapes — bitwise the same draws), and slices this shard's rows
    back out. Cost: one params gather per round, only when the stage is
    active — and that gathered tree is returned as ``full`` (None when the
    stage was a no-op) so the communicate stage reuses it instead of
    re-gathering the model it just materialized."""
    active = spec.n_lazy > 0 or spec.dp_sigma > 0.0

    @telemetry.stage("perturb")
    def perturb(params, k_lazy, k_dp):
        if not active:
            return params, None
        full = aggregation.client_all_gather(params, axis_name)
        full = lazy_lib.apply_lazy(full, k_lazy, spec.n_clients,
                                   spec.n_lazy, spec.sigma2)
        full = dp_lib.privatize(full, k_dp, spec.dp_sigma)
        return aggregation.client_local_rows(full, axis_name, n_shards), full

    return perturb


# fold_in salt deriving the attack key from k_dp — its own stream (disjoint
# from _TOPOLOGY_SALT) so adding an attack never perturbs the lazy/DP/
# topology draws, and an attack-free spec is the exact baseline.
_ATTACK_SALT = 0x6174746B  # "attk"


def make_attack(spec: RoundSpec, axis_name=None, n_shards: int = 1):
    """Byzantine attack stage factory (core/attacks.py), composed right
    after ``perturb``: what the adversary's first-M clients broadcast
    instead of their (possibly lazy/DP-perturbed) models.

    Returns ``attack(params, k_dp, full=None) -> (params, full)`` with the
    same gather discipline as ``make_perturb``: sharded, it all-gathers the
    client axis — or reuses the perturb stage's ``full`` tree when that
    stage already gathered — applies the IDENTICAL full-``[C, ...]`` keyed
    transform (``Attack.apply``; the attack key folds from ``k_dp`` with
    :data:`_ATTACK_SALT`, so the draws match bitwise across engines), and
    slices the local rows back out. The transformed ``full`` is returned so
    the communicate stage's digest / detection / mix see the post-attack
    broadcast set without re-gathering. ``spec.attack=None`` (or zero
    attackers) is the identity and adds nothing to the trace."""
    atk = spec.attack
    active = atk is not None and atk.active
    if active:
        atk._validate(spec.n_clients)   # fail at build time, not in-trace

    @telemetry.stage("attack")
    def attack(params, k_dp, full=None):
        if not active:
            return params, full
        if full is None:
            full = aggregation.client_all_gather(params, axis_name)
        k_att = jax.random.fold_in(k_dp, _ATTACK_SALT)
        full = atk.apply(full, k_att, spec.n_clients)
        return aggregation.client_local_rows(full, axis_name, n_shards), full

    return attack


# Back-compat alias: the auto sparse-mix crossover now lives with the rest
# of the mix dispatch in core/topology.py (resolve_mix_plan).
_SEGMENT_DEGREE_FACTOR = topology_lib.SEGMENT_DEGREE_FACTOR


def segment_lowering(spec: RoundSpec
                     ) -> Optional[topology_lib.SparseLowering]:
    """The SparseLowering the communicate stage will mix through, or None
    when this spec mixes densely (see ``RoundSpec.sparse_mix``). Thin view
    over ``topology.resolve_mix_plan`` — the mix decisions live there, this
    just exposes the sparse payload (|D_i| reweighting already folded in)."""
    return topology_lib.resolve_mix_plan(spec).sparse


def _mesh_axes_of(axis_name, n_shards: int, axis_sizes=()):
    """``resolve_mix_plan``'s ``mesh_axes`` from a stage factory's
    ``(axis_name, n_shards, axis_sizes)``: ``None`` single-device, else
    ``((name, extent), ...)``. When per-axis extents are unknown (a caller
    that predates ``ScanCarryPlan.axis_sizes``) only the total shard count
    is attributed — which is all the resolver consumes; the collectives
    read real extents from the mesh at trace time."""
    if axis_name is None:
        return None
    names = ((axis_name,) if isinstance(axis_name, str)
             else tuple(axis_name))
    sizes = tuple(int(s) for s in axis_sizes)
    if len(sizes) != len(names):
        sizes = (1,) * (len(names) - 1) + (int(n_shards),)
    return tuple(zip(names, sizes))


def make_communicate(spec: RoundSpec, axis_name=None, n_shards: int = 1,
                     axis_sizes=()):
    """Steps 2+5 stage factory: ``(params, prev_params, k_topo, round_idx)
    -> (mixed_params, digest, divergence, extra_metrics)``.

    Header digest and optional plagiarism screening run on the broadcast set
    (every client sees every *delivered* model; the digest always covers the
    full broadcast so the hash chain is topology-independent), divergence is
    the pre-mix client spread (delta diagnostic, Def. 1), then the
    topology's row-stochastic ``W`` mixes the models — through the
    executor mode a single :func:`~repro.core.topology.resolve_mix_plan`
    call picks (FedAvg mean, halo ``collective_permute`` window, cluster
    two-level exchange, sparse segment-sum, psum tier, or the dense
    all-gather matmul). This factory is a thin executor over that
    :class:`~repro.core.topology.MixPlan` — it holds NO lowering-kind
    logic of its own, so ``dispatch_plan``'s report and the traced mix
    cannot drift.

    Sharded, the digest / divergence / detection diagnostics all-gather the
    broadcast set and run the identical full-width math (the digest folds a
    cross-client fp32 sum per leaf — partial psums would change its bits and
    with it every downstream hash link); the FullMesh and gather mixes reuse
    that same gathered tree, so diagnostics add no extra collective. When
    the perturb stage already gathered the broadcast set, its ``full`` tree
    is accepted (re-barriered, so the digest reduce stays fusion-pinned)
    instead of gathering twice.

    Schedules compile into the traced body with no retrace across K: a
    deterministic schedule's matrices become a static ``[P, C, C]`` table
    indexed by the traced round counter; a :class:`GossipRotation`'s
    round-dependent offsets become a ``lax.switch`` over P static permute
    branches (``mix_shift_halo`` — its linearized permutes cover compound
    ``('pod','data')`` client axes too — or rolls off-mesh);
    stochastic schedules draw their phase graph from ``k_topo`` like
    ``RandomGraph``. ``spec.data_weights`` (|D_i| row reweighting) rides the
    dense-matrix paths — permute lowerings bake uniform window weights, so a
    weighted spec routes ``neighbor_permute`` topologies through their
    matrices instead.

    ``spec.fast_allreduce`` reroutes the DENSE kinds onto the reassociating
    psum tier: a ``psum`` lowering (FullMesh / uniform-row topologies) mixes
    via ``aggregation.mix_psum`` (one model-sized psum, ~C/D× less data), a
    ``gather`` kind via ``aggregation.mix_psum_dense`` (local column-block
    matmul + psum), and the digest / divergence diagnostics are finished
    with psums of local partials instead of the broadcast-set gather — the
    fast round never materializes the full client axis (except for lazy
    detection, which keeps its exact gathered math). Permute lowerings are
    already O(window) and stay bitwise under the flag.

    ``spec.fused_mix`` routes the dense mixes through the fused Pallas
    row-block matmul (``aggregation.mix_gather`` / ``mix_psum_dense`` with
    ``use_kernel=True``) and computes digest + divergence in ONE fused sweep
    of the broadcast set (``kernels/fedavg.digest_divergence_tree``) instead
    of two jnp traversals. Tolerance tier, same contract as
    ``fast_allreduce``: the fp32 reassociation forks the ledger hashes
    deterministically. FullMesh's all-reduce mix and the permute lowerings
    are untouched (one mean / O(window) moves — nothing for a matmul kernel
    to win), as are the psum'd diagnostics of the fast_dense path (the fused
    sweep needs the client axis resident, psum partials don't)."""
    topo = spec.topology
    plan = topology_lib.resolve_mix_plan(
        spec, _mesh_axes_of(axis_name, n_shards, axis_sizes))
    mode = plan.mode
    # plan payloads → device constants baked into the trace. Edge lists /
    # weight rows are static host arrays, so no retrace across K rounds.
    weights = (jnp.asarray(plan.weights, jnp.float32)
               if plan.weights is not None else None)
    psum_row = (jnp.asarray(plan.psum_row, jnp.float32)
                if plan.psum_row is not None else None)
    seg = plan.sparse
    seg_idx = seg.neighbor_idx if seg is not None else None
    seg_w = seg.edge_w if seg is not None else None

    def mix_scheduled_shifts(params, phase):
        """Rotation dispatch: lax.switch over one static branch per phase."""
        if axis_name is None:
            return jax.lax.switch(
                phase,
                [lambda p, o=o: aggregation.mix_rolls(p, o, plan.weight)
                 for o in plan.offsets_table], params)
        return jax.lax.switch(
            phase, [lambda p, o=o: aggregation.mix_shift_halo(
                p, o, plan.weight, axis_name) for o in plan.offsets_table],
            params)

    @telemetry.stage("communicate")
    def communicate(params, prev_params, k_topo, round_idx, full=None):
        if plan.fast_diagnostics:
            # tolerance tier: psum'd diagnostics + mix, no broadcast gather.
            # The digest reassociates fp32 under shard_map, so the ledger
            # hashes fork from the bitwise engine (documented + tested).
            digest = mining.digest_tree(params, axis_name=axis_name)
            divergence = aggregation.client_divergence_psum(
                params, axis_name, n_shards)
            extra = {}
            if spec.detect_lazy:
                det_full = (aggregation.client_all_gather(params, axis_name)
                            if full is None
                            else jax.lax.optimization_barrier(full))
                prev_full = aggregation.client_all_gather(prev_params,
                                                          axis_name)
                suspects, _ = detection.detect_lazy_round(
                    det_full, prev_full, threshold_frac=spec.detect_threshold)
                extra["n_suspects"] = jnp.sum(suspects).astype(jnp.int32)
            if mode == topology_lib.EXEC_PSUM:
                params = aggregation.mix_psum(params, psum_row,
                                              axis_name=axis_name,
                                              n_shards=n_shards)
            else:
                w = topo.matrix(spec.n_clients, key=k_topo,
                                round_idx=round_idx)
                params = aggregation.mix_psum_dense(
                    params, w, weights, axis_name=axis_name,
                    n_shards=n_shards, use_kernel=plan.use_kernel,
                    interpret=spec.kernel_interpret)
            return params, digest, divergence, extra
        if full is None:
            full = aggregation.client_all_gather(params, axis_name)
        else:
            full = jax.lax.optimization_barrier(full)
        extra = {}
        if spec.fused_mix:
            # one fused sweep of the broadcast set computes digest AND
            # divergence (kernels/fedavg.digest_divergence_tree) — the jnp
            # path below traverses it twice. Tolerance tier: the tile-partial
            # leaf sums fork the digest (and the ledger) deterministically.
            from repro.kernels.fedavg import ops as fedavg_ops
            digest, divergence = fedavg_ops.digest_divergence_tree(
                full, interpret=spec.kernel_interpret)
        else:
            digest = mining.digest_tree(full)
            divergence = aggregation.client_divergence(full)
        if spec.detect_lazy:
            prev_full = aggregation.client_all_gather(prev_params, axis_name)
            suspects, _ = detection.detect_lazy_round(
                full, prev_full, threshold_frac=spec.detect_threshold)
            extra["n_suspects"] = jnp.sum(suspects).astype(jnp.int32)
        if mode == topology_lib.EXEC_SEGMENT:
            # sparse segment mix: O(C·deg) gather + segment_sum over the
            # broadcast set (reuses the diagnostics gather); |D_i| weights
            # were folded into seg_w by the resolver
            params = aggregation.mix_segment(params, seg_idx, seg_w,
                                             axis_name=axis_name,
                                             n_shards=n_shards, full=full)
        elif mode == topology_lib.EXEC_FEDAVG:
            params = aggregation.mix_all_reduce(params, weights,
                                                axis_name=axis_name,
                                                n_shards=n_shards, full=full)
        elif mode == topology_lib.EXEC_SHIFT_TABLE:
            phase = jnp.mod(jnp.asarray(round_idx, jnp.int32), plan.period)
            params = mix_scheduled_shifts(params, phase)
        elif mode == topology_lib.EXEC_CLUSTER:
            params = aggregation.mix_cluster(params, plan.n_clusters,
                                             plan.inter_weight, axis_name,
                                             n_shards=n_shards, full=full)
        elif mode == topology_lib.EXEC_HALO:
            params = aggregation.mix_neighbor_halo(params, plan.offsets,
                                                   plan.weight, axis_name)
        elif mode == topology_lib.EXEC_SHIFT_HALO:
            params = aggregation.mix_shift_halo(params, plan.offsets,
                                                plan.weight, axis_name)
        elif mode == topology_lib.EXEC_MEDIAN:
            params = aggregation.mix_median(params, axis_name=axis_name,
                                            n_shards=n_shards, full=full)
        elif mode == topology_lib.EXEC_TRIMMED:
            params = aggregation.mix_trimmed(params, plan.trim,
                                             axis_name=axis_name,
                                             n_shards=n_shards, full=full)
        elif mode == topology_lib.EXEC_GEOMED:
            params = aggregation.mix_geomedian(params, plan.robust_iters,
                                               axis_name=axis_name,
                                               n_shards=n_shards, full=full)
        else:
            w = topo.matrix(spec.n_clients, key=k_topo, round_idx=round_idx)
            params = aggregation.mix_gather(params, w, weights,
                                            axis_name=axis_name,
                                            n_shards=n_shards, full=full,
                                            use_kernel=plan.use_kernel,
                                            interpret=spec.kernel_interpret)
        return params, digest, divergence, extra

    communicate.plan = plan
    return communicate


def make_mine(spec: RoundSpec, axis_name=None, n_shards: int = 1):
    """Steps 3+4 stage factory: the PoW race and the hash link.

    Returns ``mine(prev_hash, digest, round_idx) -> (mine_metrics,
    new_hash)``. Every client searches its own salted nonce space over the
    calibrated attempt budget (eq. 1 accounting); the winner is the argmin
    hash across the client axis — the decentralized "first to find" — and
    the winner's nonce seals the new block header onto ``prev_hash``.

    Sharded, each shard races only its local client block (ids offset by
    the shard index so the global salt assignment is unchanged), then the
    per-client best hashes/nonces — uint32, so gather order cannot perturb
    them — are all-gathered for the replicated argmin.

    ``spec.use_kernel`` dispatches the race to the Pallas 2-D
    (clients × nonce chunks) grid (``kernels/pow_hash``) instead of the
    per-client ``vmap(fori_loop)``: same ``client_salt`` nonce spaces, same
    tail-chunk budget mask charging exactly ``mine_attempts`` nonces, so
    every output — and therefore the hash-linked ledger — is bitwise
    identical to the fori_loop path at any ``(mine_attempts, mine_chunk)``
    (tests/test_kernels.py pins this including non-divisible budgets)."""
    n_local = spec.n_clients // n_shards
    if spec.use_kernel:
        from repro.kernels.pow_hash import ops as pow_ops

    @telemetry.stage("mine")
    def mine(prev_hash, digest, round_idx):
        client_ids = jnp.arange(n_local, dtype=jnp.uint32)
        if axis_name is not None:
            shard = aggregation.client_shard_index(axis_name).astype(jnp.uint32)
            client_ids = client_ids + shard * jnp.uint32(n_local)
        nonce_offset = round_idx.astype(jnp.uint32) * jnp.uint32(1 << 20)
        if spec.use_kernel:
            best_h, best_n = pow_ops.pow_race(
                prev_hash, digest, client_ids, spec.mine_attempts,
                nonce_offset=nonce_offset, chunk=spec.mine_chunk,
                interpret=spec.kernel_interpret)
        else:
            search = jax.vmap(
                lambda cid: mining.pow_search(
                    prev_hash, digest, cid, spec.mine_attempts,
                    nonce_offset=nonce_offset, chunk=spec.mine_chunk))
            best_h, best_n = search(client_ids)
        best_h = aggregation.client_all_gather(best_h, axis_name)
        best_n = aggregation.client_all_gather(best_n, axis_name)
        winner = mining.winner_of(best_h)
        solved = best_h[winner] <= mining.difficulty_threshold(spec.difficulty_bits)
        new_hash = mining.mix_hash(prev_hash, digest, best_n[winner])
        metrics = {
            "winner": winner.astype(jnp.int32),
            "pow_hash": best_h[winner],
            "nonce": best_n[winner],
            "solved": solved,
        }
        return metrics, new_hash

    return mine


def make_finalize(loss_fn: LossFn, spec: RoundSpec, axis_name=None,
                  n_rounds: Optional[int] = None):
    """Closing stage factory: strided global-loss eval + the next carry.

    Returns ``finalize(state, params, key, new_hash, batch, metrics) ->
    (RoundState, metrics)``. The global loss is the mean over clients of
    each post-mix model's loss on its own shard, NaN-masked by the
    ``eval_every`` stride: with ``eval_every == 1`` the eval is
    unconditional — the exact pre-stride computation, no cond in the jaxpr
    — otherwise a ``lax.cond`` skips the eval vmap on rounds where
    ``(round_idx + 1) % eval_every != 0`` and reports a NaN row, keeping
    the metrics pytree static for ``lax.scan`` (the history layout stays
    ``[K]``; downstream consumers take the last *finite* entry).

    ``n_rounds`` (the horizon, when the driver knows it) forces an eval on
    the LAST round even when ``K % eval_every != 0`` — otherwise the run
    would end on a NaN ``global_loss`` and poison every downstream
    best-K/`final_loss` consumer (the sweep_k / bench_topology selection
    bug this closes).

    The stage emits the PER-CLIENT eval vector ``[C]`` (sharded: local
    blocks all-gathered, so every engine sees the identical vector); the
    drivers reduce it to the scalar ``history[k]["global_loss"]`` with the
    same host-side ``np.mean``. The final mean deliberately does NOT run on
    device: a ``[C] -> scalar`` fp32 reduce is vectorized with lane-partial
    accumulators whose association shifts with XLA fusion context, which is
    exactly the kind of last-ulp drift the sharded engine's bit-for-bit
    contract forbids."""

    def eval_glosses(params, batch):
        # The input barrier bounds the eval subgraph identically in the
        # sharded and single-device programs: the per-client loss ends in a
        # full reduce to a scalar whose XLA:CPU association would otherwise
        # depend on what the forward pass fuses with.
        params, batch = jax.lax.optimization_barrier((params, batch))
        glosses = jax.vmap(lambda p, b: loss_fn(p, b)[0])(params, batch)
        return aggregation.client_all_gather(glosses, axis_name)

    @telemetry.stage("finalize")
    def finalize(state, params, key, new_hash, batch, metrics):
        if spec.eval_global_loss:
            if spec.eval_every <= 1:
                metrics["global_loss"] = eval_glosses(params, batch)
            else:
                is_eval = (state.round_idx + 1) % spec.eval_every == 0
                if n_rounds is not None:
                    is_eval = jnp.logical_or(
                        is_eval, state.round_idx + 1 == n_rounds)
                metrics["global_loss"] = jax.lax.cond(
                    is_eval, lambda: eval_glosses(params, batch),
                    lambda: jnp.full((spec.n_clients,), jnp.nan, jnp.float32))
        new_state = RoundState(params=params, key=key,
                               round_idx=state.round_idx + 1,
                               prev_hash=new_hash)
        return new_state, metrics

    return finalize


def make_integrated_round(loss_fn: LossFn, spec: RoundSpec, axis_name=None,
                          n_shards: int = 1,
                          n_rounds: Optional[int] = None,
                          axis_sizes=()):
    """Build the jittable round function: (RoundState, batch) -> (RoundState, metrics).

    ``batch`` leaves have leading client axis [C, local_batch, ...]. The
    round is the composition of the stage factories above (local_train,
    perturb, the optional Byzantine attack stage, communicate, mine,
    finalize); swap a stage to express a new scenario.

    With ``axis_name`` set (a mesh axis name or tuple of names) the round
    body is written for ``shard_map``: the leading axis of params/batch is
    this shard's ``C / n_shards`` client block and cross-client steps use
    collectives (see each stage factory). ``axis_name=None`` is the exact
    single-device computation. ``n_rounds`` (when the driver knows the
    horizon) lets the finalize stage force a global-loss eval on the last
    round regardless of the ``eval_every`` stride. ``axis_sizes`` (the
    mesh's per-axis extents, ``ScanCarryPlan.axis_sizes``) refines the mix
    resolution on compound client axes; when omitted only the total
    ``n_shards`` is attributed."""
    local_train = make_local_train(loss_fn, spec, n_shards)
    perturb = make_perturb(spec, axis_name, n_shards)
    attack = make_attack(spec, axis_name, n_shards)
    communicate = make_communicate(spec, axis_name, n_shards,
                                   axis_sizes=axis_sizes)
    mine = make_mine(spec, axis_name, n_shards)
    finalize = make_finalize(loss_fn, spec, axis_name, n_rounds)

    def round_fn(state: RoundState, batch) -> Tuple[RoundState, Dict[str, jnp.ndarray]]:
        key, k_lazy, k_dp = jax.random.split(state.key, 3)
        k_topo = jax.random.fold_in(k_dp, _TOPOLOGY_SALT) \
            if spec.topology.stochastic else None

        params, local_losses = local_train(state.params, batch)
        params, broadcast_full = perturb(params, k_lazy, k_dp)
        params, broadcast_full = attack(params, k_dp, full=broadcast_full)
        params, digest, divergence, extra = communicate(
            params, state.params, k_topo, state.round_idx,
            full=broadcast_full)
        mine_metrics, new_hash = mine(state.prev_hash, digest, state.round_idx)

        # per-client [C] vector; the drivers np.mean it on host — a device
        # `jnp.mean` here is a fusion-context-sensitive scalar reduce over
        # the gathered axis (same discipline as global_loss, RL301)
        local_losses = aggregation.client_all_gather(local_losses, axis_name)
        metrics = {"local_loss": local_losses, **mine_metrics,
                   "digest": digest, "divergence": divergence, **extra}
        return finalize(state, params, key, new_hash, batch, metrics)

    return round_fn


# How many times each compiled multi-round runner was (re)traced. The
# equivalence test asserts this stays flat in K — the whole point of the
# scan engine is ONE trace for the full horizon, not one per round.
TRACE_COUNTS: Dict[str, int] = {"scan_runner": 0}

# Problem-size crossovers for run_blade_fl's automatic dispatch, measured on
# XLA:CPU (benchmarks/bench_rounds.py; docs/architecture.md §Kernel
# dispatch). Micro-sims at or below BOTH micro bounds run faster on the
# per-round driver than nested in the scan's while loop, and a PoW grid
# under _KERNEL_MIN_ATTEMPTS costs more in kernel launch/grid overhead than
# the fori_loop it replaces.
_MICRO_MAX_CLIENTS = 4
_MICRO_MAX_SAMPLES = 32
_KERNEL_MIN_ATTEMPTS = 512

# The last decision run_blade_fl's auto dispatch took (driver/pow/mix +
# reason) — module-level like TRACE_COUNTS so benchmarks can record the
# chosen lowering in their CSV notes without re-deriving it.
LAST_DISPATCH: Dict[str, str] = {}


def dispatch_plan(spec: RoundSpec, batches, n_rounds: int, *,
                  jit: bool = True, stacked: bool = False,
                  mesh: Optional[Mesh] = None) -> Dict[str, str]:
    """Pick the (driver, pow, mix) lowerings for this problem size.

    Pure function of the call signature — ``run_blade_fl`` applies it and
    records the result in :data:`LAST_DISPATCH`; benches call it directly to
    annotate their CSV lines. Keys:

      ``driver`` — ``"scan"`` (all K rounds in one jitted ``lax.scan``) or
        ``"loop"`` (per-round jitted driver). Callables and ``jit=False``
        force the loop; static micro-sims at or below the measured CPU
        crossover (C <= 4 AND <= 32 samples per client, single device,
        non-stacked) dispatch to the loop too — the results are bitwise
        identical either way, only wall-clock differs.
      ``pow`` — ``"kernel"`` (Pallas 2-D grid) when ``spec.use_kernel`` and
        the budget amortizes the grid (``mine_attempts >=
        _KERNEL_MIN_ATTEMPTS``), else ``"fori_loop"``. Bitwise identical
        either way.
      ``mix`` — ``"fused"`` (Pallas row-block matmul + one-sweep
        diagnostics, tolerance tier) when ``spec.fused_mix``;
        ``"segment"`` when the resolver reroutes the mix through the
        sparse gather + ``segment_sum`` path (ExplicitSparse topologies,
        low-degree GATHER mixes, or ``spec.sparse_mix=True``);
        ``"robust"`` when ``spec.robust_agg`` overrides the linear mix
        with a Byzantine-robust consensus reducer; else ``"jnp"``.
      ``mix_mode`` — the resolved ``MixPlan.mode`` executor strategy
        (``topology.EXEC_*``). Reported from the SAME
        :func:`topology.resolve_mix_plan` call ``make_communicate``
        executes, so report and trace cannot drift (pinned in
        tests/test_hierarchy.py).
      ``reason`` — one phrase saying why the driver was chosen.
    """
    plan: Dict[str, str] = {}
    if callable(batches):
        plan.update(driver="loop", reason="per-round batch callable")
    elif not jit:
        plan.update(driver="loop", reason="jit=False debugging path")
    else:
        samples = 0
        if not stacked:
            leaves = jax.tree.leaves(batches)
            samples = max((x.shape[1] for x in leaves if x.ndim > 1),
                          default=0)
        micro = (mesh is None and not stacked
                 and spec.n_clients <= _MICRO_MAX_CLIENTS
                 and samples <= _MICRO_MAX_SAMPLES)
        if micro:
            plan.update(driver="loop",
                        reason=f"micro-sim C={spec.n_clients} samples="
                               f"{samples} below scan crossover")
        else:
            plan.update(driver="scan", reason="static batch at/above "
                                              "scan crossover")
    if spec.use_kernel and spec.mine_attempts < _KERNEL_MIN_ATTEMPTS:
        plan["pow"] = "fori_loop"
    else:
        plan["pow"] = "kernel" if spec.use_kernel else "fori_loop"
    mplan = topology_lib.resolve_mix_plan(spec)
    plan["mix"] = mplan.mix
    plan["mix_mode"] = mplan.mode
    return plan

# Jitted runners cached on (loss_fn identity, static config). A weakref
# scheme cannot work here — the cached runner's closure chain pins loss_fn,
# so a weak key would never die. A small bounded LRU is the honest tradeoff:
# module-level loss fns (mlp_loss, sweep/benchmark loops at fixed config)
# get cross-call reuse of the compiled executable, while per-call closures
# (launch/train arch paths) pin at most maxsize compiled programs before
# LRU eviction frees them.
@functools.lru_cache(maxsize=16)
def _scan_runner(loss_fn: LossFn, spec: RoundSpec, n_rounds: int,
                 stacked: bool, mesh: Optional[Mesh] = None,
                 plan: Optional["plans_lib.ScanCarryPlan"] = None):
    """Build (and cache) the jitted K-round runner for this config.

    With ``mesh``/``plan`` the whole scan runs inside ``shard_map``: the
    carry enters with the plan's layout (params client-sharded, ledger
    link/key/counter replicated), stays sharded across all K rounds, and
    the stacked metrics come out replicated — XLA never reshards the
    donated carry between rounds."""
    axis_name = plan.client_axes if mesh is not None else None
    n_shards = plan.n_shards if mesh is not None else 1
    axis_sizes = plan.axis_sizes if mesh is not None else ()
    round_fn = make_integrated_round(loss_fn, spec, axis_name=axis_name,
                                     n_shards=n_shards, n_rounds=n_rounds,
                                     axis_sizes=axis_sizes)

    def run(state: RoundState, batch):
        TRACE_COUNTS["scan_runner"] += 1
        if stacked:
            return jax.lax.scan(round_fn, state, batch)
        return jax.lax.scan(lambda s, _: round_fn(s, batch), state, None,
                            length=n_rounds)

    if mesh is not None:
        state_specs = RoundState(params=plan.client_spec(), key=P(),
                                 round_idx=P(), prev_hash=P())
        run = jax.shard_map(run, mesh=mesh,
                            in_specs=(state_specs, plan.batch_spec(stacked)),
                            out_specs=(state_specs, P()),
                            check_vma=False)

    # Donate the carry so params never hold two live copies on the device.
    # Every backend donates (XLA:CPU too), so the tests exercise the same
    # buffer lifetimes the chip runs.
    return jax.jit(run, donate_argnums=(0,))


@functools.lru_cache(maxsize=16)
def _round_runner(loss_fn: LossFn, spec: RoundSpec,
                  n_rounds: Optional[int] = None):
    """Cached jitted single-round step for the Python-loop path, so repeated
    ``run_blade_fl`` calls at the same config (K-sweeps, benchmarks) reuse
    the compiled executable instead of retracing per call. ``n_rounds``
    mirrors the scan runner's forced last-round eval (part of the cache key
    only when ``eval_every > 1`` actually consults it)."""
    return jax.jit(make_integrated_round(loss_fn, spec, n_rounds=n_rounds))


def _run_counts(spec: RoundSpec, n_rounds: int) -> Dict[str, int]:
    """The counts a ``blade.run`` span carries: rounds, clients per round,
    and PoW hashes attempted over the call."""
    n_rounds = int(n_rounds)
    return {"rounds": n_rounds, "clients": spec.n_clients,
            "hashes": n_rounds * spec.n_clients * spec.mine_attempts}


def _history_entry(metrics) -> Dict[str, float]:
    """One round's history row from its metrics on the host: scalars as
    floats, the per-client ``[C]`` losses reduced here with ``np.mean``
    (see :func:`make_finalize` for why not on the device)."""
    metrics = dict(metrics)
    glosses = metrics.pop("global_loss", None)
    llosses = metrics.pop("local_loss")
    entry = {name: float(v) for name, v in metrics.items()}
    entry["local_loss_mean"] = float(np.mean(llosses))
    if glosses is not None:
        entry["global_loss"] = float(np.mean(glosses))
    return entry


def _append_block(ledger: chain.Ledger, metrics) -> None:
    """Seal one round's host metrics into the next block of ``ledger``."""
    ledger.append(chain.make_block(
        index=len(ledger.blocks), prev_hash=ledger.head_hash,
        model_digest=int(metrics["digest"]), winner=int(metrics["winner"]),
        nonce=int(metrics["nonce"]), pow_hash=int(metrics["pow_hash"])))


def _scan_setup(loss_fn: LossFn, spec: RoundSpec, batch, n_rounds: int,
                stacked: bool, mesh: Optional[Mesh],
                plan: Optional["plans_lib.ScanCarryPlan"]):
    """Check the static batch and look up the cached K-round runner."""
    if callable(batch):
        raise TypeError(
            "run_blade_fl_scan needs a static batch pytree; use "
            "run_blade_fl for per-round batch callables")
    if stacked:
        leads = {x.shape[0] for x in jax.tree.leaves(batch)}
        if leads != {int(n_rounds)}:
            raise ValueError(
                f"stacked batch leading dims {sorted(leads)} != "
                f"n_rounds={int(n_rounds)}; scan takes its length from xs")
    if mesh is not None and plan is None:
        plan = plans_lib.scan_carry_plan(mesh, spec.n_clients)
    return _scan_runner(loss_fn, spec, int(n_rounds), bool(stacked), mesh,
                        plan)


def _scan_call(runner, spec: RoundSpec, params_single, batch, key,
               n_rounds: int, ledger: Optional[chain.Ledger]):
    """One call of a K-round runner: the carry, the jitted call, the one
    host transfer, the history and the replayed ledger."""
    with telemetry.span("init"):
        # the runner donates the carry: copy the caller's key into it so
        # the caller's own key stays alive
        state = init_state(params_single, key.copy(), spec.n_clients)
    with telemetry.span("dispatch"):
        state, stacked_metrics = runner(state, batch)
    with telemetry.span("fetch", bytes=telemetry.nbytes(stacked_metrics)):
        host = jax.device_get(stacked_metrics)   # the one host transfer
    with telemetry.span("history"):
        history = [_history_entry({name: v[k] for name, v in host.items()})
                   for k in range(int(n_rounds))]
    ledger = chain.ledger_from_scan(
        host["digest"], host["winner"], host["nonce"], host["pow_hash"],
        ledger=ledger)
    return state, history, ledger


def run_blade_fl_scan(loss_fn: LossFn, spec: RoundSpec, params_single, batch,
                      key, n_rounds: int,
                      ledger: Optional[chain.Ledger] = None,
                      stacked: bool = False,
                      mesh: Optional[Mesh] = None,
                      plan: Optional["plans_lib.ScanCarryPlan"] = None):
    """Compiled driver: all K integrated rounds in one ``jax.jit(lax.scan)``.

    ``batch`` is a static pytree: one ``[C, ...]`` batch reused every round,
    or — with ``stacked=True`` — a ``[K, C, ...]`` stack scanned over as xs.
    The carry stays on device for the whole horizon; metrics and block-header
    fields come back stacked and the single end-of-run ``device_get`` is the
    only host transfer. Returns the same ``(state, history, ledger)`` triple
    as the Python-loop path, with the ledger rebuilt and re-validated by
    ``chain.ledger_from_scan``.

    Pass ``mesh`` (and optionally a ``sharding.plans.scan_carry_plan``) to
    run the scan client-sharded: the carry is laid out per the plan, the
    whole K-round horizon executes inside ``shard_map``, and the results —
    params, metrics, ledger hash links — are bit-for-bit those of the
    single-device scan (see module docstring).
    """
    with telemetry.span("run", **_run_counts(spec, n_rounds)):
        with telemetry.span("plan"):
            runner = _scan_setup(loss_fn, spec, batch, n_rounds, stacked,
                                 mesh, plan)
        return _scan_call(runner, spec, params_single, batch, key, n_rounds,
                          ledger)


def run_blade_fl(loss_fn: LossFn, spec: RoundSpec, params_single, batches,
                 key, n_rounds: int, ledger: Optional[chain.Ledger] = None,
                 jit: bool = True, stacked: bool = False,
                 mesh: Optional[Mesh] = None,
                 plan: Optional["plans_lib.ScanCarryPlan"] = None):
    """Run K integrated rounds; returns (final RoundState, history, ledger).

    Dispatches to the compiled scan engine when ``batches`` is a static
    pytree (see module docstring); falls back to the per-round Python loop
    for callables (``batches(k) -> batch``), ``jit=False``, and static
    micro-sims below the scan crossover (:func:`dispatch_plan` — results are
    bitwise identical on either driver, this only picks the faster one).
    The same plan downgrades ``spec.use_kernel`` when the mining budget is
    too small to amortize the Pallas grid; the decision taken is recorded in
    :data:`LAST_DISPATCH`. ``mesh`` (+ optional ``plan``) selects the
    client-sharded scan engine and therefore requires the static-batch path.
    """
    with telemetry.span("run", **_run_counts(spec, n_rounds)):
        with telemetry.span("plan"):
            decision = dispatch_plan(spec, batches, n_rounds, jit=jit,
                                     stacked=stacked, mesh=mesh)
            LAST_DISPATCH.clear()
            LAST_DISPATCH.update(decision)
            if spec.use_kernel and decision["pow"] == "fori_loop":
                spec = dataclasses.replace(spec, use_kernel=False)
            if decision["driver"] == "scan":
                runner = _scan_setup(loss_fn, spec, batches, n_rounds,
                                     stacked, mesh, plan)
            elif mesh is not None:
                raise ValueError(
                    "mesh-sharded execution needs the compiled scan engine: "
                    "pass a static batch pytree and jit=True (per-round "
                    "batch callables would reshard the carry every round)")
            else:
                # the horizon only matters to the forced last-round eval;
                # keep it out of the runner cache key when eval_every == 1
                # so K-sweeps share one compiled round
                horizon = int(n_rounds) if spec.eval_every > 1 else None
                round_fn = (_round_runner(loss_fn, spec, horizon) if jit
                            else make_integrated_round(loss_fn, spec,
                                                       n_rounds=horizon))
        if decision["driver"] == "scan":
            return _scan_call(runner, spec, params_single, batches, key,
                              n_rounds, ledger)
        with telemetry.span("init"):
            state = init_state(params_single, key, spec.n_clients)
        ledger = ledger if ledger is not None else chain.Ledger()
        history = []
        for k in range(n_rounds):
            if callable(batches):
                batch = batches(k)
            elif stacked:
                batch = jax.tree.map(lambda x: x[k], batches)
            else:
                batch = batches
            with telemetry.span("dispatch", round=k):
                state, metrics = round_fn(state, batch)
            with telemetry.span("fetch", round=k,
                                bytes=telemetry.nbytes(metrics)):
                metrics = jax.device_get(metrics)
            with telemetry.span("ledger", round=k):
                _append_block(ledger, metrics)
            with telemetry.span("history", round=k):
                history.append(_history_entry(metrics))
        return state, history, ledger


# ---------------------------------------------------------------------------
# Cohort-sampled population driver (enrolled C >> active A)
# ---------------------------------------------------------------------------


class PopulationStore:
    """Host-side parameter store for the enrolled population.

    The cohort driver's memory contract: devices only ever hold the
    ``[A, ...]`` active-cohort stack; the ``C_enrolled`` population lives
    here, LAZILY — every client starts as a reference to the shared init
    model and only materializes its own row after a round it participated
    in scatters back. Host memory is therefore
    O(model + touched · model), never O(C_enrolled · model): a
    10k-population run that ever activates 400 distinct clients stores 401
    model copies.

    ``gather(idx)`` stacks the cohort's rows into device arrays (span
    ``blade.store.gather``); ``scatter(idx, cohort_params)`` writes a
    round's post-mix cohort back: one ``device_get`` (span ``blade.fetch``),
    then the rows copied out so no stacked device buffer is pinned (span
    ``blade.store.scatter``).
    """

    def __init__(self, params_single, n_enrolled: int):
        if n_enrolled < 1:
            raise ValueError("PopulationStore needs n_enrolled >= 1")
        self.n_enrolled = int(n_enrolled)
        self._init = jax.tree.map(lambda x: np.asarray(x), params_single)
        self._row_bytes = telemetry.nbytes(self._init)
        self._rows: Dict[int, Any] = {}

    @property
    def touched(self) -> int:
        """How many clients have materialized their own row."""
        return len(self._rows)

    def materialized_bytes(self) -> int:
        """Host bytes held beyond the shared init model."""
        return self._row_bytes * self.touched

    def _check_idx(self, idx: np.ndarray) -> np.ndarray:
        idx = np.asarray(idx)
        if idx.ndim != 1:
            raise ValueError(f"cohort index must be 1-D, got {idx.shape}")
        if idx.size and (idx.min() < 0 or idx.max() >= self.n_enrolled):
            raise ValueError(
                f"cohort indices must lie in [0, {self.n_enrolled}), got "
                f"range [{idx.min()}, {idx.max()}]")
        return idx

    def gather(self, idx) -> Any:
        """Stack rows ``idx`` into a ``[len(idx), ...]`` device pytree."""
        idx = self._check_idx(idx)
        with telemetry.span("store.gather", rows=idx.size,
                            bytes=idx.size * self._row_bytes):
            rows = [self._rows.get(int(i), self._init) for i in idx]
            return jax.tree.map(lambda *xs: jnp.asarray(np.stack(xs)), *rows)

    def scatter(self, idx, cohort_params) -> None:
        """Write a round's post-mix ``[len(idx), ...]`` cohort stack back."""
        idx = self._check_idx(idx)
        with telemetry.span("fetch", bytes=telemetry.nbytes(cohort_params)):
            host = jax.device_get(cohort_params)
        leads = {x.shape[0] for x in jax.tree.leaves(host)}
        if leads != {idx.size}:
            raise ValueError(
                f"cohort_params leading dims {sorted(leads)} != "
                f"len(idx)={idx.size}")
        with telemetry.span("store.scatter", rows=idx.size) as span:
            before = self.touched
            for a, i in enumerate(idx):
                self._rows[int(i)] = jax.tree.map(lambda x: np.array(x[a]),
                                                  host)
            span.set_metadata(new_rows=self.touched - before)


@functools.lru_cache(maxsize=16)
def _cohort_round_runner(loss_fn: LossFn, spec: RoundSpec,
                         n_rounds: Optional[int],
                         mesh: Optional[Mesh] = None,
                         plan: Optional["plans_lib.CohortCarryPlan"] = None):
    """Cached jitted single-round step for the cohort driver. Identical to
    :func:`_round_runner` single-device (so an ``A == C_enrolled`` cohort
    run is bitwise the loop driver); with ``mesh``/``plan`` the round body
    runs inside ``shard_map`` with the ``[A, ...]`` cohort stack sharded
    over the plan's client axes — the enrolled population never has a
    device layout at all."""
    if mesh is None:
        return _round_runner(loss_fn, spec, n_rounds)
    round_fn = make_integrated_round(loss_fn, spec,
                                     axis_name=plan.client_axes,
                                     n_shards=plan.n_shards,
                                     n_rounds=n_rounds,
                                     axis_sizes=plan.axis_sizes)
    state_specs = RoundState(params=plan.client_spec(), key=P(),
                             round_idx=P(), prev_hash=P())
    fn = jax.shard_map(round_fn, mesh=mesh,
                       in_specs=(state_specs, plan.batch_spec(False)),
                       out_specs=(state_specs, P()),
                       check_vma=False)
    return jax.jit(fn)


def run_blade_fl_cohort(loss_fn: LossFn, spec: RoundSpec, params_single,
                        batches, key, n_rounds: int,
                        cohort: topology_lib.CohortSchedule,
                        ledger: Optional[chain.Ledger] = None,
                        store: Optional[PopulationStore] = None,
                        mesh: Optional[Mesh] = None,
                        plan: Optional["plans_lib.CohortCarryPlan"] = None):
    """Cohort-sampled population driver: K rounds over ``C_enrolled``
    clients of which only an active cohort of ``A = spec.n_clients``
    participates per round.

    Per round: draw the cohort from the engine's per-round ``k_topo``
    stream (``cohort.cohort_at`` — so ``topology_keys(key, K)`` replays the
    memberships), gather the cohort's rows out of the host-side
    :class:`PopulationStore`, run ONE integrated round — training, lazy/DP
    perturbation, digest, the intra-cohort topology mix, the PoW race and
    the hash link, all at cohort size ``A`` — and scatter the post-mix
    cohort back. The device working set is O(A·model) + the mix's
    O(A·deg), independent of ``C_enrolled``; nothing of shape
    ``[C_enrolled, ...]`` (let alone ``[C, C]``) ever exists on device.

    ``spec`` describes the INTRA-cohort round (``spec.n_clients`` must
    equal ``cohort.cohort_size``): ``spec.topology`` mixes within the
    round's cohort, lazy/DP/mining semantics are unchanged. The ledger is
    global — one hash-linked chain across rounds exactly like the other
    drivers, with the device-side ``prev_hash`` carry crossing rounds
    through the host mirror. ``PartialParticipation`` population semantics
    are ``CohortSchedule(..., bias="prefix")`` + ``FullMesh`` intra-cohort:
    the first ``A`` enrolled clients mix every round and the rest idle —
    now at O(A) cost instead of a masked dense ``[C, C]`` mix.

    ``batches`` is either a callable ``(round_idx, cohort_idx) ->
    [A, ...]`` batch pytree (the scalable form — build only the cohort's
    data) or a static ``[C_enrolled, ...]`` pytree indexed host-side per
    round. ``key`` follows the exact split chain of the other drivers.

    Returns ``(store, history, ledger)``; each history entry additionally
    records the round's cohort as ``entry["cohort"]``.
    """
    if cohort.cohort_size != spec.n_clients:
        raise ValueError(
            f"spec.n_clients={spec.n_clients} must equal "
            f"cohort.cohort_size={cohort.cohort_size}: the round engine "
            "runs at cohort size")
    if store is None:
        store = PopulationStore(params_single, cohort.n_enrolled)
    if store.n_enrolled != cohort.n_enrolled:
        raise ValueError(
            f"store holds n_enrolled={store.n_enrolled} but the schedule "
            f"samples from {cohort.n_enrolled}")
    if callable(batches):
        batch_fn = batches
    else:
        leads = {x.shape[0] for x in jax.tree.leaves(batches)}
        if leads != {cohort.n_enrolled}:
            raise ValueError(
                f"static batches leading dims {sorted(leads)} != "
                f"n_enrolled={cohort.n_enrolled} (pass a callable "
                "(round_idx, cohort_idx) -> batch to build per-cohort data)")
        host_batches = jax.tree.map(np.asarray, batches)

        def batch_fn(k, idx):
            with telemetry.span("data", round=k):
                return jax.tree.map(
                    lambda x: jnp.asarray(x[np.asarray(idx)]), host_batches)

    with telemetry.span("run", **_run_counts(spec, n_rounds)):
        with telemetry.span("plan"):
            if mesh is not None and plan is None:
                plan = plans_lib.cohort_carry_plan(mesh, cohort.n_enrolled,
                                                   spec.n_clients)
            decision = dispatch_plan(spec, batches, n_rounds, mesh=mesh)
            decision.update(driver="cohort",
                            reason=f"cohort A={cohort.cohort_size} over "
                                   f"C_enrolled={cohort.n_enrolled}")
            LAST_DISPATCH.clear()
            LAST_DISPATCH.update(decision)
            # mirror run_blade_fl's horizon handling so A == C_enrolled
            # cohort runs reuse (and bitwise match) the loop driver's cached
            # runner
            horizon = int(n_rounds) if spec.eval_every > 1 else None
            runner = _cohort_round_runner(loss_fn, spec, horizon, mesh, plan)
        ledger = ledger if ledger is not None else chain.Ledger()
        history = []
        host_key = key
        prev_hash = jnp.uint32(chain.GENESIS_HASH)
        for k in range(int(n_rounds)):
            with telemetry.span("cohort", round=k):
                # host mirror of the round body's split chain
                # (= topology_keys)
                next_key, _k_lazy, k_dp = jax.random.split(host_key, 3)
                k_topo = jax.random.fold_in(k_dp, _TOPOLOGY_SALT)
                idx = np.asarray(cohort.cohort_at(k_topo))
            batch = batch_fn(k, idx)
            params = store.gather(idx)
            with telemetry.span("dispatch", round=k):
                state = RoundState(params=params, key=host_key,
                                   round_idx=jnp.int32(k),
                                   prev_hash=prev_hash)
                state, metrics = runner(state, batch)
            store.scatter(idx, state.params)
            prev_hash = state.prev_hash
            host_key = next_key
            with telemetry.span("fetch", round=k,
                                bytes=telemetry.nbytes(metrics)):
                metrics = jax.device_get(metrics)
            with telemetry.span("ledger", round=k):
                _append_block(ledger, metrics)
            with telemetry.span("history", round=k):
                entry = _history_entry(metrics)
                entry["cohort"] = [int(i) for i in idx]
                history.append(entry)
        return store, history, ledger
