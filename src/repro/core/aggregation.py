"""Decentralized model aggregation (paper §3.1 Steps 2+5).

In BLADE-FL every client broadcasts its model and every client computes the
same aggregate — on a TPU mesh with the client axis sharded over 'data'
(x 'pod'), the broadcast+aggregate pair is exactly one all-reduce (mean over
the leading client axis, re-broadcast to every client slot). The fixed point
is identical to N gossip broadcasts; the ICI ring plays the gossip network.

``aggregate`` is the pure-jnp path; ``repro.kernels.fedavg`` provides the
fused Pallas kernel (aggregate + DP/lazy noise in one VMEM pass) selected by
``use_kernel=True``.

Mesh lowerings (the ``mix_*`` family)
-------------------------------------

Every ``mix_*`` function takes an optional ``axis_name``. With
``axis_name=None`` it is the plain device-local math; with a mesh axis name
(or tuple of names) it is the same computation expressed with collectives,
meant to run inside ``shard_map`` with the client axis sharded over that
axis. The engine (``core/rounds``) picks the lowering through the
:class:`repro.core.topology.MixLowering` each ``Topology`` advertises:

  ``mix_all_reduce``      FullMesh — one weighted all-reduce over the client
                          axis (all-gather + replicated reduce).
  ``mix_neighbor_halo``   Ring — two neighbor ``collective_permute``s build a
                          halo; each client window-averages locally.
  ``mix_gather``          general / sparse ``W`` — masked gather fallback:
                          all-gather the broadcast set, apply the dense
                          mixing matrix, keep the local rows.

Bit-for-bit contract: the sharded path of each lowering reproduces its dense
path EXACTLY, not just to float tolerance. Cross-client fp32 reductions are
therefore never computed as a psum of per-shard partial sums (that reorders
the fp32 association and would change the model digest, breaking the hash
chain) — instead the full client axis is materialized (all-gather is itself
a permute pattern on the ICI ring) and the reduction runs replicated with
the identical HLO the single-device engine executes. The neighbor-halo path
accumulates offsets in the same fixed order as its dense roll-based twin, so
it too is bitwise stable. A true psum would move ~C/D× less data for the
full mesh; it is deliberately not the default — the hash-linked ledger is
the ground truth the sharded engine must reproduce.

The opt-in fast tier (``mix_psum`` / ``mix_psum_dense``)
--------------------------------------------------------

``RoundSpec.fast_allreduce=True`` trades the bitwise contract for exactly
that saved data movement:

  ``mix_psum``        rank-1 (uniform-row) mixes — FullMesh and any
                      ``W = 1 rᵀ``: each shard pre-weights its local client
                      rows, ONE model-sized ``lax.psum`` produces the shared
                      aggregate, every client adopts it. O(1) models moved
                      per device instead of O(C).
  ``mix_psum_dense``  any dense ``W``: each shard contracts its local client
                      block against its column block of ``W`` and psums the
                      ``[C, ...]`` partial products (the SUMMA-style variant
                      the bitwise tier refuses) — same O(C) volume as the
                      gather but no materialized full client axis, and the
                      reduce can ride the ICI all-reduce lanes.

Both reassociate the cross-client fp32 reduction, so their results agree
with the gathered paths only to float tolerance (rtol ≈ 1e-5 over a K-round
run) and the model digest — hence every downstream ledger hash — forks from
the bitwise engine's chain. That is the tolerance equivalence tier:
``tests/equivalence.py`` holds the assertion helpers,
``tests/test_fast_allreduce.py`` pins psum-vs-gather agreement, and
docs/architecture.md §The tolerance tier documents the contract.

Robust consensus reducers (``mix_median`` / ``mix_trimmed`` /
``mix_geomedian``)
------------------------------------------------------------------

Byzantine-tolerant alternatives to the linear mix family, selected via
``RoundSpec.robust_agg`` (docs/architecture.md §Robust aggregation):
coordinate-wise median, coordinate-wise trimmed mean, and a
fixed-iteration Weiszfeld geometric median — all vectorized inside the
scan, all lowering onto the mesh as all-gather + replicated order
statistics (robust reductions are not psum-associative, so they live in
the tolerance tier; see the section comment at their definitions).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

AxisName = Union[str, Tuple[str, ...], None]

# Every mix contraction runs at full f32. A TPU's default for an f32 dot is
# one bf16 pass, which would round each aggregated parameter to 8 mantissa
# bits per round and fork psum from gather by 2^-9 relative; XLA:CPU
# computes f32 dots in f32 either way.
MIX_PRECISION = jax.lax.Precision.HIGHEST


def fedavg(params, weights: Optional[jnp.ndarray] = None):
    """Mean (optionally weighted by |D_i|) over leading client axis C,
    broadcast back to every client: returns same-shaped pytree.

    >>> import jax.numpy as jnp
    >>> out = fedavg({"w": jnp.array([[0.0], [2.0], [4.0]])})
    >>> [float(v) for v in out["w"].ravel()]
    [2.0, 2.0, 2.0]
    """

    def one(leaf):
        if weights is None:
            agg = jnp.mean(leaf.astype(jnp.float32), axis=0)
        else:
            w = (weights / jnp.sum(weights)).astype(jnp.float32)
            agg = jnp.tensordot(w, leaf.astype(jnp.float32), axes=(0, 0),
                                precision=MIX_PRECISION)
        return jnp.broadcast_to(agg, leaf.shape).astype(leaf.dtype)

    return jax.tree.map(one, params)


def _reweight_rows(W: jnp.ndarray,
                   weights: Optional[jnp.ndarray]) -> jnp.ndarray:
    """|D_i| row reweighting shared by every dense mix path:
    ``W'[i, j] ∝ W[i, j] * weights[j]``, renormalized per row. One helper so
    the bitwise ``mix`` and the psum fast tier cannot drift apart."""
    W = jnp.asarray(W, jnp.float32)
    if weights is None:
        return W
    W = W * jnp.asarray(weights, jnp.float32)[None, :]
    return W / jnp.sum(W, axis=1, keepdims=True)


def mix(params, W: jnp.ndarray, weights: Optional[jnp.ndarray] = None):
    """Generalized Steps 2+5: client i adopts ``sum_j W[i, j] * params_j``.

    ``W [C, C]`` is a row-stochastic mixing matrix from ``core.topology``
    (full mesh ``11^T/C`` recovers ``fedavg`` up to float association order;
    the identity matrix is a no-communication round). Optional ``weights``
    (|D_i| data sizes) reweight each row's contributions —
    ``W'[i, j] ∝ W[i, j] * weights[j]``, renormalized per row — so the
    full-mesh W with weights equals weighted ``fedavg``. Accumulation is in
    float32; each leaf round-trips back to its own dtype.
    """
    W = _reweight_rows(W, weights)

    def one(leaf):
        flat = leaf.astype(jnp.float32).reshape((leaf.shape[0], -1))
        return jnp.matmul(W, flat, precision=MIX_PRECISION).reshape(
            leaf.shape).astype(leaf.dtype)

    return jax.tree.map(one, params)


def aggregate_once(params, weights: Optional[jnp.ndarray] = None):
    """Mean over client axis WITHOUT re-broadcast (single global model)."""

    def one(leaf):
        if weights is None:
            return jnp.mean(leaf.astype(jnp.float32), axis=0).astype(leaf.dtype)
        w = (weights / jnp.sum(weights)).astype(jnp.float32)
        return jnp.tensordot(w, leaf.astype(jnp.float32), axes=(0, 0),
                             precision=MIX_PRECISION).astype(leaf.dtype)

    return jax.tree.map(one, params)


def replicate(params, n_clients: int):
    """Lift a single model to the client axis (round-0 initialization)."""
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (n_clients,) + a.shape), params)


# ---------------------------------------------------------------------------
# Client-axis collectives (shard_map helpers)
# ---------------------------------------------------------------------------


def _axis_tuple(axis_name: AxisName) -> Tuple[str, ...]:
    return (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)


def client_all_gather(tree, axis_name: AxisName):
    """Materialize the full client axis on every shard.

    Identity-plus-barrier when ``axis_name`` is None (single-device: the
    tree already holds all C clients). Inside ``shard_map`` this turns every
    ``[C/D, ...]`` leaf into the full ``[C, ...]`` leaf, concatenated in
    shard order — so the result is bitwise identical to the array the
    single-device engine holds.

    The ``optimization_barrier`` (applied in BOTH modes) is load-bearing for
    the bitwise contract: downstream full reductions to a scalar (the model
    digest's per-leaf sum, the per-client ``global_loss``/``local_loss``
    vectors the drivers ``np.mean`` on host, the
    divergence diagnostic) are vectorized by XLA:CPU with lane-partial
    accumulators whose association can change with the fusion context. The
    barrier pins the reduction input to a materialized buffer in the sharded
    and single-device programs alike, so both emit the identical standalone
    reduce. Axis-0-only reductions (``fedavg``'s mean, the mix matmul) keep
    a fixed per-column order regardless and don't need this.
    """
    if axis_name is None:
        return jax.lax.optimization_barrier(tree)
    gathered = jax.tree.map(
        lambda x: jax.lax.all_gather(x, axis_name, axis=0, tiled=True), tree)
    return jax.lax.optimization_barrier(gathered)


def client_shard_index(axis_name: AxisName) -> jnp.ndarray:
    """Linear index of this shard along the (possibly compound) client axis,
    matching the order ``all_gather(..., tiled=True)`` concatenates shards."""
    idx = jnp.int32(0)
    for name in _axis_tuple(axis_name):
        idx = idx * jax.lax.psum(1, name) + jax.lax.axis_index(name)
    return idx


def client_local_rows(full_tree, axis_name: AxisName, n_shards: int):
    """Slice this shard's block of clients back out of full ``[C, ...]``
    leaves (inverse of :func:`client_all_gather`). Identity when
    ``axis_name`` is None or ``n_shards == 1`` outside ``shard_map``."""
    if axis_name is None:
        return full_tree
    idx = client_shard_index(axis_name)

    def one(leaf):
        local = leaf.shape[0] // n_shards
        return jax.lax.dynamic_slice_in_dim(leaf, idx * local, local, axis=0)

    return jax.tree.map(one, full_tree)


# ---------------------------------------------------------------------------
# Topology-keyed mix lowerings (see module docstring for the bitwise contract)
# ---------------------------------------------------------------------------


def mix_all_reduce(params, weights: Optional[jnp.ndarray] = None, *,
                   axis_name: AxisName = None, n_shards: int = 1, full=None):
    """FullMesh lowering: one weighted all-reduce over the client axis.

    Dense (``axis_name=None``) this IS :func:`fedavg`. Sharded, the
    all-reduce is realized gather-side — all-gather the client axis (pass a
    pre-gathered ``full`` tree to reuse the communicate stage's gather),
    run the IDENTICAL :func:`fedavg` replicated on every shard, and keep
    the local client block — so the result matches the single-device
    ``fedavg`` bit for bit (one shared implementation, nothing to drift).
    """
    if axis_name is None:
        return fedavg(params, weights)
    full = client_all_gather(params, axis_name) if full is None else full
    return client_local_rows(fedavg(full, weights), axis_name, n_shards)


def mix_rolls(params, offsets: Sequence[int], weight: float):
    """Dense twin of the neighbor-halo lowering: client ``i`` adopts
    ``weight * sum_off params[(i + off) % C]`` with the offsets accumulated
    in the given (fixed) order. For ``Ring(k)`` with window ``2k+1 <= C``
    this equals ``mix(params, Ring(k).matrix(C))`` up to fp32 association —
    the roll form is the canonical one because the halo path can reproduce
    it bitwise with two ``collective_permute``s.

    The window sum accumulates RAW terms and scales by ``weight`` once at
    the end: a per-term ``acc + w * x`` chain invites XLA to contract the
    multiply into an FMA, and whether it does varies with fusion context —
    exactly the last-ulp drift the bitwise contract forbids. Plain add
    chains have no multiply to contract, so dense and halo stay stable.

    >>> import jax.numpy as jnp
    >>> p = {"w": jnp.arange(4.0).reshape(4, 1)}
    >>> out = mix_rolls(p, offsets=(-1, 0, 1), weight=1.0 / 3.0)
    >>> [round(float(v), 4) for v in out["w"].ravel()]
    [1.3333, 1.0, 2.0, 1.6667]
    """
    w = jnp.float32(weight)

    def one(leaf):
        x = leaf.astype(jnp.float32)
        acc = jnp.roll(x, -offsets[0], axis=0)
        for off in offsets[1:]:
            acc = acc + jnp.roll(x, -off, axis=0)
        return (acc * w).astype(leaf.dtype)

    return jax.tree.map(one, params)


def _linear_axis(axis_name: AxisName):
    """``(ppermute target, total extent)`` for a possibly-compound client
    axis: the shard index linearizes row-major over the axis tuple
    (``idx = idx * extent + axis_index`` per name — the same order
    :func:`client_shard_index` computes and ``all_gather(..., tiled=True)``
    concatenates), so a multi-axis ``('pod', 'data')`` mesh permutes like a
    single flat axis of ``n_pod * n_data`` devices. Extents fold to concrete
    Python ints under ``shard_map``, so the permute lists stay static."""
    names = _axis_tuple(axis_name)
    n_dev = 1
    for nm in names:
        n_dev *= jax.lax.psum(1, nm)
    return (names[0] if len(names) == 1 else names), n_dev


def mix_neighbor_halo(params, offsets: Sequence[int], weight: float,
                      axis_name: AxisName):
    """Ring lowering on the mesh: neighbor ``collective_permute``s.

    Each shard exchanges its client block with its two ring neighbors (one
    ``ppermute`` per direction), assembles the ``[3·C/D, ...]`` halo, and
    window-averages its own clients locally — communication is
    O(window), independent of C, versus the all-gather fallback's O(C).
    Accumulation order and fp32 math match :func:`mix_rolls` exactly, so
    dense and sharded Ring mixes are bitwise identical. Requires
    ``max(|off|) <= C/D`` (one-block halo). A compound client axis
    (``('pod', 'data')``) is linearized row-major (:func:`_linear_axis`) —
    the ring's cross-pod wrap is just one more permute edge, no gather.
    """
    if axis_name is None:
        return mix_rolls(params, offsets, weight)
    name, n_dev = _linear_axis(axis_name)
    fwd = [((j + 1) % n_dev, j) for j in range(n_dev)]   # nxt[j] = block j+1
    bwd = [((j - 1) % n_dev, j) for j in range(n_dev)]   # prv[j] = block j-1
    w = jnp.float32(weight)

    def one(leaf):
        x = leaf.astype(jnp.float32)
        local = x.shape[0]
        nxt = jax.lax.ppermute(x, name, fwd)
        prv = jax.lax.ppermute(x, name, bwd)
        ext = jnp.concatenate([prv, x, nxt], axis=0)     # rows -local..2·local
        # raw-sum-then-scale, mirroring mix_rolls (FMA-contraction safety)
        acc = jax.lax.dynamic_slice_in_dim(
            ext, local + offsets[0], local, axis=0)
        for off in offsets[1:]:
            acc = acc + jax.lax.dynamic_slice_in_dim(
                ext, local + off, local, axis=0)
        return (acc * w).astype(leaf.dtype)

    return jax.tree.map(one, params)


def mix_shift_halo(params, offsets: Sequence[int], weight: float,
                   axis_name: AxisName):
    """Arbitrary-shift generalization of :func:`mix_neighbor_halo`.

    Client ``i`` adopts ``weight * sum_off params[(i + off) % C]`` for any
    static offsets — not just offsets inside one neighbor block. Each offset
    ``s`` decomposes as ``s = q * L + m`` over the per-shard block size
    ``L``: the rows client ``i`` needs live in the blocks of devices
    ``d + q`` and ``d + q + 1``, so the lowering is (at most) two
    whole-block ``ppermute``s plus a static slice per offset — O(1) blocks
    moved per offset, independent of C, which is what lets a gossip
    *rotation* keep its one-partner communication volume on the mesh.

    Bitwise contract: pure data movement plus the same fixed-order
    raw-sum-then-scale accumulation as :func:`mix_rolls`, so the sharded
    result equals the dense roll form bit for bit. A compound client axis
    is linearized row-major (:func:`_linear_axis`) — shifts that cross pod
    boundaries or wrap the whole population stay two whole-block permutes;
    with ``axis_name=None`` it IS :func:`mix_rolls`.
    """
    if axis_name is None:
        return mix_rolls(params, offsets, weight)
    name, n_dev = _linear_axis(axis_name)
    w = jnp.float32(weight)

    def block_from(x, q):
        q = q % n_dev
        if q == 0:
            return x
        # dest d receives the block of source (d + q) % D
        perm = [(j, (j - q) % n_dev) for j in range(n_dev)]
        return jax.lax.ppermute(x, name, perm)

    def rows_at(x, s):
        local = x.shape[0]
        q, m = divmod(s % (local * n_dev), local)
        if m == 0:
            return block_from(x, q)
        ext = jnp.concatenate([block_from(x, q), block_from(x, q + 1)], axis=0)
        return jax.lax.slice_in_dim(ext, m, m + local, axis=0)

    def one(leaf):
        x = leaf.astype(jnp.float32)
        acc = rows_at(x, offsets[0])
        for off in offsets[1:]:
            acc = acc + rows_at(x, off)
        return (acc * w).astype(leaf.dtype)

    return jax.tree.map(one, params)


def _kernel_mix_tree(params, w_rows, interpret):
    """Route a tree's leaf matmuls through the fused Pallas row-block kernel
    (``kernels.fedavg.mix_rows_flat``). Imported lazily so importing
    ``core.aggregation`` never pulls the pallas machinery (the dry-run
    imports this module before locking its device count)."""
    from repro.kernels.fedavg import ops as fedavg_ops
    return fedavg_ops.mix_rows_tree(params, w_rows, interpret=interpret)


def mix_gather(params, W: jnp.ndarray, weights: Optional[jnp.ndarray] = None,
               *, axis_name: AxisName = None, n_shards: int = 1, full=None,
               use_kernel: bool = False, interpret: Optional[bool] = None):
    """General/sparse-``W`` fallback: masked gather pattern.

    All-gather the broadcast set (a permute pattern on the ring; pass a
    pre-gathered ``full`` tree to reuse the communicate stage's gather),
    apply the dense row-stochastic mask ``W`` with the identical full-width
    matmul the single-device engine runs (bitwise equal — same HLO on the
    same ``[C, ...]`` input), and keep only this shard's client rows. A
    SUMMA-style permute-and-accumulate over shard blocks would halve peak
    memory but reorders the fp32 contraction, so it is not used.

    ``use_kernel=True`` (RoundSpec.fused_mix) contracts through the fused
    Pallas row-block kernel instead: the shard's ROW block of the reweighted
    ``W`` is sliced first and only the local output rows are ever computed —
    the weighted gather, matmul and local-row-select fuse into one kernel.
    Tolerance tier (the kernel's contraction order replaces XLA's), like the
    psum fast tier. ``interpret`` threads RoundSpec.kernel_interpret
    (None = interpret everywhere except real TPU backends).
    """
    if use_kernel:
        w_rows = _reweight_rows(W, weights)
        if axis_name is not None:
            full = client_all_gather(params, axis_name) if full is None \
                else full
            idx = client_shard_index(axis_name)
            local = w_rows.shape[0] // n_shards
            w_rows = jax.lax.dynamic_slice_in_dim(w_rows, idx * local, local,
                                                  axis=0)
            return _kernel_mix_tree(full, w_rows, interpret)
        return _kernel_mix_tree(params, w_rows, interpret)
    if axis_name is None:
        return mix(params, W, weights)
    full = client_all_gather(params, axis_name) if full is None else full
    mixed = mix(full, W, weights)
    return client_local_rows(mixed, axis_name, n_shards)


def mix_segment(params, neighbor_idx, edge_w, *, axis_name: AxisName = None,
                n_shards: int = 1, full=None):
    """Sparse-topology mix: neighbor gather + ``jax.ops.segment_sum``.

    ``neighbor_idx``/``edge_w`` are the FULL ``[C, D]`` edge-list form of the
    mixing matrix (``topology.SparseLowering``, padded to max degree ``D``
    with weight-0 self-edges): client ``i`` adopts
    ``sum_d edge_w[i, d] * params[neighbor_idx[i, d]]``. Work and the
    gathered working set are O(C·D) — for a topology whose degree is ≪ C
    this replaces the dense ``mix`` matmul's O(C²) row contraction, which is
    what lets cohort populations scale past toy C.

    Sharded, each shard slices its local ROW block of the edge lists (same
    shard-index slicing as ``mix_psum_dense``), gathers only the flattened
    neighbor rows it references out of the broadcast set (``full`` reuses
    the communicate stage's gather), and segment-sums into its own
    ``C/D_shards`` outputs — no cross-shard reduction at all, so unlike the
    psum tier there is no partial-sum reassociation: each output row's sum
    runs in the same ascending-neighbor order on every shard layout. Like
    every mix, accumulation is fp32 with a round-trip to the leaf dtype.

    Association caveat: XLA's scatter-add (`segment_sum`) does not promise
    the dense matmul's contraction order, so sparse-vs-dense agreement is
    pinned at the TOLERANCE tier (tests/test_sparse_mix.py); sharded-vs-
    single-device sparse agreement is bitwise (identical per-row segment
    reductions either way).

    >>> import jax.numpy as jnp
    >>> p = {"w": jnp.arange(3.0).reshape(3, 1)}
    >>> idx = jnp.array([[0, 1], [0, 1], [2, 2]])
    >>> ew = jnp.array([[0.5, 0.5], [0.5, 0.5], [1.0, 0.0]])
    >>> [float(v) for v in mix_segment(p, idx, ew)["w"].ravel()]
    [0.5, 0.5, 2.0]
    """
    idx_full = jnp.asarray(neighbor_idx, jnp.int32)
    w_full = jnp.asarray(edge_w, jnp.float32)
    c, d = idx_full.shape
    if axis_name is None:
        source = params if full is None else full
        idx_loc, w_loc = idx_full, w_full
        n_rows = c
    else:
        source = client_all_gather(params, axis_name) if full is None \
            else full
        shard = client_shard_index(axis_name)
        n_rows = c // n_shards
        idx_loc = jax.lax.dynamic_slice_in_dim(idx_full, shard * n_rows,
                                               n_rows, axis=0)
        w_loc = jax.lax.dynamic_slice_in_dim(w_full, shard * n_rows,
                                             n_rows, axis=0)
    seg_ids = jnp.repeat(jnp.arange(n_rows, dtype=jnp.int32), d)
    src_rows = idx_loc.reshape(-1)
    w_flat = w_loc.reshape(-1)

    def one(p_leaf, s_leaf):
        flat = s_leaf.astype(jnp.float32).reshape((s_leaf.shape[0], -1))
        gathered = jnp.take(flat, src_rows, axis=0)       # [n_rows·D, F]
        mixed = jax.ops.segment_sum(gathered * w_flat[:, None], seg_ids,
                                    num_segments=n_rows)
        return mixed.reshape(p_leaf.shape).astype(p_leaf.dtype)

    return jax.tree.map(one, params, source)


def mix_cluster(params, n_clusters: int, inter_weight: float,
                axis_name: AxisName = None, *, n_shards: int = 1,
                full=None):
    """Two-level ``ClusterTopology`` mix: intra-cluster mean + ring-coupled
    cluster means (``W = B ⊗ J_S/S``; see ``topology.ClusterTopology``).

    Dense (``axis_name=None``): reshape ``[C, ...]`` to ``[G, S, ...]``,
    reduce each cluster to its mean (raw-sum-then-scale, FMA safety), roll
    the means one step each way, and recombine ``[w_self, w_nbr, w_nbr]``
    against the stacked ``[self, prev, next]`` terms as ONE ``dot_general``.
    The dot is the load-bearing choice: scaled adds get FMA-contracted
    differently per fusion context (``optimization_barrier`` does NOT block
    contraction) and the bits fork between the dense and sharded programs,
    while a dot has a single deterministic lowering everywhere — the same
    reason ``mix_gather``/``mix_psum_dense`` combine via matmul. Every
    client in a cluster broadcasts the same mixed mean, so the result is
    exactly rank-G.

    Cluster-aligned sharded path — a two-axis client mesh whose FIRST axis
    extent equals ``n_clusters`` (the ``('pod', 'data')`` layout
    ``sharding.plans.scan_carry_plan`` produces): the cluster sum is an
    in-pod ``all_gather`` over the second axis (``S`` rows, never leaves the
    pod) reduced with the same ``[1, S, ...]`` sum structure as the dense
    ``[G, S, ...]`` reduce, and the roll becomes TWO model-sized cross-pod
    ``ppermute``s of the cluster mean — O(S + 2) models moved versus the
    flat gather's O(C), and still bitwise (same sums, same combine order;
    no psum anywhere).

    Any other layout (single axis, pod extent != G) falls back to the
    gathered dense math + local-rows slice — bitwise by construction, the
    alignment only buys communication volume.

    >>> import jax.numpy as jnp
    >>> p = {"w": jnp.arange(4.0).reshape(4, 1)}
    >>> out = mix_cluster(p, n_clusters=2, inter_weight=0.5)
    >>> [float(v) for v in out["w"].ravel()]
    [1.5, 1.5, 1.5, 1.5]
    >>> out = mix_cluster(p, n_clusters=2, inter_weight=0.0)
    >>> [float(v) for v in out["w"].ravel()]
    [0.5, 0.5, 2.5, 2.5]
    """
    g = int(n_clusters)
    w_row = jnp.array([1.0 - inter_weight, inter_weight / 2.0,
                       inter_weight / 2.0], jnp.float32)

    def combine(m, prv, nxt):
        # one dot_general, never scaled adds: see the docstring's FMA note
        return jnp.tensordot(w_row, jnp.stack([m, prv, nxt], axis=0), axes=1,
                             precision=MIX_PRECISION)

    def dense(tree):
        def one(leaf):
            x = leaf.astype(jnp.float32)
            s = x.shape[0] // g
            grp = x.reshape((g, s) + x.shape[1:])
            # one [1, S, ...] reduce PER CLUSTER — the exact operand shape
            # the aligned sharded path reduces, because XLA associates a
            # reduce differently for [G, S, ...] vs [1, S, ...] operands on
            # some leaf ranks and that forks the bits. The barrier pins the
            # scaled mean so the combine multiplies see the same value in
            # every fusion context.
            m = jnp.concatenate([
                grp[i:i + 1].sum(axis=1) for i in range(g)])  # [G, ...]
            m = jax.lax.optimization_barrier(m * jnp.float32(1.0 / s))
            out = combine(m, jnp.roll(m, 1, axis=0), jnp.roll(m, -1, axis=0))
            # pin the stage output: downstream consumers (next round's loss)
            # must see the same fusion boundary in both programs
            out = jax.lax.optimization_barrier(out)
            return jnp.broadcast_to(
                out[:, None], grp.shape).reshape(x.shape).astype(leaf.dtype)
        return jax.tree.map(one, tree)

    if axis_name is None:
        return dense(params)
    names = _axis_tuple(axis_name)
    aligned = len(names) == 2 and jax.lax.psum(1, names[0]) == g
    if not aligned:
        src = client_all_gather(params, axis_name) if full is None else full
        return client_local_rows(dense(src), axis_name, n_shards)
    pod_axis, data_axis = names
    fwd = [((j + 1) % g, j) for j in range(g)]   # nxt[p] = mean of pod p+1
    bwd = [((j - 1) % g, j) for j in range(g)]   # prv[p] = mean of pod p-1

    def one(leaf):
        x = leaf.astype(jnp.float32)
        blk = jax.lax.all_gather(x, data_axis, axis=0, tiled=True)
        blk = jax.lax.optimization_barrier(blk)   # in-pod rows: [S, ...]
        s = blk.shape[0]
        # [1, S, ...] sum(axis=1) mirrors the dense [G, S, ...] reduce
        # structure, so the cluster sum is bitwise the dense one; same
        # barrier pin on the scaled mean as the dense path
        m = jax.lax.optimization_barrier(
            blk.reshape((1, s) + blk.shape[1:]).sum(axis=1)[0]
            * jnp.float32(1.0 / s))
        nxt = jax.lax.ppermute(m, pod_axis, fwd)
        prv = jax.lax.ppermute(m, pod_axis, bwd)
        out = jax.lax.optimization_barrier(combine(m, prv, nxt))
        return jnp.broadcast_to(out[None], x.shape).astype(leaf.dtype)

    return jax.tree.map(one, params)


# ---------------------------------------------------------------------------
# Robust consensus reducers (Byzantine-tolerant alternatives to the linear
# mix; selected via RoundSpec.robust_agg -> topology.resolve_mix_plan)
# ---------------------------------------------------------------------------
#
# Each reducer maps the broadcast set [C, ...] to ONE aggregate that every
# client adopts (rank-1, like FullMesh) — a robust consensus primitive over
# the full broadcast set, deliberately independent of the round's topology
# matrix: a Byzantine row must be EXCLUDED per coordinate, not merely
# down-weighted, and the per-coordinate order statistics that do that are
# defined over the whole client axis. Breakdown points (max attackers
# tolerated): median and the Weiszfeld geometric median ⌊(C-1)/2⌋,
# trimmed(t) exactly t per tail — versus 0 for every linear mix, where one
# sign-flipping client corrupts all C models (tests/test_robust_mix.py pins
# both sides).
#
# Sharded, each lowers as all-gather + replicated per-coordinate order
# statistics over the full client axis + keep-local-rows — robust
# reductions are NOT psum-associative (a median of medians is not the
# median), so there is no partial-sum fast path and the family lives under
# the TOLERANCE equivalence tier (rtol ≈ 1e-5, tests/test_robust_mix.py)
# rather than the bitwise contract: sort/selection networks and the
# Weiszfeld reweighting are fusion-context-sensitive in ways the
# barrier-pinned linear reductions are not, and pinning every comparator is
# not worth freezing the implementation.


def robust_median(full_tree):
    """Coordinate-wise median over the leading client axis, broadcast back
    to every client slot (rank-1 aggregate).

    >>> import jax.numpy as jnp
    >>> out = robust_median({"w": jnp.array([[0.0], [1.0], [100.0]])})
    >>> [float(v) for v in out["w"].ravel()]
    [1.0, 1.0, 1.0]
    """

    def one(leaf):
        agg = jnp.median(leaf.astype(jnp.float32), axis=0)
        return jnp.broadcast_to(agg, leaf.shape).astype(leaf.dtype)

    return jax.tree.map(one, full_tree)


def robust_trimmed(full_tree, trim: int):
    """Coordinate-wise trimmed mean: sort each coordinate over the client
    axis, drop the ``trim`` smallest and ``trim`` largest values, average
    the surviving ``C - 2*trim``. ``trim=0`` is the plain mean (up to fp32
    association of the sorted sum — ULP-bound, tests/test_property.py).

    >>> import jax.numpy as jnp
    >>> out = robust_trimmed({"w": jnp.array([[0.0], [1.0], [2.0],
    ...                                       [1000.0]])}, trim=1)
    >>> [float(v) for v in out["w"].ravel()]
    [1.5, 1.5, 1.5, 1.5]
    """
    t = int(trim)

    def one(leaf):
        c = leaf.shape[0]
        if not 0 <= 2 * t < c:
            raise ValueError(f"trim={t} must satisfy 2*trim < C={c}")
        kept = jnp.sort(leaf.astype(jnp.float32), axis=0)[t:c - t]
        agg = jnp.sum(kept, axis=0) / jnp.float32(c - 2 * t)
        return jnp.broadcast_to(agg, leaf.shape).astype(leaf.dtype)

    return jax.tree.map(one, full_tree)


def robust_geomedian(full_tree, n_iters: int = 8, eps: float = 1e-6):
    """Geometric median of the flattened client models by Weiszfeld
    iteration with a STATIC iteration count — a fixed ``fori_loop``, so the
    reducer is jax-traceable and compiles into the scan with no per-round
    retrace (no data-dependent convergence test; ``n_iters`` in the 5-10
    range is ample at FL scales, and the eps floor guards the reweighting
    when the iterate lands on a client point).

    Unlike the coordinate-wise reducers this is a MODEL-space median: the
    minimizer of ``sum_i ||x_i - y||_2`` over the concatenated leaves,
    which no coordinate-wise attack can drag further than the honest
    diameter while a majority of clients is honest (breakdown ⌊(C-1)/2⌋).
    """
    leaves, treedef = jax.tree.flatten(full_tree)
    c = leaves[0].shape[0]
    flat = jnp.concatenate(
        [leaf.astype(jnp.float32).reshape(c, -1) for leaf in leaves], axis=1)

    def body(_, y):
        d = jnp.sqrt(jnp.sum((flat - y[None]) ** 2, axis=1))   # [C]
        w = 1.0 / jnp.maximum(d, jnp.float32(eps))
        w = w / jnp.sum(w)
        return jnp.tensordot(w, flat, axes=(0, 0), precision=MIX_PRECISION)

    y = jax.lax.fori_loop(0, int(n_iters), body, jnp.mean(flat, axis=0))

    out, offset = [], 0
    for leaf in leaves:
        size = 1
        for d in leaf.shape[1:]:
            size *= int(d)
        agg = jax.lax.dynamic_slice_in_dim(y, offset, size, axis=0)
        offset += size
        out.append(jnp.broadcast_to(agg.reshape(leaf.shape[1:]),
                                    leaf.shape).astype(leaf.dtype))
    return jax.tree.unflatten(treedef, out)


def _mix_robust(params, reduce_full, *, axis_name: AxisName, n_shards: int,
                full):
    """Shared mesh lowering of the robust family: gather the client axis
    (reusing the communicate stage's ``full`` when it already gathered),
    run the replicated full-width reducer, keep the local rows."""
    if axis_name is None:
        return reduce_full(params if full is None else full)
    full = client_all_gather(params, axis_name) if full is None else full
    return client_local_rows(reduce_full(full), axis_name, n_shards)


def mix_median(params, *, axis_name: AxisName = None, n_shards: int = 1,
               full=None):
    """Coordinate-wise-median mix (see :func:`robust_median`). Tolerance
    tier on the mesh — see the section comment above."""
    return _mix_robust(params, robust_median, axis_name=axis_name,
                       n_shards=n_shards, full=full)


def mix_trimmed(params, trim: int, *, axis_name: AxisName = None,
                n_shards: int = 1, full=None):
    """Trimmed-mean mix (see :func:`robust_trimmed`). Tolerance tier on the
    mesh — see the section comment above."""
    return _mix_robust(params, lambda t: robust_trimmed(t, trim),
                       axis_name=axis_name, n_shards=n_shards, full=full)


def mix_geomedian(params, n_iters: int = 8, *, eps: float = 1e-6,
                  axis_name: AxisName = None, n_shards: int = 1, full=None):
    """Weiszfeld geometric-median mix (see :func:`robust_geomedian`).
    Tolerance tier on the mesh — see the section comment above."""
    return _mix_robust(params,
                       lambda t: robust_geomedian(t, n_iters, eps=eps),
                       axis_name=axis_name, n_shards=n_shards, full=full)


# ---------------------------------------------------------------------------
# Opt-in psum fast tier (reassociates fp32 — tolerance tier, not bitwise)
# ---------------------------------------------------------------------------


def mix_psum(params, weights: Optional[jnp.ndarray] = None, *,
             axis_name: AxisName = None, n_shards: int = 1):
    """Rank-1 mix as a true in-mesh psum of locally pre-weighted rows.

    Every client adopts the same aggregate ``sum_j w_j x_j / sum_j w_j``
    (uniform ``w`` = ``fedavg``; ``weights`` may be the |D_i| data sizes, a
    uniform-row topology's shared row, or their product — any nonnegative
    per-client weighting). Sharded, each device contracts only its local
    client block and ONE model-sized ``lax.psum`` finishes the reduction —
    ~C/D× less data than the gather-side all-reduce, which is the whole
    point of ``RoundSpec.fast_allreduce``.

    NOT bitwise: the psum reassociates the cross-client fp32 sum (per-shard
    partials, backend-chosen reduction tree), so results agree with
    :func:`fedavg` / :func:`mix_all_reduce` only to float tolerance and the
    model digest forks. With ``axis_name=None`` it is the same
    sum-then-scale math without the collective (float-close to ``fedavg``,
    same association as the sharded form up to the psum tree).

    ``weights`` is always the FULL ``[C]`` vector; the local block is sliced
    by shard index, mirroring how params rows are laid out.

    >>> import jax.numpy as jnp
    >>> out = mix_psum({"w": jnp.array([[0.0], [2.0], [4.0]])})
    >>> [float(v) for v in out["w"].ravel()]
    [2.0, 2.0, 2.0]
    """
    denom = None
    w_local = None
    if weights is not None:
        w_full = jnp.asarray(weights, jnp.float32)
        denom = jnp.sum(w_full)
        if axis_name is None:
            w_local = w_full
        else:
            idx = client_shard_index(axis_name)
            local = w_full.shape[0] // n_shards
            w_local = jax.lax.dynamic_slice_in_dim(w_full, idx * local,
                                                   local, axis=0)

    def one(leaf):
        x = leaf.astype(jnp.float32)
        if weights is None:
            part = jnp.sum(x, axis=0)
        else:
            part = jnp.tensordot(w_local, x, axes=(0, 0),
                                 precision=MIX_PRECISION)
        if axis_name is not None:
            part = jax.lax.psum(part, axis_name)
        if weights is None:
            n_total = x.shape[0] * (n_shards if axis_name is not None else 1)
            agg = part / jnp.float32(n_total)
        else:
            agg = part / denom
        return jnp.broadcast_to(agg, x.shape).astype(leaf.dtype)

    return jax.tree.map(one, params)


def mix_psum_dense(params, W: jnp.ndarray,
                   weights: Optional[jnp.ndarray] = None, *,
                   axis_name: AxisName = None, n_shards: int = 1,
                   use_kernel: bool = False,
                   interpret: Optional[bool] = None):
    """General-``W`` psum variant: local column-block matmul, then psum.

    Shard d holds client rows ``[d·L, (d+1)·L)``; it contracts them against
    its COLUMN block ``W[:, d·L:(d+1)·L]`` to produce the ``[C, ...]``
    partial products every output row owes to its clients, ``lax.psum``s the
    partials (the SUMMA-style accumulate the bitwise tier deliberately
    avoids), and keeps its own rows. Volume is O(C) like the gather, but no
    shard ever materializes the full client axis and the reduction rides
    the all-reduce lanes. ``W`` may be traced (stochastic topologies /
    schedule tables). ``weights`` (|D_i|) reweights rows exactly like
    :func:`mix`.

    NOT bitwise: the contraction is reassociated across shards (tolerance
    tier). With ``axis_name=None`` this IS :func:`mix` (or the fused kernel
    mix when ``use_kernel=True``, which routes the local column-block matmul
    through ``kernels.fedavg.mix_rows_flat``).
    """
    if axis_name is None:
        return mix_gather(params, W, weights, use_kernel=use_kernel,
                          interpret=interpret) if use_kernel \
            else mix(params, W, weights)
    W = _reweight_rows(W, weights)
    idx = client_shard_index(axis_name)
    local = W.shape[0] // n_shards
    w_cols = jax.lax.dynamic_slice_in_dim(W, idx * local, local, axis=1)
    if use_kernel:
        from repro.kernels.fedavg import ops as fedavg_ops
        if interpret is None:
            interpret = fedavg_ops._default_interpret()

    def one(leaf):
        flat = leaf.astype(jnp.float32).reshape((leaf.shape[0], -1))
        if use_kernel:
            from repro.kernels.fedavg.kernel import mix_rows_flat
            part = mix_rows_flat(w_cols, flat, interpret=interpret)
        else:
            part = jnp.matmul(w_cols, flat,       # [C, F] partial products
                              precision=MIX_PRECISION)
        full = jax.lax.psum(part, axis_name)
        mine = jax.lax.dynamic_slice_in_dim(full, idx * local, local, axis=0)
        return mine.reshape(leaf.shape).astype(leaf.dtype)

    return jax.tree.map(one, params)


def client_divergence_psum(params, axis_name: AxisName = None,
                           n_shards: int = 1) -> jnp.ndarray:
    """Tolerance-tier twin of :func:`client_divergence`: cross-shard
    reductions as psums of local partials instead of gathered full-width
    math, so the fast path never materializes the full client axis. Same
    quantity up to fp32 association."""
    scale = n_shards if axis_name is not None else 1

    def sq(leaf):
        x = leaf.astype(jnp.float32)
        s = jnp.sum(x, axis=0)
        if axis_name is not None:
            s = jax.lax.psum(s, axis_name)
        mean = s / jnp.float32(x.shape[0] * scale)
        return jnp.sum((x - mean) ** 2, axis=tuple(range(1, x.ndim)))

    total = sum(jax.tree.leaves(jax.tree.map(sq, params)))
    tsum = jnp.sum(total)
    if axis_name is not None:
        tsum = jax.lax.psum(tsum, axis_name)
    return jnp.sqrt(tsum / jnp.float32(total.shape[0] * scale))


def client_divergence(params) -> jnp.ndarray:
    """Mean pairwise L2 distance of client models from their average —
    diagnostic for the gradient-divergence delta of Definition 1."""
    def sq(leaf):
        mean = jnp.mean(leaf.astype(jnp.float32), axis=0, keepdims=True)
        return jnp.sum((leaf.astype(jnp.float32) - mean) ** 2, axis=tuple(range(1, leaf.ndim)))
    total = sum(jax.tree.leaves(jax.tree.map(sq, params)))
    return jnp.sqrt(jnp.mean(total))
