"""Fused BLADE-FL aggregation kernel — TPU Pallas.

One VMEM pass per tile fuses the paper's Steps 2+5 epilogue: weighted mean
over the client axis, re-broadcast to every client slot, and the optional
additive noise (DP mechanism §6 / lazy disguise §5 — noise tile precomputed
outside, the kernel fuses the add so the aggregate never round-trips HBM
between mean, broadcast and noise).

Layout: params are flattened per-leaf to [C, N]; grid tiles N. C (<=32) rides
whole in the sublane dimension of each tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def _fedavg_kernel(x_ref, w_ref, noise_ref, o_ref, *, with_noise: bool):
    x = x_ref[...].astype(jnp.float32)            # [C, bn]
    w = w_ref[...].astype(jnp.float32)            # [C, 1]
    # weighted mean (w sums to 1) as a sublane reduce: Mosaic has no
    # vector-matrix dot form for a rank-1 weight operand
    agg = jnp.sum(w * x, axis=0, keepdims=True)   # [1, bn]
    out = jnp.broadcast_to(agg, x.shape)
    if with_noise:
        out = out + noise_ref[...].astype(jnp.float32)
    o_ref[...] = out.astype(o_ref.dtype)


def fedavg_flat(x: jnp.ndarray, weights: jnp.ndarray,
                noise: jnp.ndarray | None = None, *, block_n: int = 2048,
                interpret: bool = True) -> jnp.ndarray:
    """x: [C, N]; weights: [C] (normalized); noise: [C, N] or None."""
    c, n = x.shape
    block_n = min(block_n, n)
    pad = (-n) % block_n
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
        if noise is not None:
            noise = jnp.pad(noise, ((0, 0), (0, pad)))
    npad = x.shape[1]
    with_noise = noise is not None
    if noise is None:
        noise = jnp.zeros((c, block_n), x.dtype)  # dummy single tile
        noise_spec = pl.BlockSpec((c, block_n), lambda i: (0, 0))
    else:
        noise_spec = pl.BlockSpec((c, block_n), lambda i: (0, i))

    out = pl.pallas_call(
        functools.partial(_fedavg_kernel, with_noise=with_noise),
        grid=(npad // block_n,),
        in_specs=[
            pl.BlockSpec((c, block_n), lambda i: (0, i)),
            pl.BlockSpec((c, 1), lambda i: (0, 0)),
            noise_spec,
        ],
        out_specs=pl.BlockSpec((c, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((c, npad), x.dtype),
        interpret=interpret,
    )(x, jnp.reshape(weights, (c, 1)), noise)
    return out[:, :n]


def _mix_rows_kernel(w_ref, x_ref, o_ref):
    w = w_ref[...].astype(jnp.float32)            # [R, K]
    x = x_ref[...].astype(jnp.float32)            # [K, bn]
    # full f32, the precision of the engine's jnp mix (aggregation.py)
    o_ref[...] = jnp.dot(w, x, precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32).astype(o_ref.dtype)


def mix_rows_flat(w_rows: jnp.ndarray, x: jnp.ndarray, *, block_n: int = 2048,
                  interpret: bool = True) -> jnp.ndarray:
    """Fused weighted-gather + matmul + row-select: ``w_rows [R, K] @ x
    [K, N] -> [R, N]``, tiled over N with the whole (reweighted,
    row-selected) mixing block resident per tile.

    This is the local column/row-block contraction of the Steps 2+5 mix:
    ``aggregation.mix_gather`` passes its shard's ROW block of ``W`` (R =
    local clients, K = C — only the local rows are ever computed, the
    row-select is fused into the matmul instead of slicing a full [C, N]
    product), ``aggregation.mix_psum_dense`` passes its COLUMN block (R = C,
    K = local clients). Tolerance tier: the kernel's own contraction order
    replaces XLA's.
    """
    r, k = w_rows.shape
    k2, n = x.shape
    if k != k2:
        raise ValueError(
            f"mix_rows_flat: w_rows [R={r}, K={k}] does not contract with "
            f"x [K={k2}, N={n}]")
    block_n = min(block_n, n)
    pad = (-n) % block_n
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    npad = x.shape[1]
    out = pl.pallas_call(
        _mix_rows_kernel,
        grid=(npad // block_n,),
        in_specs=[pl.BlockSpec((r, k), lambda i: (0, 0)),
                  pl.BlockSpec((k, block_n), lambda i: (0, i))],
        out_specs=pl.BlockSpec((r, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((r, npad), x.dtype),
        interpret=interpret,
    )(w_rows, x)
    return out[:, :n]


def _digest_div_kernel(x_ref, s_ref, r_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        r_ref[...] = jnp.zeros_like(r_ref)

    x = x_ref[...].astype(jnp.float32)            # [C, bn]
    c = x.shape[0]
    # column means over the (fully resident) client axis; zero-padded tail
    # columns contribute 0 to both outputs, so no mask is needed
    mean = jnp.sum(x, axis=0, keepdims=True) / np.float32(c)
    # both accumulators stay 2-D vectors: Mosaic stores no scalars to VMEM
    s_ref[...] = s_ref[...] + jnp.sum(jnp.sum(x, axis=1, keepdims=True),
                                      axis=0, keepdims=True)
    r_ref[...] = r_ref[...] + jnp.sum((x - mean) ** 2, axis=1, keepdims=True)


def digest_div_flat(x: jnp.ndarray, *, block_n: int = 2048,
                    interpret: bool = True):
    """One sweep of a ``[C, N]`` leaf for BOTH diagnostics of the
    communicate stage: returns ``(leaf_sum scalar, residuals [C])`` where
    ``leaf_sum`` feeds the model digest fold (``mining.fold_digest``) and
    ``residuals[c]`` is client c's squared distance from the client mean
    over this leaf (the divergence diagnostic, Def. 1). The jnp path reads
    the broadcast set twice (digest_tree + client_divergence); this reads it
    once. Tolerance tier: the leaf sum accumulates tile partials, so the
    digest forks deterministically from ``mining.digest_tree``.
    """
    c, n = x.shape
    block_n = min(block_n, n)
    pad = (-n) % block_n
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    s, r = pl.pallas_call(
        _digest_div_kernel,
        grid=(x.shape[1] // block_n,),
        in_specs=[pl.BlockSpec((c, block_n), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)),
                   pl.BlockSpec((c, 1), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, 1), jnp.float32),
                   jax.ShapeDtypeStruct((c, 1), jnp.float32)],
        interpret=interpret,
    )(x)
    return s[0, 0], r[:, 0]
