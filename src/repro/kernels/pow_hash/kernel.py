"""PoW nonce-search kernel — TPU Pallas.

The mining hot-spot (paper §3.1 Step 3): evaluate the integer mixing hash
over a nonce grid and reduce to the (min_hash, argmin_nonce) pair per client.
Nonce tiles are generated in-register (iota + offset, no HBM input traffic);
the running minimum lives in a revisited output block, so per grid step the
only HBM traffic is the final 2-word result per client — the kernel is
pure-VPU integer throughput, exactly how mining behaves on real silicon.

Layout (what Mosaic lowers): the shared ``(prev_hash, nonce_offset)`` pair
sits in SMEM; clients ride the sublanes in 8-row blocks (the client axis is
padded to a multiple of 8) and nonces the lanes, in lane-aligned chunks. The
per-chunk argmin is two lane reductions — the min hash, then the first nonce
index whose hash equals it — over order-preserving int32 keys (the uint32
hash with its sign bit flipped), so no vector is indexed dynamically.

Matches repro.core.mining.mix_hash bit-for-bit (validated vs ref.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# numpy scalars (NOT jnp arrays) so pallas inlines them as literals
_M1 = np.uint32(2654435761)
_M2 = np.uint32(2246822519)
_M3 = np.uint32(3266489917)

_LANES = 128
_ROWS = 8                           # clients per block: one sublane tile
_SIGN = np.uint32(0x80000000)       # uint32 -> order-preserving int32 key
_KEY_MAX = np.int32(0x7FFFFFFF)     # key of hash 0xFFFFFFFF (the "no find")


def _avalanche(h):
    h = h ^ (h >> np.uint32(15))
    h = h * _M2
    h = h ^ (h >> np.uint32(13))
    h = h * _M3
    h = h ^ (h >> np.uint32(16))
    return h


def _pow_race_kernel(seed_ref, payload_ref, best_h_ref, best_n_ref, *,
                     block: int, n_attempts: int):
    """2-D grid body: program (c, j) races nonce chunk j of client block c.

    The chunk axis is the minor (innermost) grid dimension, so a client
    block's output is revisited across all its chunks and carries the
    running (min key, argmin nonce). Chunked running-min with first-index
    tie-breaking per chunk equals the full-range first-occurrence argmin, so
    the result is bitwise independent of ``block`` — the property the
    engine's (mine_attempts, mine_chunk) sweep tests pin.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        best_h_ref[...] = jnp.full(best_h_ref.shape, _KEY_MAX, jnp.int32)
        best_n_ref[...] = jnp.zeros(best_n_ref.shape, jnp.int32)

    prev_hash = seed_ref[0]
    offset = seed_ref[1]
    h = _avalanche((prev_hash * _M1) ^ payload_ref[...])          # [R, 1]
    local = j * block + jax.lax.broadcasted_iota(
        jnp.int32, (best_h_ref.shape[0], block), 1)               # [R, bk]
    nonces = offset + local.astype(jnp.uint32)
    hs = _avalanche(h ^ nonces)
    key = jax.lax.bitcast_convert_type(hs ^ _SIGN, jnp.int32)
    # budget mask: the tail chunk charges exactly n_attempts nonces (eq. 1)
    key = jnp.where(local < n_attempts, key, _KEY_MAX)
    k_min = jnp.min(key, axis=1, keepdims=True)                   # [R, 1]
    first = jnp.min(jnp.where(key == k_min, local, np.int32(n_attempts)),
                    axis=1, keepdims=True)
    n_min = jax.lax.bitcast_convert_type(offset + first.astype(jnp.uint32),
                                         jnp.int32)
    take = k_min < best_h_ref[...]
    best_h_ref[...] = jnp.where(take, k_min, best_h_ref[...])
    best_n_ref[...] = jnp.where(take, n_min, best_n_ref[...])


def pow_race_kernel(prev_hash, payloads, nonce_offset, n_attempts: int, *,
                    block: int = 2048, interpret: bool = True):
    """Whole-race form of the PoW search: one 2-D (client blocks × nonce
    chunks) grid replaces the per-client ``vmap(fori_loop)`` of
    ``core.mining.pow_search``.

    ``payloads`` is the ``[C]`` uint32 vector of per-client pre-salted
    payloads (``digest ^ mining.client_salt(client_id)`` — the disjoint
    nonce spaces); ``prev_hash`` / ``nonce_offset`` are shared uint32
    scalars. ``block`` is the nonce tile, rounded up to whole lanes. Returns
    ``(best_hashes [C], best_nonces [C])``, bitwise equal to the fori_loop
    path at every ``(n_attempts, block)`` including non-divisible budgets.
    """
    if n_attempts <= 0:
        raise ValueError(f"n_attempts must be positive, got {n_attempts}")
    if payloads.ndim != 1:
        raise ValueError(f"payloads must be a [C] vector, got {payloads.shape}")
    c = payloads.shape[0]
    c_pad = -(-c // _ROWS) * _ROWS
    block = -(-min(block, n_attempts) // _LANES) * _LANES
    n_blocks = -(-n_attempts // block)
    seed = jnp.stack([jnp.asarray(prev_hash, jnp.uint32),
                      jnp.asarray(nonce_offset, jnp.uint32)])
    col = jnp.pad(jnp.asarray(payloads, jnp.uint32), (0, c_pad - c))[:, None]
    row_block = pl.BlockSpec((_ROWS, 1), lambda ci, j: (ci, 0))
    best_h, best_n = pl.pallas_call(
        functools.partial(_pow_race_kernel, block=block,
                          n_attempts=n_attempts),
        grid=(c_pad // _ROWS, n_blocks),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), row_block],
        out_specs=[row_block, row_block],
        out_shape=[jax.ShapeDtypeStruct((c_pad, 1), jnp.int32)] * 2,
        interpret=interpret,
    )(seed, col)
    best_h = jax.lax.bitcast_convert_type(best_h[:c, 0], jnp.uint32) ^ _SIGN
    best_n = jax.lax.bitcast_convert_type(best_n[:c, 0], jnp.uint32)
    return best_h, best_n


def pow_search_kernel(prev_hash, payload, nonce_offset, n_attempts: int, *,
                      block: int = 2048, interpret: bool = True):
    """Single-client race: ``pow_race_kernel`` over a one-client vector.
    Returns (best_hash, best_nonce); all inputs uint32 scalars (payload
    already salted per client)."""
    best_h, best_n = pow_race_kernel(
        prev_hash, jnp.reshape(jnp.asarray(payload, jnp.uint32), (1,)),
        nonce_offset, n_attempts, block=block, interpret=interpret)
    return best_h[0], best_n[0]
