"""Plain reference of the BLADE-FL round, written from the paper and the
configuration files alone. It imports nothing of the program under test.

One integrated round over C clients (arXiv:2101.06905 section 3.1):

1. local training: tau full-batch gradient steps per client on the MLP
   784-256-10 with softmax cross-entropy; the local loss is the one seen at
   the last step;
2. lazy clients (the first M) broadcast the fresh model of client
   M + (i mod (C - M)) plus N(0, sigma^2) noise, one normal draw per leaf
   from ``split(k_lazy, n_leaves)`` in sorted-leaf order (eq. 7);
3. full-mesh mix: every client adopts the mean of the broadcast set;
4. PoW race: client c hashes nonces ``round * 2**20 + [0, attempts)`` with
   its salted payload; the first least hash wins, and the device hash link
   moves to ``mix(prev, digest, nonce)``;
5. the ledger appends a sha256-linked block header;
6. the global loss is the mean over clients of the mixed model's loss on
   the client's own data.

The model arithmetic runs in float32 at ``Precision.HIGHEST`` (or, for the
control, in bfloat16). The integer layers (hashes, race, ledger) are exact
numpy and hashlib code.
"""
from __future__ import annotations

import functools
import hashlib
import struct

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
TOPOLOGY_SALT = 0x746F706F   # "topo": k_topo = fold_in(k_dp, salt)
COHORT_SALT = 0x636F686F     # "coho": cohort key = fold_in(k_topo, salt)
NONCE_STRIDE = 1 << 20       # round r searches nonces from r * 2**20
DIGEST_INIT = 0x9E3779B9
_M1, _M2, _M3 = (np.uint32(2654435761), np.uint32(2246822519),
                 np.uint32(3266489917))


# ---------------------------------------------------------------- model


def mlp_loss(p, x, y, precision):
    h = jnp.maximum(jnp.dot(x, p["w1"], precision=precision) + p["b1"], 0)
    logits = (jnp.dot(h, p["w2"], precision=precision)
              + p["b2"]).astype(jnp.float32)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


@functools.lru_cache(maxsize=None)
def _round_program(n_clients, tau, eta, n_lazy, sigma2, dtype_name):
    """One jitted round: (P [C, ...], x, y, key) -> (mixed P, next key,
    local losses [C], global losses [C], per-leaf sums of the broadcast
    set). ``dtype_name`` is float32 (the reference, at HIGHEST) or
    bfloat16 (the control)."""
    dtype = jnp.dtype(dtype_name)
    precision = HIGHEST if dtype == jnp.float32 else None
    src = np.arange(n_clients)
    for i in range(n_lazy):
        src[i] = n_lazy + i % (n_clients - n_lazy)

    def train_one(p, x, y):
        x = x.astype(dtype)
        grad = jax.value_and_grad(lambda q: mlp_loss(q, x, y, precision))
        loss = jnp.zeros((), jnp.float32)
        for _ in range(tau):
            loss, g = grad(p)
            p = jax.tree.map(lambda w, d: w - jnp.asarray(eta, dtype) * d,
                             p, g)
        return p, loss

    def round_fn(P, x, y, key):
        key, k_lazy, _k_dp = jax.random.split(key, 3)
        P, local = jax.vmap(train_one)(P, x, y)
        if n_lazy:
            names = sorted(P)
            keys = dict(zip(names, jax.random.split(k_lazy, len(names))))
            lazy = (jnp.arange(n_clients) < n_lazy)

            def perturb(name):
                leaf = P[name]
                stolen = leaf[src]
                if sigma2 > 0:
                    noise = jax.random.normal(keys[name], leaf.shape,
                                              jnp.float32) * np.sqrt(sigma2)
                    stolen = stolen + noise.astype(dtype)
                sel = lazy.reshape((n_clients,) + (1,) * (leaf.ndim - 1))
                return jnp.where(sel, stolen, leaf)

            P = {name: perturb(name) for name in P}
        sums = {n: jnp.sum(v.astype(jnp.float32)) for n, v in P.items()}
        mixed = {n: jnp.broadcast_to(
            jnp.mean(v.astype(jnp.float32), axis=0).astype(dtype), v.shape)
            for n, v in P.items()}
        glob = jax.vmap(lambda p, a, b: mlp_loss(p, a.astype(dtype), b,
                                                 precision))(mixed, x, y)
        return mixed, key, local, glob, sums

    return jax.jit(round_fn)


def train_rounds(params, x, y, key, n_rounds, rs, dtype="float32"):
    """``n_rounds`` rounds of the model layers from one initial model
    replicated over the clients of ``x``. Returns (final params as host
    float64 arrays, history [{"local_loss_mean", "global_loss"}], digests
    of the broadcast sets)."""
    fn = _round_program(int(x.shape[0]), rs["tau"], rs["eta"], rs["n_lazy"],
                        rs["sigma2"], dtype)
    P = {n: jnp.broadcast_to(jnp.asarray(v, dtype), (x.shape[0],) + v.shape)
         for n, v in params.items()}
    hist, digests = [], []
    for _ in range(int(n_rounds)):
        P, key, local, glob, sums = fn(P, x, y, key)
        hist.append({"local_loss_mean": float(np.mean(np.asarray(local))),
                     "global_loss": float(np.mean(np.asarray(glob)))})
        digests.append(digest(sums))
    return {n: np.asarray(v, np.float64) for n, v in P.items()}, hist, digests


def cohort_round(rows, x, y, key, rs, dtype="float32"):
    """One cohort round from per-client rows (the store's gather): returns
    (post-mix rows, local loss mean, global loss mean, digest)."""
    fn = _round_program(int(x.shape[0]), rs["tau"], rs["eta"], rs["n_lazy"],
                        rs["sigma2"], dtype)
    P = {n: jnp.asarray(v, dtype) for n, v in rows.items()}
    P, _, local, glob, sums = fn(P, x, y, key)
    return (P, float(np.mean(np.asarray(local))),
            float(np.mean(np.asarray(glob))), digest(sums))


# ------------------------------------------------------- keys and data


def split3(key):
    """The per-round key chain: (next key, k_lazy, k_dp)."""
    return tuple(jax.random.split(key, 3))


def cohort_members(k_dp, n_enrolled, cohort):
    """A uniform cohort without replacement by Gumbel top-k, sorted."""
    k_topo = jax.random.fold_in(k_dp, TOPOLOGY_SALT)
    g = jax.random.gumbel(jax.random.fold_in(k_topo, COHORT_SALT),
                          (n_enrolled,), jnp.float32)
    return np.sort(np.asarray(jax.lax.top_k(g, cohort)[1]))


class ClientData:
    """Per-client data of the cross-device population: client ``c`` owns
    512 samples with Dirichlet(alpha) label proportions, drawn from
    ``fold_in(client key, c)`` around class templates shared by all."""

    def __init__(self, key, samples, alpha, image_dim, n_classes,
                 noise=1.3, scale=0.35):
        k_tmpl, _k_eval, self.client_key = jax.random.split(key, 3)
        self.templates = (jax.random.normal(k_tmpl, (n_classes, image_dim))
                          * scale).astype(jnp.float32)
        conc = jnp.full((n_classes,), alpha)

        def draw(cid):
            k_prop, k_lbl, k_noise = jax.random.split(
                jax.random.fold_in(self.client_key, cid), 3)
            props = jax.random.dirichlet(k_prop, conc)
            y = jax.random.categorical(k_lbl, jnp.log(props + 1e-9),
                                       shape=(samples,))
            x = self.templates[y] + jax.random.normal(
                k_noise, (samples, image_dim)) * noise
            return jax.nn.sigmoid(x).astype(jnp.float32), y.astype(jnp.int32)

        self._draw = jax.jit(jax.vmap(draw))

    def batch(self, ids):
        return self._draw(jnp.asarray(ids, jnp.uint32))


# ------------------------------------------------------ integer layers


def _aval(h):
    h = h ^ (h >> np.uint32(15))
    h = h * _M2
    h = h ^ (h >> np.uint32(13))
    h = h * _M3
    return h ^ (h >> np.uint32(16))


def mix_hash(prev, payload, nonce):
    with np.errstate(over="ignore"):
        h = np.asarray(prev, np.uint32) * _M1
        h = _aval(h ^ np.asarray(payload, np.uint32))
        return _aval(h ^ np.asarray(nonce, np.uint32))


def digest(leaf_sums):
    """uint32 fingerprint of a broadcast set from its per-leaf float32
    sums, in sorted-leaf order."""
    acc = np.uint32(DIGEST_INIT)
    with np.errstate(over="ignore"):
        for name in sorted(leaf_sums):
            bits = np.asarray(leaf_sums[name], np.float32).reshape(1) \
                .view(np.uint32)[0]
            acc = _aval(np.asarray(acc ^ bits, np.uint32))
    return int(acc)


def race(prev, dig, round_idx, n_clients, attempts):
    """The PoW race of one round: (winner, nonce, pow_hash, next link)."""
    with np.errstate(over="ignore"):
        ids = np.arange(n_clients, dtype=np.uint32)
        salts = _aval(ids * _M2)
        nonces = (np.uint32(round_idx) * np.uint32(NONCE_STRIDE)
                  + np.arange(attempts, dtype=np.uint32))
        payload = np.uint32(dig) ^ salts
        h = _aval((np.uint32(prev) * _M1) ^ payload)
        hs = _aval(h[:, None] ^ nonces[None, :])
    best = np.argmin(hs, axis=1)
    best_h = hs[ids, best]
    winner = int(np.argmin(best_h))
    nonce = int(nonces[best[winner]])
    return winner, nonce, int(best_h[winner]), int(mix_hash(prev, dig, nonce))


def sha_u32(*words):
    payload = struct.pack(f"<{len(words)}I", *[w & 0xFFFFFFFF for w in words])
    return struct.unpack("<I", hashlib.sha256(payload).digest()[:4])[0]


GENESIS = sha_u32(0xB1ADE, 0xF1)


def header_hash(index, prev, dig, winner, nonce, pow_hash):
    return sha_u32(index, prev, dig, winner, nonce, pow_hash)
