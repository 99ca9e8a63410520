"""Sharding-aware batching for training drivers.

The FL substrate consumes client-stacked batches [C, m, ...]; the pipeline
builds them deterministically per round (so experiments are reproducible and
the dry-run's ShapeDtypeStructs match real batches bit-for-shape).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import telemetry
from repro.data import synthetic


class FLDataSource:
    """Fixed per-client local datasets (paper: |D_i| = 512 samples each);
    each round every client does full-batch GD on its local shard."""

    def __init__(self, key, n_clients: int, samples_per_client: int,
                 dirichlet_alpha: float = 0.5, dataset: str = "mnist",
                 seed: int = 0):
        n_eval = 2048
        n_total = n_clients * samples_per_client * 2 + n_eval
        maker = synthetic.mnist_proxy if dataset == "mnist" else synthetic.fashion_proxy
        # one draw so train and eval share the SAME class templates
        full = maker(key, n_total)
        self.eval_data = {k: v[-n_eval:] for k, v in full.items()}
        self.data = {k: v[:-n_eval] for k, v in full.items()}
        part = synthetic.dirichlet_partition(
            np.asarray(self.data["y"]), n_clients, dirichlet_alpha,
            samples_per_client, seed=seed)
        self.client_data = synthetic.client_batches(self.data, part)

    def round_batch(self, k: int) -> Dict[str, jnp.ndarray]:
        # full local batch every round (paper does full-batch GD locally)
        return self.client_data

    def static_batch(self) -> Dict[str, jnp.ndarray]:
        """The [C, m, ...] batch every round reuses — feed this straight to
        ``run_blade_fl`` / ``run_blade_fl_scan`` to take the compiled
        multi-round path (no [K, ...] stacking needed: full-batch GD means
        the scan closes over one constant batch)."""
        return self.client_data


class CohortDataSource:
    """Enrolled-population data for the cohort driver
    (``core.rounds.run_blade_fl_cohort``).

    ``FLDataSource`` materializes every client's local dataset up front —
    O(C · samples) memory, fine at C = 20, unbuildable for a 10k enrolled
    population. Here each client's fixed local dataset is a pure function
    of ``(source key, client id)``: shared class templates (one draw, so
    the population learns one task), per-client Dirichlet(alpha) label
    proportions (the same non-IID skew the partitioned source has) and
    per-client sample noise, all folded from the client id — built only
    when a round's cohort actually contains the client, LRU-bounded. A K-
    round run touches O(A · K) client datasets, never O(C_enrolled).

    ``cohort_batch`` has the ``(round_idx, cohort_idx) -> [A, m, ...]``
    signature ``run_blade_fl_cohort`` expects for its ``batches``
    callable.
    """

    def __init__(self, key, samples_per_client: int,
                 dirichlet_alpha: float = 0.5, dataset: str = "mnist",
                 image_dim: int = 784, n_classes: int = 10,
                 cache_size: int = 512):
        if samples_per_client < 1:
            raise ValueError("samples_per_client must be >= 1")
        if cache_size < 1:
            raise ValueError("cache_size must be >= 1")
        noise, template_scale = ((1.3, 0.35) if dataset == "mnist"
                                 else (4.0, 0.3))
        k_tmpl, k_eval, self._client_key = jax.random.split(key, 3)
        self.templates = (jax.random.normal(k_tmpl, (n_classes, image_dim))
                          * template_scale).astype(jnp.float32)
        self.samples_per_client = samples_per_client
        self.dirichlet_alpha = dirichlet_alpha
        self.n_classes = n_classes
        self.noise = noise
        self.eval_data = self._draw(k_eval, 2048, skew=False)
        self._cache: Dict[int, Dict[str, jnp.ndarray]] = {}
        self._cache_size = cache_size
        self.draws = 0      # client datasets drawn (cache misses)

    def _draw(self, key, n: int, skew: bool = True) -> Dict[str, jnp.ndarray]:
        k_prop, k_lbl, k_noise = jax.random.split(key, 3)
        if skew:
            # per-client Dirichlet label proportions = the non-IID skew
            props = jax.random.dirichlet(
                k_prop, jnp.full((self.n_classes,), self.dirichlet_alpha))
            y = jax.random.categorical(k_lbl, jnp.log(props + 1e-9), shape=(n,))
        else:
            y = jax.random.randint(k_lbl, (n,), 0, self.n_classes)
        x = self.templates[y] + jax.random.normal(
            k_noise, (n, self.templates.shape[1])) * self.noise
        return {"x": jax.nn.sigmoid(x).astype(jnp.float32),
                "y": y.astype(jnp.int32)}

    def client_batch(self, client_id: int) -> Dict[str, jnp.ndarray]:
        """Client ``client_id``'s fixed local dataset ``[m, ...]`` —
        deterministic in the id, cached while hot."""
        cid = int(client_id)
        hit = self._cache.get(cid)
        if hit is not None:
            return hit
        with telemetry.span("data.draw", client=cid):
            batch = self._draw(jax.random.fold_in(self._client_key, cid),
                               self.samples_per_client)
        self.draws += 1
        if len(self._cache) >= self._cache_size:
            self._cache.pop(next(iter(self._cache)))
        self._cache[cid] = batch
        return batch

    def cohort_batch(self, round_idx: int, cohort_idx) -> Dict[str, jnp.ndarray]:
        """The ``[A, m, ...]`` stack for a round's cohort (full-batch GD:
        round_idx is unused, each client always trains its fixed local
        set). Span ``blade.data``, counting the datasets ``draws`` (cache
        misses) and ``hits``."""
        ids = np.asarray(cohort_idx)
        with telemetry.span("data", round=int(round_idx)) as span:
            before = self.draws
            rows = [self.client_batch(i) for i in ids]
            span.set_metadata(draws=self.draws - before,
                              hits=ids.size - (self.draws - before))
            return jax.tree.map(lambda *xs: jnp.stack(xs), *rows)


class LMDataSource:
    """Synthetic token streams for the assigned-architecture train runs,
    stacked on a leading client axis."""

    def __init__(self, cfg: ModelConfig, shape: ShapeConfig, n_clients: int,
                 seed: int = 0):
        self.cfg, self.shape, self.n_clients = cfg, shape, n_clients
        self.seed = seed

    def round_batch(self, k: int) -> Dict[str, jnp.ndarray]:
        cfg, shape = self.cfg, self.shape
        key = jax.random.key(self.seed * 100_003 + k)
        b, s = shape.global_batch, shape.seq_len
        c = self.n_clients
        m = b // c
        if cfg.family == "vlm":
            p = cfg.vlm_prefix_len
            k1, k2 = jax.random.split(key)
            return {
                "patches": jax.random.normal(k1, (c, m, p, cfg.d_model), jnp.float32),
                "tokens": synthetic.lm_token_stream(k2, c * m, s - p, cfg.vocab
                                                    ).reshape(c, m, s - p),
            }
        if cfg.audio_frontend:
            k1, k2, k3 = jax.random.split(key, 3)
            return {
                "frames": jax.random.normal(k1, (c, m, s, cfg.d_model), jnp.float32),
                "mask_positions": jax.random.bernoulli(k2, 0.08, (c, m, s)),
                "targets": jax.random.randint(k3, (c, m, s), 0, cfg.vocab),
            }
        toks = synthetic.lm_token_stream(key, c * m, s, cfg.vocab)
        return {"tokens": toks.reshape(c, m, s)}

    def stacked_batches(self, n_rounds: int) -> Dict[str, jnp.ndarray]:
        """All K round batches stacked on a leading axis: leaves are
        [K, C, m, ...]. This is the xs tensor the compiled scan driver
        (core/rounds.run_blade_fl_scan with ``stacked=True``) consumes —
        per-round streams stay deterministic (same round_batch(k) draws)
        while the whole horizon runs without host round-trips."""
        per_round = [self.round_batch(k) for k in range(n_rounds)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *per_round)
