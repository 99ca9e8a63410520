"""The MLP family (``model.arch == "mlp"``): the paper's in-hidden-classes
MLP on the synthetic MNIST proxy.

The data and the initial model are built here, not by the program, so the
plain reference (``reference.py``) can take the same inputs without taking
anything the program made. The formulas are copies of the program's own
generators (``repro.data.synthetic.mnist_proxy`` / ``dirichlet_partition``
as ``repro.data.pipeline.FLDataSource`` calls them, and
``repro.models.mlp.init_mlp``), so a cell sees the draws, labels and
partition that ``python -m repro.launch.train --seed <n>`` builds; the
floats agree to the last bits, since one jitted call fuses what the CLI
runs op by op (``tests/test_inputs.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from families import seed_key


def init_mlp(key, in_dim: int, hidden: int, n_classes: int):
    """``repro.models.mlp.init_mlp``: N(0, 1/fan_in) weights, zero biases."""
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (in_dim, hidden)) * in_dim ** -0.5,
        "b1": jnp.zeros((hidden,), jnp.float32),
        "w2": jax.random.normal(k2, (hidden, n_classes)) * hidden ** -0.5,
        "b2": jnp.zeros((n_classes,), jnp.float32),
    }


def _mnist_proxy(key, n, n_classes, image_dim, noise=1.3, scale=0.35):
    k_tmpl, k_lbl, k_noise = jax.random.split(key, 3)
    templates = jax.random.normal(k_tmpl, (n_classes, image_dim)) * scale
    y = jax.random.randint(k_lbl, (n,), 0, n_classes)
    x = templates[y] + jax.random.normal(k_noise, (n, image_dim)) * noise
    return jax.nn.sigmoid(x).astype(jnp.float32), y.astype(jnp.int32)


def dirichlet_partition(y: np.ndarray, n_clients: int, alpha: float,
                        samples_per_client: int, seed: int) -> np.ndarray:
    """Non-IID split, client i's labels ~ Dir(alpha): the program's
    ``dirichlet_partition``, host-side numpy."""
    rng = np.random.default_rng(seed)
    n_classes = int(y.max()) + 1
    by_class = [np.flatnonzero(y == c) for c in range(n_classes)]
    for idx in by_class:
        rng.shuffle(idx)
    out = np.zeros((n_clients, samples_per_client), dtype=np.int64)
    for i in range(n_clients):
        counts = rng.multinomial(samples_per_client,
                                 rng.dirichlet(np.full(n_classes, alpha)))
        chosen = [rng.choice(by_class[c], size=k, replace=len(by_class[c]) < k)
                  for c, k in enumerate(counts)]
        flat = np.concatenate(chosen)
        rng.shuffle(flat)
        out[i] = flat[:samples_per_client]
    return out


def model_dims(cfg):
    m = cfg["model"]
    return m["in_dim"], m["hidden"], m["n_classes"]


def build(cfg, seed: int):
    """Everything a cell's run needs from the seed, on the device:
    ``{"key", "run_key", "params"}`` and, for a partitioned population,
    ``"batch"`` ({"x": [C, m, d], "y": [C, m]}). The key chain is the
    program's: data from ``key``, the model from ``fold_in(key, 1)``, the
    run from ``fold_in(key, 2)``."""
    key = seed_key(seed)
    in_dim, hidden, n_classes = model_dims(cfg)
    data = cfg["data"]
    out = {"key": key, "run_key": jax.random.fold_in(key, 2)}
    if data["kind"] == "partitioned":
        c, m = cfg["round_spec"]["n_clients"], data["samples_per_client"]
        n_total = c * m * 2 + data["n_eval"]

        @jax.jit
        def make(key):
            x, y = _mnist_proxy(key, n_total, n_classes, in_dim)
            return (x[:-data["n_eval"]], y[:-data["n_eval"]],
                    init_mlp(jax.random.fold_in(key, 1), in_dim, hidden,
                             n_classes))

        x, y, out["params"] = make(key)
        part = dirichlet_partition(np.asarray(y), c, data["dirichlet_alpha"],
                                   m, seed)
        idx = jnp.asarray(part)
        out["batch"] = {"x": x[idx], "y": y[idx]}
    else:
        out["params"] = jax.jit(lambda k: init_mlp(
            jax.random.fold_in(k, 1), in_dim, hidden, n_classes))(key)
    return out


def to_host(inputs):
    """The reference's inputs: the initial parameters as float64 arrays
    and, for a partitioned population, the clients' ``x`` and ``y``."""
    host = {"params": {n: np.asarray(v, np.float64)
                       for n, v in inputs["params"].items()}}
    if "batch" in inputs:
        host.update(x=inputs["batch"]["x"], y=inputs["batch"]["y"])
    return host


def mlp_step_flops(in_dim: int, hidden: int, n_classes: int) -> int:
    """Matmul FLOPs of one training sample of the MLP in->hidden->classes:
    the forward pass (2 per weight), the weight gradients (2 per weight)
    and the gradient into the hidden layer (2 per second-layer weight).
    The gradient into the input is never needed and never computed."""
    w1, w2 = in_dim * hidden, hidden * n_classes
    return 2 * (w1 + w2) + 2 * (w1 + w2) + 2 * w2


def round_flops(cfg) -> int:
    """Training FLOPs of one integrated round: tau local steps of every
    client over its whole local set. The global-loss eval and the PoW
    hashes are left out."""
    rs = cfg["round_spec"]
    return (mlp_step_flops(*model_dims(cfg))
            * cfg["data"]["samples_per_client"] * rs["tau"] * rs["n_clients"])
