"""Shared neural-net building blocks (pure-functional, pytree params).

All modules are init/apply pairs over plain dict pytrees so they compose with
pjit sharding rules (repro.sharding.specs) and with the BLADE-FL client-axis
vmap (repro.core.rounds).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


def dense_init(key, in_dim: int, out_dim: int, dtype=jnp.float32, scale: float | None = None):
    scale = scale if scale is not None else in_dim ** -0.5
    return (jax.random.normal(key, (in_dim, out_dim)) * scale).astype(dtype)


def embed_init(key, vocab: int, dim: int, dtype=jnp.float32):
    return (jax.random.normal(key, (vocab, dim)) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm_init(dim: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((dim,), dtype=dtype)}


def rms_norm(params: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * params["scale"].astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embedding (half-rotation convention)
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [..., seq, heads, head_dim]; positions: [..., seq] int32."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)  # [half]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., seq, half]
    cos = jnp.cos(angles)[..., None, :]  # [..., seq, 1, half]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp_init(key, d_model: int, d_ff: int, kind: str, dtype=jnp.float32) -> Params:
    keys = jax.random.split(key, 3)
    gated = kind in ("swiglu", "geglu")
    p: Params = {"w_in": dense_init(keys[0], d_model, d_ff, dtype)}
    if gated:
        p["w_gate"] = dense_init(keys[1], d_model, d_ff, dtype)
    p["w_out"] = dense_init(keys[2], d_ff, d_model, dtype)
    return p


def mlp_apply(params: Params, x: jnp.ndarray, kind: str) -> jnp.ndarray:
    h = x @ params["w_in"]
    if kind == "swiglu":
        h = jax.nn.silu(x @ params["w_gate"]) * h
    elif kind == "geglu":
        h = jax.nn.gelu(x @ params["w_gate"]) * h
    elif kind == "squared_relu":
        h = jnp.square(jax.nn.relu(h))
    elif kind == "gelu":
        h = jax.nn.gelu(h)
    else:
        raise ValueError(f"unknown mlp kind {kind}")
    return h @ params["w_out"]


# ---------------------------------------------------------------------------
# Depthwise causal conv (mamba / xlstm local mixing; hubert conv-pos stub)
# ---------------------------------------------------------------------------


def causal_conv_init(key, channels: int, width: int, dtype=jnp.float32) -> Params:
    return {
        "w": (jax.random.normal(key, (width, channels)) * width ** -0.5).astype(dtype),
        "b": jnp.zeros((channels,), dtype=dtype),
    }


def causal_conv_apply(params: Params, x: jnp.ndarray) -> jnp.ndarray:
    """x: [B, T, C] -> depthwise causal conv over T."""
    w = params["w"]  # [W, C]
    width = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(width):  # width is small (4); unrolled adds
        out = out + pad[:, i : i + x.shape[1], :] * w[i]
    return out + params["b"]


def causal_conv_step(params: Params, conv_state: jnp.ndarray, x_t: jnp.ndarray):
    """Single decode step. conv_state: [B, W-1, C]; x_t: [B, C]."""
    w = params["w"]
    window = jnp.concatenate([conv_state, x_t[:, None, :]], axis=1)  # [B, W, C]
    out = jnp.einsum("bwc,wc->bc", window, w) + params["b"]
    return out, window[:, 1:, :]


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------


def softmax_cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray, mask=None):
    """logits: [..., V] (any dtype, upcast), labels int32 [...]."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    # Pick each label's logit with a one-hot select over the class axis, not a
    # gather: on a TPU the gather (and its scatter transpose) costs more than
    # the MLP's matmuls, while the select fuses with the logsumexp reduction.
    # `where`, not a multiply by a float one-hot, so an inf elsewhere in the
    # row cannot turn the pick into NaN.
    hit = labels[..., None] == jnp.arange(logits.shape[-1], dtype=labels.dtype)
    gold = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
    nll = logz - gold
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
