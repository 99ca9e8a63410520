"""softmax_cross_entropy picks each label's logit with a one-hot select.

The select must agree with the gather form it replaced (loss and gradient,
with and without a mask), and the compiled MLP training step must hold no
gather and no scatter, so the TPU's gather fusion cannot come back unnoticed.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers
from repro.models.mlp import init_mlp, mlp_loss


def _gather_cross_entropy(logits, labels, mask=None):
    """The gather form the select replaced, kept here as the test's reference."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def _inputs(vocab, dtype, masked, shape=(3, 17)):
    kx, ky, km = jax.random.split(jax.random.key(vocab), 3)
    logits = (4.0 * jax.random.normal(kx, shape + (vocab,))).astype(dtype)
    labels = jax.random.randint(ky, shape, 0, vocab, jnp.int32)
    # The first and last class are always among the labels.
    labels = labels.at[0, 0].set(0).at[0, 1].set(vocab - 1)
    mask = jax.random.bernoulli(km, 0.6, shape) if masked else None
    return logits, labels, mask


@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "mask"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("vocab", [10, 257])
def test_select_matches_gather(vocab, dtype, masked):
    logits, labels, mask = _inputs(vocab, dtype, masked)

    def grad_of(fn):
        return jax.jit(jax.value_and_grad(lambda z: fn(z, labels, mask)))(logits)

    loss, grad = grad_of(layers.softmax_cross_entropy)
    want_loss, want_grad = grad_of(_gather_cross_entropy)
    assert loss.dtype == jnp.float32 and grad.dtype == dtype
    np.testing.assert_allclose(np.asarray(loss), np.asarray(want_loss),
                               rtol=1e-6)
    # The gradient is computed in float32 either way and cast back to the
    # logits' dtype; bfloat16 may round a last-ulp float32 difference to one
    # bfloat16 ulp.
    rtol = 1e-6 if dtype == jnp.float32 else 2.0 ** -7
    got = np.asarray(grad.astype(jnp.float32))
    want = np.asarray(want_grad.astype(jnp.float32))
    np.testing.assert_allclose(got, want, rtol=rtol,
                               atol=rtol * np.abs(want).max())


def test_select_ignores_inf_off_the_label():
    """A -inf logit away from the label leaves the loss finite (where, not *)."""
    logits = jnp.array([[0.5, -jnp.inf, 1.5, -jnp.inf]])
    labels = jnp.array([2], jnp.int32)
    loss = layers.softmax_cross_entropy(logits, labels)
    assert np.isfinite(float(loss))
    np.testing.assert_allclose(
        float(loss), float(_gather_cross_entropy(logits, labels)), rtol=1e-6)


def _paper_batch(clients=None, batch=512, in_dim=784):
    lead = (batch,) if clients is None else (clients, batch)
    return {"x": jax.ShapeDtypeStruct(lead + (in_dim,), jnp.float32),
            "y": jax.ShapeDtypeStruct(lead, jnp.int32)}


@pytest.mark.parametrize("clients", [None, 20], ids=["one_client",
                                                     "vmap_20_clients"])
def test_mlp_step_compiles_without_gather_or_scatter(clients):
    params = init_mlp(jax.random.key(0))
    step = jax.value_and_grad(mlp_loss, has_aux=True)
    if clients is not None:
        params = jax.tree.map(
            lambda p: jnp.broadcast_to(p, (clients,) + p.shape), params)
        step = jax.vmap(step)
    text = jax.jit(step).lower(params, _paper_batch(clients)).compile().as_text()
    assert "gather(" not in text
    assert "scatter(" not in text
