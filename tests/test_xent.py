"""``softmax_xent`` against the formulation it replaced.

The gold logit used to be gathered from an fp32 copy of the whole logits;
it is now read inside an fp32 reduction over the vocabulary, so no such
copy exists (``tests/test_tpu_compile.py`` checks the compiled step). The
reference below is the old formulation, kept verbatim: run op by op on
the CPU, the loss and every gradient must be the same bits.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import softmax_xent

B, S, D, V = 3, 7, 64, 1000


def gathered_xent(logits, labels, mask=None):
    """The old formulation: an fp32 copy of the logits, then a gather."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _case(seed):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (B, S, D), jnp.bfloat16)
    w = (0.3 * jax.random.normal(k[1], (D, V))).astype(jnp.bfloat16)
    b = (0.3 * jax.random.normal(k[2], (V,))).astype(jnp.bfloat16)
    labels = jax.random.randint(k[3], (B, S), 0, V)
    mask = (jax.random.uniform(k[4], (B, S)) > 0.3).astype(jnp.float32)
    return x, w, b, labels, mask


def _same_bits(got, want):
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert g.dtype == r.dtype
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(r, np.float32))


@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "mask"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_xent_matches_gathered_form(seed, dtype, masked):
    """Loss and d(loss)/d(logits), for bf16 and fp32 logits."""
    x, w, b, labels, mask = _case(seed)
    logits = (x @ w + b).astype(dtype)
    m = mask if masked else None
    got = jax.value_and_grad(lambda l: softmax_xent(l, labels, m))(logits)
    want = jax.value_and_grad(lambda l: gathered_xent(l, labels, m))(logits)
    _same_bits(got, want)


@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "mask"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_xent_head_grads_match_gathered_form(seed, masked):
    """The loss and its gradients through a bf16 ``x @ w + b`` head."""
    x, w, b, labels, mask = _case(seed)
    m = mask if masked else None

    def through_head(xent):
        return jax.value_and_grad(
            lambda w, b, x: xent(x @ w + b, labels, m),
            argnums=(0, 1, 2))(w, b, x)

    _same_bits(through_head(softmax_xent), through_head(gathered_xent))
