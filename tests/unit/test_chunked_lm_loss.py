"""``layers.chunked_lm_loss`` against the full-logits
``cross_entropy_with_logits`` on a tiny untied head: the loss and both
gradients, and the count of products and loops that says the gradient is
made in the forward — the guard against the recomputation coming back."""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import layers
from deepspeed_tpu.utils.logging import logger

ROWS, SEQ, HIDDEN, VOCAB = 2, 64, 32, 96


def _head(params, x):
    with jax.named_scope("lm_head"):
        return jnp.matmul(x, params["lm_head"]["kernel"],
                          preferred_element_type=jnp.float32)


def _inputs(dtype, labelled):
    keys = jax.random.split(jax.random.PRNGKey(48), 3)
    params = {"lm_head": {"kernel": (0.2 * jax.random.normal(
        keys[0], (HIDDEN, VOCAB))).astype(dtype)}}
    x = jax.random.normal(keys[1], (ROWS, SEQ, HIDDEN)).astype(dtype)
    labels = jax.random.randint(keys[2], (ROWS, SEQ), 0, VOCAB)
    if labelled == "partly":
        labels = labels.at[0, 5:23].set(-100).at[:, -1].set(-100)
    elif labelled == "none":
        labels = jnp.full_like(labels, -100)
    return params, x, labels


def _chunk_for(config_chunk):
    """``Mellum._lm_loss``'s rule: the whole sequence where ``loss_chunk``
    is 0 or does not divide it."""
    return SEQ if not config_chunk or SEQ % config_chunk else config_chunk


def _recomputed_loop(params, x, labels, chunk):
    """The form ``chunked_lm_loss`` replaced: a ``lax.map`` of a
    checkpointed body, the logits multiplied again on the way back."""
    return layers._plain_chunked_loss(_head, params, x, labels, chunk,
                                      recompute=True)


def _norm_gap(got, want):
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    return np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)


# dtype, what bounds the gradients' norm gap: float32 differs by the order
# of its sums; a bfloat16 d W leaves each chunk's product rounded where the
# full-logits form rounds once over all the rows (0.0029 at four chunks;
# 0.0035 with the chunks added in bfloat16, as the recomputed loop did)
DTYPES = {"float32": (jnp.float32, 2e-6), "bfloat16": (jnp.bfloat16, 6e-3)}


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("config_chunk,labelled,cotangent", [
    (SEQ, "all", 1.0), (16, "all", 1.0), (0, "all", 1.0),
    (24, "all", 1.0),                       # does not divide: falls back
    (16, "partly", 1.0), (16, "none", 1.0),
    (16, "partly", 4096.0),                 # the engine's loss scale
], ids=["chunk_is_seq", "chunk_divides", "chunk_0", "chunk_does_not_divide",
        "labels_partly_ignored", "labels_all_ignored", "cotangent_4096"])
def test_loss_and_both_gradients_match_the_full_logits(
        dtype, config_chunk, labelled, cotangent):
    dtype, limit = DTYPES[dtype]
    params, x, labels = _inputs(dtype, labelled)
    chunk = _chunk_for(config_chunk)

    def chunked(params, x):
        return cotangent * layers.chunked_lm_loss(_head, params, x, labels,
                                                  chunk)

    def full(params, x):
        return cotangent * layers.cross_entropy_with_logits(
            _head(params, x), labels)

    got, (got_w, got_x) = jax.value_and_grad(chunked, argnums=(0, 1))(
        params, x)
    want, (want_w, want_x) = jax.value_and_grad(full, argnums=(0, 1))(
        params, x)
    got_w, want_w = got_w["lm_head"]["kernel"], want_w["lm_head"]["kernel"]
    assert got_w.dtype == dtype and got_x.dtype == dtype
    assert got_w.shape == want_w.shape and got_x.shape == x.shape
    assert float(got) == pytest.approx(float(want), rel=2e-6, abs=1e-30)
    # the loss alone (no gradient asked) is the same number
    assert float(jax.jit(chunked)(params, x)) == pytest.approx(
        float(got), rel=1e-6, abs=1e-30)
    for g in (got_w, got_x):
        assert np.isfinite(np.asarray(g, np.float32)).all()
    if labelled == "none":
        assert float(got) == 0.0
        assert not np.asarray(got_w, np.float32).any()
        assert not np.asarray(got_x, np.float32).any()
    else:
        assert _norm_gap(got_w, want_w) < limit
        assert _norm_gap(got_x, want_x) < limit
        assert np.linalg.norm(np.asarray(want_x, np.float64)) > 0


def _counts(fun, *args):
    text = str(jax.make_jaxpr(fun)(*args))
    return (text.count("dot_general"),
            text.count("scan[") + text.count("while["))


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_three_products_a_chunk_in_one_loop(dtype, caplog):
    """One loop body in the printed jaxpr is one chunk: the differentiated
    loss holds the logits' product and its two transposes in ONE loop; the
    recomputed loop holds the logits' product twice, in two."""
    params, x, labels = _inputs(DTYPES[dtype][0], "partly")

    def chunked(params, x):
        return layers.chunked_lm_loss(_head, params, x, labels, 16)

    def recomputed(params, x):
        return _recomputed_loop(params, x, labels, 16)

    logger.propagate = True
    try:
        with caplog.at_level(logging.INFO, logger=logger.name):
            assert _counts(chunked, params, x) == (1, 1)
            assert _counts(jax.grad(chunked, argnums=(0, 1)), params,
                           x) == (3, 1)
    finally:
        logger.propagate = False
    assert [r.getMessage() for r in caplog.records] == [
        "chunked_lm_loss geometry: rows=2 seq=64 chunk=16 chunks=4 vocab=96 "
        "head_products_per_chunk=1 (primal)",
        "chunked_lm_loss geometry: rows=2 seq=64 chunk=16 chunks=4 vocab=96 "
        "head_products_per_chunk=3 (gradient in the forward)"]
    assert _counts(recomputed, params, x) == (1, 1)
    assert _counts(jax.grad(recomputed, argnums=(0, 1)), params, x) == (4, 2)
    # with respect to one argument alone nothing is recomputed either
    assert _counts(jax.grad(chunked), params, x) == (3, 1)


def test_a_float16_head_keeps_the_recomputed_loop():
    """float16 gradients need the loss scale inside ``d logits``: made in
    the forward, before the cotangent, they would flush to zero."""
    params, x, labels = _inputs(jnp.float16, "partly")
    scale = 2.0 ** 14

    def chunked(params, x):
        return scale * layers.chunked_lm_loss(_head, params, x, labels, 16)

    def full(params, x):
        return scale * layers.cross_entropy_with_logits(_head(params, x),
                                                        labels)

    assert _counts(jax.grad(chunked, argnums=(0, 1)), params, x) == (4, 2)
    got = jax.grad(chunked, argnums=(0, 1))(params, x)
    want = jax.grad(full, argnums=(0, 1))(params, x)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert g.dtype == jnp.float16
        assert _norm_gap(g, w) < 2e-3
