"""``models/expert_shard.held_experts_ffn``: only the (token, choice) pairs
held here are gathered, multiplied and combined, a pass of ``capacity``
rows at a time, and no routing drops a pair.  The grouped product runs
through Pallas' interpreter, as in ``test_deepseek_v2.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import expert_shard
from deepspeed_tpu.models.layers import gated_silu_mlp

HIDDEN, WIDTH, TILE = 32, 16, 16


def _experts(held, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    return {"gate_up": 0.3 * jax.random.normal(
                keys[0], (held, HIDDEN, 2 * WIDTH)),
            "down": 0.3 * jax.random.normal(keys[1], (held, WIDTH, HIDDEN))}


def _dense(x, weights, ids, valid, experts, first_expert):
    """Every held expert over every token in float32, weighted where the
    token chose it."""
    y = np.zeros(x.shape, np.float32)
    for e in range(experts["down"].shape[0]):
        f = np.asarray(gated_silu_mlp(
            {"gate_up": {"kernel": experts["gate_up"][e]},
             "down": {"kernel": experts["down"][e]}}, x))
        chosen = (np.asarray(ids) == first_expert + e) \
            & np.asarray(valid)[:, None]
        y += (np.asarray(weights) * chosen).sum(axis=1)[:, None] * f
    return y


def _uniform_ids(tokens, top_k, routed, seed):
    """``top_k`` distinct experts a token, uniform over the ``routed``."""
    keys = jax.random.split(jax.random.PRNGKey(seed), tokens)
    return jnp.stack([jax.random.permutation(k, routed)[:top_k]
                      for k in keys]).astype(jnp.int32)


# name -> (tokens, top_k, held, routed, first_expert, ids: None uniform or
# a function of (tokens, top_k), valid tokens, (capacity, passes) expected)
CASES = {
    "uniform_top6_20_of_160": (64, 6, 20, 160, 0, None, 64, (96, 1)),
    "uniform_top8_16_of_128": (48, 8, 16, 128, 32, None, 48, (96, 1)),
    # 120 pairs, all of them on two held experts: four passes of 32
    "every_pair_held_here": (
        40, 3, 20, 160, 40,
        lambda t, k: jnp.broadcast_to(jnp.asarray([41, 47, 41]), (t, k)),
        40, (32, 4)),
    "no_pair_held_here": (
        24, 4, 16, 128, 16,
        lambda t, k: jnp.broadcast_to(jnp.asarray([0, 15, 32, 127]), (t, k)),
        24, (32, 1)),
    "padding_rows_invalid": (40, 6, 20, 160, 20, None, 29, (64, 1)),
    # 21 pairs: a capacity of one tile means every pair, and no loop
    "pairs_not_a_multiple_of_the_tile": (7, 3, 4, 16, 4, None, 7, (21, 1)),
    "pairs_over_a_tile_not_a_multiple": (25, 3, 4, 16, 0, None, 25, (48, 1)),
    "capacity_is_every_pair": (16, 4, 8, 16, 8, None, 16, (64, 1)),
}


@pytest.mark.parametrize("case", CASES)
def test_held_pairs_alone_are_computed_and_none_is_dropped(case):
    tokens, top_k, held, routed, first, make_ids, n_valid, want = CASES[case]
    capacity, passes = want
    experts = _experts(held)
    x = jax.random.normal(jax.random.PRNGKey(5), (tokens, HIDDEN))
    ids = (_uniform_ids(tokens, top_k, routed, 11) if make_ids is None
           else make_ids(tokens, top_k))
    weights = jax.random.uniform(jax.random.PRNGKey(6), (tokens, top_k),
                                 minval=0.1, maxval=1.0)
    valid = jnp.arange(tokens) < n_valid
    assert expert_shard.pair_capacity(
        tokens * top_k, held, routed, TILE) == capacity
    y, counts = jax.jit(lambda *a: expert_shard.held_experts_ffn(
        *a, first_expert=first, interpret=True, tiling=(TILE, 128, 128),
        routed=routed))(x, weights, ids, valid, experts)
    assert y.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(y), _dense(x, weights, ids, valid, experts, first),
        rtol=1e-4, atol=1e-5)
    assert not np.asarray(y[n_valid:]).any()
    local = np.asarray(ids) - first
    here = (local >= 0) & (local < held) & np.asarray(valid)[:, None]
    assert counts.tolist() == [
        *np.bincount(local[here], minlength=held),
        n_valid * top_k - here.sum()]
    assert int(expert_shard.pair_passes(
        counts, tokens * top_k, routed, TILE)) == passes


def test_capacity_follows_from_shapes_alone():
    # the cells' layers: DeepSeek-V2 prefill and decode, K-EXAONE's; one
    # row tile is a decode step's, and a pass then holds every pair
    assert expert_shard.pair_capacity(8192 * 6, 20, 160, 256) == 12288
    assert expert_shard.pair_capacity(64 * 6, 20, 160, 128) == 384
    assert expert_shard.pair_capacity(4096 * 8, 16, 128, 256) == 8192
    assert expert_shard.pair_capacity(64 * 8, 16, 128, 128) == 512
    # never above the pairs there are; all of them with no router width
    assert expert_shard.pair_capacity(9, 4, 16, 128) == 9
    assert expert_shard.pair_capacity(384, 20, None, 128) == 384
