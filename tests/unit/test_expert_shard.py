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


def _bits(a):
    return np.asarray(a).view(np.int32)


@pytest.mark.parametrize("case", CASES)
def test_the_sort_the_sizes_and_each_passs_pairs_are_the_old_forms(case):
    """``sorted_pairs`` and ``pairs_of_pass`` against the forms they took
    the place of, written out here: the group sizes as a scatter-add, each
    pair's place as a scatter of the order, a sorted pair's weight as a
    gather by the order, a pass's pairs as a gather of the order at
    clamped positions — the same integers and bits, with the pass a Python
    integer (``reverse``) and a traced one (the loop's counter)."""
    tokens, top_k, held, routed, first, make_ids, n_valid, want = CASES[case]
    capacity, _ = want
    pairs = tokens * top_k
    ids = (_uniform_ids(tokens, top_k, routed, 11) if make_ids is None
           else make_ids(tokens, top_k))
    local = ids - first
    here = (local >= 0) & (local < held) & (jnp.arange(tokens) < n_valid)[
        :, None]
    group = jnp.where(here, local, held).reshape(-1)
    weights = jax.random.uniform(jax.random.PRNGKey(6), (pairs,))
    order, sorted_weights, place, sizes = jax.jit(
        expert_shard.sorted_pairs, static_argnums=(2, 3))(
        group, weights, held + 1, capacity)
    old_order = jnp.argsort(group, stable=True)
    passes = -(-pairs // capacity)
    assert order.shape == sorted_weights.shape == (passes * capacity,)
    assert (np.asarray(order[:pairs]) == np.asarray(old_order)).all()
    assert (_bits(sorted_weights[:pairs]) == _bits(weights[old_order])).all()
    assert (np.asarray(place) == np.asarray(
        jnp.zeros_like(old_order).at[old_order].set(jnp.arange(pairs)))).all()
    assert (np.asarray(sizes) == np.asarray(
        jnp.zeros((held + 1,), jnp.int32).at[group].add(1))).all()
    assert sizes.dtype == jnp.int32 and place.dtype == old_order.dtype
    traced = jax.jit(expert_shard.pairs_of_pass, static_argnums=2)
    for p in range(passes):
        old = old_order[jnp.minimum(p * capacity + jnp.arange(capacity),
                                    pairs - 1)]
        assert (np.asarray(expert_shard.pairs_of_pass(order, p, capacity))
                == np.asarray(old)).all()
        assert (np.asarray(traced(order, jnp.int32(p), capacity))
                == np.asarray(old)).all()


def _old_group_limited_topk(scores, n_group, topk_group, top_k):
    """The choice as it was: the kept groups scattered into a mask, and
    ``lax.top_k`` with its own reverse rule (a scatter of ``d weights``)."""
    tokens, experts = scores.shape
    grouped = scores.reshape(tokens, n_group, experts // n_group)
    _, best_groups = jax.lax.top_k(grouped.max(axis=-1), topk_group)
    keep = jnp.zeros((tokens, n_group), bool).at[
        jnp.arange(tokens)[:, None], best_groups].set(True)
    masked = jnp.where(keep[:, :, None], grouped, 0.0).reshape(
        tokens, experts)
    return jax.lax.top_k(masked, top_k)


@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
@pytest.mark.parametrize("n_group,topk_group", [(1, 1), (8, 3), (8, 8)],
                         ids=["one_group", "3_of_8_groups", "8_of_8_groups"])
def test_the_choice_and_its_gradient_are_lax_top_ks_to_the_last_bit(
        n_group, topk_group, scoring):
    """``group_limited_topk`` — no scatter for the kept groups, none on the
    way back — against the old form: weights and d scores equal bit for
    bit, ids equal, under a probe with zeros and negative entries."""
    tokens, experts, top_k = 96, 64, 6
    x = jax.random.normal(jax.random.PRNGKey(2), (tokens, HIDDEN))
    kernel = jax.random.normal(jax.random.PRNGKey(3), (HIDDEN, experts))
    scores = expert_shard.router_scores(x, kernel, scoring)
    probe = jax.random.normal(jax.random.PRNGKey(4), (tokens, top_k))
    probe = probe.at[::7].set(0.0)

    def through(choose):
        def f(scores):
            weights, ids = choose(scores, n_group, topk_group, top_k)
            return jnp.sum(weights * probe), (weights, ids)
        return jax.jit(jax.value_and_grad(f, has_aux=True))(scores)

    (_, (w, ids)), d = through(expert_shard.group_limited_topk)
    (_, (w_old, ids_old)), d_old = through(_old_group_limited_topk)
    assert (np.asarray(ids) == np.asarray(ids_old)).all()
    assert (_bits(w) == _bits(w_old)).all()
    assert (_bits(d) == _bits(d_old)).all()
    assert np.asarray(d).any()


def test_the_aux_losss_load_is_the_scatter_adds():
    ids = _uniform_ids(160, 6, 64, 13)
    scores = jax.nn.softmax(jax.random.normal(
        jax.random.PRNGKey(8), (160, 64)), axis=-1)
    load = jnp.zeros((64,), jnp.float32).at[ids.reshape(-1)].add(1.0) / 160
    old = 64 * jnp.sum(load * scores.mean(0))
    assert _bits(expert_shard.aux_load_balance(scores, ids, 64)) == _bits(old)


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


# -- the layer differentiated: d x, d weights, d expert weights ---------------

def _dense_jax(x, weights, ids, valid, experts, first_expert):
    """``_dense`` in jax, for ``jax.grad``."""
    y = jnp.zeros(x.shape, jnp.float32)
    with jax.default_matmul_precision("highest"):
        for e in range(experts["down"].shape[0]):
            f = gated_silu_mlp(
                {"gate_up": {"kernel": experts["gate_up"][e]},
                 "down": {"kernel": experts["down"][e]}}, x)
            chosen = (ids == first_expert + e) & valid[:, None]
            y = y + (weights * chosen).sum(axis=1)[:, None] * f
    return y


GRAD_CASES = {
    # every pair on two held experts: the second, third and fourth passes run
    "every_pair_held_here": "reverse",
    "uniform_top6_20_of_160": "reverse",
    "no_pair_held_here": "reverse",
    "padding_rows_invalid": "reverse",
    # a capacity of every pair: the loop-free form, by plain autodiff
    "capacity_is_every_pair": "plain",
}


@pytest.mark.parametrize("case", GRAD_CASES)
def test_layer_gradients_match_the_dense_sum_and_nothing_is_dropped(case):
    tokens, top_k, held, routed, first, make_ids, n_valid, want = CASES[case]
    experts = _experts(held)
    x = jax.random.normal(jax.random.PRNGKey(5), (tokens, HIDDEN))
    ids = (_uniform_ids(tokens, top_k, routed, 11) if make_ids is None
           else make_ids(tokens, top_k))
    weights = jax.random.uniform(jax.random.PRNGKey(6), (tokens, top_k),
                                 minval=0.1, maxval=1.0)
    valid = jnp.arange(tokens) < n_valid
    probe = jax.random.normal(jax.random.PRNGKey(7), (tokens, HIDDEN))

    def layer(x, weights, experts):
        y, counts = expert_shard.held_experts_ffn(
            x, weights, ids, valid, experts, first_expert=first,
            interpret=True, tiling=(TILE, 128, 128), routed=routed,
            reverse=GRAD_CASES[case] == "reverse")
        return jnp.sum(y * probe), (y, counts)

    def dense(x, weights, experts):
        return jnp.sum(_dense_jax(x, weights, ids, valid, experts, first)
                       * probe)

    (_, (y, counts)), got = jax.jit(jax.value_and_grad(
        layer, argnums=(0, 1, 2), has_aux=True))(x, weights, experts)
    want_grads = jax.grad(dense, argnums=(0, 1, 2))(x, weights, experts)
    np.testing.assert_allclose(
        np.asarray(y), _dense(x, weights, ids, valid, experts, first),
        rtol=1e-4, atol=1e-5)
    assert int(expert_shard.pair_passes(
        counts, tokens * top_k, routed, TILE)) == want[1]
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=2e-4,
                                   atol=2e-5)


@pytest.mark.parametrize("p,held_pairs", [
    (3, 120), (3, 101), (3, 96), (3, 60), (2, 120), (0, 7)], ids=[
    "filled_last_pass_every_real_pair_held", "filled_last_pass_five_held",
    "filled_last_pass_none_held", "filled_last_pass_held_pairs_end_before_it",
    "whole_pass_every_pair_held", "first_pass_seven_held"])
def test_d_weights_scatter_gives_each_held_row_a_pair_of_its_own(
        p, held_pairs):
    """The reverse rule of ``_weighted_rows`` scatters a pass's dots to
    their pairs with ``unique_indices``.  That holds because the held pairs
    sort first, so a row below ``held_rows`` is never the filling of the last
    pass (the last pair again and again): 120 pairs in passes of 32, the last
    one of 24 pairs and 8 fillers, against the form the scatter took the
    place of, ``dots[row]`` gathered back — equal bit for bit."""
    tokens, top_k, capacity = 40, 3, 32
    pairs, lo = tokens * top_k, p * capacity
    order = jax.random.permutation(jax.random.PRNGKey(1), pairs).astype(
        jnp.int32)
    place = jnp.zeros_like(order).at[order].set(
        jnp.arange(pairs, dtype=jnp.int32)).reshape(tokens, top_k)
    weights = jax.random.uniform(jax.random.PRNGKey(2), (tokens, top_k))
    filled = jnp.pad(order, (0, -pairs % capacity), mode="edge")
    pair = expert_shard.pairs_of_pass(filled, p, capacity)
    row = place - lo
    inside = (place < held_pairs) & (row >= 0) & (row < capacity)
    row, group = jnp.where(inside, row, 0), jnp.where(inside, 0, -1)
    held_rows = jnp.int32(np.clip(held_pairs - lo, 0, capacity))
    assert int(inside.sum()) == int(held_rows)
    if p == 3:   # the filling is there, and is the last pair's
        assert (np.asarray(pair[24:]) == int(order[-1])).all()
    out = jax.random.normal(jax.random.PRNGKey(3), (capacity, HIDDEN))
    g = jax.random.normal(jax.random.PRNGKey(4), (tokens, HIDDEN))

    def summed(weights):
        return expert_shard._weighted_rows(
            None, jnp.zeros((tokens, HIDDEN)), out, weights, row, group,
            pair, weights.reshape(-1)[pair], held_rows)

    def gathered():
        dots = jnp.sum(g[pair // top_k] * out, axis=-1)
        return jnp.where(group >= 0, dots[row], 0.0)

    got, = jax.jit(lambda w: jax.vjp(summed, w)[1](g))(weights)
    old = jax.jit(gathered)()
    assert (_bits(got) == _bits(old)).all()
    assert int((np.asarray(got) != 0).sum()) == int(held_rows)


@pytest.mark.parametrize("sizes", [(16, 0, 32, 16), (5, 27, 0, 32),
                                   (0, 0, 0, 64), (30, 30, 4, 0)],
                         ids=str)
def test_grouped_product_gradient_leaves_out_the_groups_held_elsewhere(sizes):
    """Three held groups and a fourth (the last of ``sizes``) whose rows are
    nobody's: d lhs zero there, d rhs the held groups' rows alone, an empty
    group's zero."""
    from deepspeed_tpu.ops.transformer.grouped_matmul import (
        moe_grouped_matmul)

    m, k, n = sum(sizes), 32, 48
    lhs = jax.random.normal(jax.random.PRNGKey(1), (m, k))
    rhs = jax.random.normal(jax.random.PRNGKey(2), (3, k, n))
    probe = jax.random.normal(jax.random.PRNGKey(3), (m, n))
    group_sizes = jnp.asarray(sizes, jnp.int32)
    group = np.repeat(np.arange(4), sizes)
    held = jnp.asarray(group < 3)[:, None]

    def kernel(lhs, rhs):
        return jnp.sum(moe_grouped_matmul(
            lhs, rhs, group_sizes, tiling=(16, 128, 128), interpret=True)
            * probe)

    def plain(lhs, rhs):
        out = jnp.einsum("mk,mkn->mn", lhs, rhs[np.minimum(group, 2)],
                         precision="highest")
        return jnp.sum(jnp.where(held, out, 0.0) * probe)

    got = jax.grad(kernel, argnums=(0, 1))(lhs, rhs)
    want = jax.grad(plain, argnums=(0, 1))(lhs, rhs)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-4,
                                   atol=1e-4)
    assert not np.asarray(got[0])[group == 3].any()


def test_plain_softmax_router_renormalises_over_all_the_chosen():
    x = jax.random.normal(jax.random.PRNGKey(0), (12, HIDDEN))
    kernel = jax.random.normal(jax.random.PRNGKey(1), (HIDDEN, 8))
    scores = expert_shard.router_scores(x, kernel, "softmax")
    weights, ids = expert_shard.route(
        x, kernel, n_group=1, topk_group=1, top_k=3, scaling=1.0,
        scoring="softmax", renormalise=True)
    p = np.asarray(jax.nn.softmax(jnp.matmul(
        x, kernel, precision="highest"), axis=-1))
    np.testing.assert_allclose(np.asarray(scores), p, rtol=1e-5)
    top = np.argsort(-p, axis=-1)[:, :3]
    assert (np.asarray(ids) == top).all()
    chosen = np.take_along_axis(p, top, axis=-1)
    np.testing.assert_allclose(np.asarray(weights),
                               chosen / chosen.sum(-1, keepdims=True),
                               rtol=1e-5)


def test_aux_load_balance_by_hand_and_its_gradient_through_the_scores():
    # 4 tokens, 2 choices, 4 experts: loads 3, 2, 2, 1 pairs over 4 tokens
    ids = jnp.asarray([[0, 1], [0, 2], [0, 3], [1, 2]], jnp.int32)
    scores = jnp.asarray([[.4, .3, .2, .1]] * 4, jnp.float32)
    want = 4 * (0.75 * .4 + 0.5 * .3 + 0.5 * .2 + 0.25 * .1)
    assert float(expert_shard.aux_load_balance(scores, ids, 4)) == \
        pytest.approx(want, rel=1e-6)
    # an even load and even scores: top_k
    even_ids = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    assert float(expert_shard.aux_load_balance(
        jnp.full((2, 4), 0.25), even_ids, 4)) == pytest.approx(2.0)
    grad = jax.grad(expert_shard.aux_load_balance)(scores, ids, 4)
    np.testing.assert_allclose(
        np.asarray(grad), np.tile([.75, .5, .5, .25], (4, 1)), rtol=1e-6)


def _routed_pairs(tokens, top_k, held, routed, fixed):
    """``(group, row)`` of a seeded routing sorted by expert as the layer
    sorts it, ``fixed`` {token: its experts} written over the draw."""
    ids = np.array(_uniform_ids(tokens, top_k, routed, 3))
    for token, experts in fixed.items():
        ids[token] = experts
    group = np.where(ids < held, ids, held)
    order = np.argsort(group.reshape(-1), kind="stable")
    row = np.zeros(tokens * top_k, np.int32)
    row[order] = np.arange(tokens * top_k)
    return (np.where(group < held, group, -1).astype(np.int32),
            row.reshape(tokens, top_k))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("weighted", [True, False],
                         ids=["weights", "ones"])
@pytest.mark.parametrize("tokens,tile", [(48, 16), (45, 16), (21, 256)],
                         ids=["whole_tiles", "last_tile_partly_padding",
                              "one_tile_of_every_token"])
def test_the_way_out_kernel_sums_the_rows_of_the_pairs_here_and_no_other(
        dtype, weighted, tokens, tile):
    """``moe_gather_combine`` alone against ``sum_j where(inside, c *
    src[row])`` in float32: tokens with none, one and all eight of their
    choices here, pairs of another pass (their rows here, their group not),
    and every row of ``src`` that no pair here owns NaN — a neighbour in a
    fetched chunk must not reach a sum."""
    from deepspeed_tpu.ops.transformer.gather_combine import (
        moe_gather_combine)
    top_k, held, routed = 8, 8, 32
    group, row = _routed_pairs(
        tokens, top_k, held, routed,
        {0: np.arange(8, 16), 1: [3] + list(range(20, 27)),
         2: np.arange(8)[::-1], tokens - 1: np.arange(8)})
    group[5:9] = -1      # another pass's pairs
    inside = group >= 0
    assert inside[0].sum() == 0 and inside[1].sum() == 1
    assert inside[2].all() and inside[-1].all()
    rows = -(-int(row[inside].max() + 1) // 16) * 16 + 16
    src = np.array(jax.random.normal(jax.random.PRNGKey(4), (rows, HIDDEN)))
    owned = np.zeros(rows, bool)
    owned[row[inside]] = True
    src[~owned] = np.nan
    src = jnp.asarray(src, dtype)
    c = np.asarray(jax.random.uniform(
        jax.random.PRNGKey(9), (tokens, top_k), minval=0.1, maxval=1.0))
    row = np.where(inside, row, 0)
    got = moe_gather_combine(
        src, jnp.asarray(row), jnp.asarray(group),
        jnp.asarray(c) if weighted else None, held=held, tile=tile,
        interpret=True)
    want = np.zeros((tokens, HIDDEN), np.float32)
    values = np.asarray(src.astype(jnp.float32))
    for j in range(top_k):
        term = (c[:, j, None] if weighted else 1.0) * values[row[:, j]]
        want = want + np.where(inside[:, j, None], term, np.float32(0))
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert not np.isnan(np.asarray(got)).any()
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)
    assert (np.asarray(got)[0] == 0).all()      # no pair here: zeros


@pytest.mark.parametrize("held_pairs,want", [
    # one pass of 256 rows: 3 * 256 ways in + 2 * 64 ways out of
    # 3 * 256 + 2 * 512
    ([40, 24], (768 + 128) / 1792),
    ([0, 0], 768 / 1792),
    ([256, 0], (768 + 512) / 1792),
    # two passes
    ([200, 100], (2 * 768 + 600) / (2 * 1792)),
])
def test_rows_moved_share_is_the_hand_count(held_pairs, want):
    counts = jnp.asarray(held_pairs + [512 - sum(held_pairs)], jnp.int32)
    assert expert_shard.pair_capacity(512, 2, 8, 16) == 256
    got = float(expert_shard.rows_moved_share(counts, 512, 8, 16))
    assert got == pytest.approx(want, rel=1e-6)
