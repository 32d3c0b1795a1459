"""One chip's share of an expert-parallel mixture-of-experts layer, for
serving and for training: routing over ALL the experts at the published
width, and the part of the result that the experts held here give.

Three published routers run through :func:`route`: DeepSeek-V2's
(``softmax`` scores, group-limited, the chosen scores themselves as
weights, not renormalised), the DeepSeek-V3 lineage's that K-EXAONE
uses (``sigmoid`` scores, the choice made on ``score + bias``, the weights
the chosen experts' scores renormalised over ALL the chosen, held here or
not; one group, so the group limit does nothing), and the plain one Mellum
trains (``softmax`` scores, one group, the chosen renormalised), whose
load-balancing loss is :func:`aux_load_balance`.

The layer is told which experts it holds (``first_expert``, and as many as
its weights have: a routing group, or any contiguous slice).  Every token
is routed over all the experts the router scores; the (token, choice) pairs
that fall to held experts are sorted by expert and multiplied by a grouped
matrix product (``ops/transformer/grouped_matmul.py``); pairs that fall to
experts held elsewhere are left out of the sum, and no code stands in for
the other chips or for their exchange.

Only the pairs held here are moved.  One chip of ``n`` holds ``1/n`` of the
pairs on average, so :func:`held_experts_ffn` gathers, multiplies and
combines ``capacity = 2 * (held / routed experts) * tokens * top_k`` sorted
pairs at a time (whole row tiles, never more than there are:
:func:`pair_capacity`, from shapes alone) and forms no array of ``tokens *
top_k`` rows.  That is a size of the buffers, not a limit on the routing:
held pairs past one pass are computed by further trips of the same loop
(:func:`pair_passes`: 1.0 in the decode programs' counters wherever the
capacity held), so no token is ever dropped, whatever the
imbalance.  ``models/moe.py`` is the other expert layer: GShard capacity
routing that drops overflow, over an ``expert`` mesh axis.

The layer is differentiable in ``x``, the combine weights and the expert
weights.  Its ways in and out are gathers in both directions
(:func:`_rows_of_pairs`, :func:`_weighted_rows`: the reverse of a gather
of sorted pairs is a gather by the inverse permutation, never XLA's row
scatter, which the readings below put at 1.4 us a row), and the grouped
product has a gradient rule of its own.  The index arithmetic around them
— how many pairs a group got, where a pair stands among the sorted, which
lane a chosen score's gradient belongs to — is dense wherever the shapes
allow a compare (:func:`sorted_pairs`, :func:`_top_k`,
:func:`aux_load_balance`): XLA's gather or scatter of SCALARS costs 4.6 to
8.7 ns an element, so a histogram of 262,144 pairs into 17 bins took as
long as a grouped product's quarter.  What the choice and the sort produce
carries a name (``SAVED_NAMES``), so that a model which recomputes its layers
on the way back keeps them and routes once a step.  A ``while_loop`` over the
passes has no reverse rule, so a training program asks for ``reverse=True``:
the first pass as it is, and each further pass the routing could need under a
``cond`` on ``pair_passes``, recomputed on the way back, so that a pass that
does not run costs neither time nor memory in either direction.  The program
that asked for ``reverse`` — the one with a backward to pay — takes its two
ways out (the choices' weighted sum and d x: :func:`_sum_of_rows`) through
``ops/transformer/gather_combine.py``, which moves the rows of the pairs held
here alone.  The serving programs keep the ways out they had.

Readings that chose the form (on a v5e; PERF.md section 6, PR 39).  One
DeepSeek-V2 expert layer, 8,192 tokens, 6 choices, 20 of 160 experts:
moving every pair took 16.9 ms, of it 3.7 ms the gather back of 49,152
rows, 2.9 ms the relayout of the float32 ``[tokens, 6, hidden]`` array and
2.2 ms its weighted sum.  The way out as ``top_k`` gathers of ``[tokens,
hidden]`` from the compact output, summed in one fusion: 10.1 ms.  As a
scatter-add of the ``capacity`` weighted rows: 21.9 ms — XLA's row scatter
with repeated indices runs row after row, 1.4 us each.  The first pass
outside the loop would save 0.5 ms of such a layer (the carried sum is
zeroed and read once more) and cost set-up more than that is worth: a
program that holds the body twice lowered 1.3 s and loaded 1.2 s slower in
K-EXAONE's cell, 7% of a warm set-up.  In the decode programs any control
flow in the layer — the loop, or a ``cond`` around it, even when it never
runs — costs 0.1 to 0.3 ms a step: XLA no longer prefetches the shared
experts' and the next layer's weights across it (48 of 104 ``slice-start``
gone from DeepSeek-V2's compiled step).  A capacity of one row tile is a
decode step's, whose pairs are a few tiles in all and cost microseconds to
move: there a pass takes every pair, the program holds no loop, and the
way out stays one gather of all the pairs with their weighted sum —
``top_k`` gathers of 64 rows cost 26 to 42 us a layer more than that
(1.367 for 1.341 ms, 1.753 for 1.711).

Readings that chose the training program's ways (PERF.md section 6, PR
44).  XLA's row gather moves ~85 GB/s whoever owns the row: 54 ns a row
of 2,304 bfloat16, 112 of 5,120, 128 of 6,144 — Mellum's layer (32,768
tokens, 8 of 64 experts a token, 16 held) gathers 917,504 rows a step,
three of four of the ways out's masked on arrival.  The way out alone,
XLA's ``top_k`` gathers against ``moe_gather_combine`` (equal to the last
bit): 14.26 ms for 4.56 at Mellum's geometry (70 ns a HELD row: the sums
read single rows out of VMEM, a sublane of eight at work), 5.50 for 1.35
at DeepSeek-V2's prefill (8,192 tokens, 6 choices, 20 of 160) and 8.43
for 1.77 at K-EXAONE's (8 choices, 16 of 128) with the source in HBM —
where the serving cell's program has it in XLA's faster memory a row
cost 19 ns (PR 39), 1.25 ms, so the serving programs were left alone.
The ways in cut at ``held_rows`` (a loop of 8,192-row gathers into a
zeroed buffer, or ``cond`` chunks concatenated) gained nothing on the
layer alone (57.24 and 59.43 ms forward + backward for 57.18) and cost
0.3 GB: the carried buffer is copied.  A DMA cannot move one row of a
tiled array, which is why the kernel copies chunks and why no kernel
moves the ways in.

Readings that chose the index arithmetic (PERF.md section 6, PR 45; one
v5e, Mellum's geometry: 262,144 pairs, 17 groups, a pass of 131,072).
The group sizes as a scatter-add 2.33 ms, by compare-and-sum 0.21; the aux
loss's load (four sequences' 64 bins) 2.35 for 0.20; ``lax.top_k``'s own
reverse rule (a scatter into ``[32768, 64]``) 1.99 for the one-hot sum's
0.23; a pass's pairs gathered out of the order 1.16, sliced 0.19 with the
division; each pair's place by a scatter of the order 1.25, counted 0.38;
a pair's weight gathered out of ``[tokens, top_k]`` 1.19, carried through
the sort as a third operand +0.13 (the sort 0.26 -> 0.40; ``top_k``
itself, a full sort of the scores on this compiler, 0.36).  In the cell a
layer ran the choice, the sort and every forward-side op twice (``remat``):
kept by name they run once.
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops.transformer.gather_combine import moe_gather_combine
from ..ops.transformer.grouped_matmul import moe_grouped_matmul
from ..utils.logging import logger
from .layers import gated_silu


# what the choice and the sort leave for a layer's backward, by name: the
# chosen scores and ids, the sorted pairs' order and weights, each pair's
# place among the sorted and the groups' sizes.  A model that recomputes its
# layers keeps them (``jax.checkpoint``'s ``save_only_these_names``: 5 MB a
# Mellum layer) and its second forward holds no ``top_k`` and no sort
SAVED_NAMES = ("moe_chosen_scores", "moe_chosen_ids", "moe_pair_order",
               "moe_pair_weight", "moe_pair_place", "moe_group_sizes")


def _one_of(ids, n):
    """``[..., k, n]`` bool: is lane ``0..n-1`` the integer ``ids[..., j]``.
    Summed or any-ed over ``k`` it is the dense form of a histogram or a
    scatter of small integers: a compare a lane inside one fusion, where
    XLA's scatter pays 7-9 ns an index."""
    return ids[..., None] == jnp.arange(n, dtype=ids.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _top_k(scores, top_k):
    """``lax.top_k`` over the last axis, with a reverse rule that scatters
    nothing: the chosen ids of a row are distinct, so d scores is the sum
    over the choices of ``d value`` where the lane is the choice's — one
    non-zero term a lane, the value ``lax.top_k``'s own rule scatters."""
    return tuple(jax.lax.top_k(scores, top_k))


def _top_k_fwd(scores, top_k):
    values, ids = (checkpoint_name(x, n) for x, n in zip(
        jax.lax.top_k(scores, top_k), SAVED_NAMES))
    # the lanes ride along as a residual for their number alone
    return (values, ids), (ids, jnp.arange(scores.shape[-1], dtype=ids.dtype))


def _top_k_bwd(top_k, res, g):
    ids, lanes = res
    return (jnp.sum(jnp.where(_one_of(ids, lanes.size), g[0][..., None], 0.0),
                    axis=-2),)


_top_k.defvjp(_top_k_fwd, _top_k_bwd)


def group_limited_topk(scores, n_group, topk_group, top_k):
    """``group_limited_greedy``: a group's score is its best expert's;
    only the experts of the ``topk_group`` best groups stay; of those the
    ``top_k`` best are chosen.  ``scores [tokens, experts]`` (fp32) ->
    ``(weights [tokens, top_k], expert ids [tokens, top_k])``, the weights
    the chosen experts' scores themselves.  Where every group stays
    (``topk_group == n_group``: one group, Mellum's and K-EXAONE's) there
    is no limit to apply."""
    tokens, experts = scores.shape
    if topk_group < n_group:
        grouped = scores.reshape(tokens, n_group, experts // n_group)
        _, best_groups = jax.lax.top_k(grouped.max(axis=-1), topk_group)
        keep = _one_of(best_groups, n_group).any(axis=-2)
        scores = jnp.where(keep[:, :, None], grouped, 0.0).reshape(
            tokens, experts)
    return _top_k(scores, top_k)


def router_scores(x, router_kernel, scoring="softmax"):
    """Scores over every expert in fp32 (``x`` as the norm gave it, not
    rounded to the compute dtype first, and the product too: a near-tie
    between two experts must not flip on a bf16 product) — ``softmax`` or
    ``sigmoid`` of the router's logits."""
    logits = jnp.matmul(x.astype(jnp.float32),
                        router_kernel.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    return (jax.nn.sigmoid(logits) if scoring == "sigmoid"
            else jax.nn.softmax(logits, axis=-1))


def choose_experts(scores, *, n_group, topk_group, top_k, scaling, bias=None,
                   renormalise=False):
    """The group-limited choice over :func:`router_scores`, made on
    ``score + bias`` where the router has a selection ``bias``; weights
    ``scaling * score`` of the chosen (the bias chooses, it does not
    weigh), with ``renormalise`` divided by their sum over all ``top_k``
    chosen, wherever those experts are held."""
    if bias is None:
        weights, ids = group_limited_topk(scores, n_group, topk_group, top_k)
    else:
        _, ids = group_limited_topk(scores + bias.astype(jnp.float32),
                                    n_group, topk_group, top_k)
        weights = jnp.take_along_axis(scores, ids, axis=-1)
    if renormalise:
        weights = weights / weights.sum(axis=-1, keepdims=True)
    return scaling * weights, ids


def route(x, router_kernel, *, n_group, topk_group, top_k, scaling,
          scoring="softmax", bias=None, renormalise=False):
    """``(weights, ids)``: :func:`choose_experts` over
    :func:`router_scores`.  A model that needs the scores too
    (:func:`aux_load_balance`) calls the two itself."""
    return choose_experts(
        router_scores(x, router_kernel, scoring), n_group=n_group,
        topk_group=topk_group, top_k=top_k, scaling=scaling, bias=bias,
        renormalise=renormalise)


def aux_load_balance(scores, ids, n_experts):
    """The load-balancing loss of the Switch / Mixtral lineage over ALL the
    ``n_experts`` the router scores: ``n_experts * sum_e f_e P_e`` with
    ``f_e`` the (token, choice) pairs routed to expert ``e`` over the
    tokens and ``P_e`` the tokens' mean score of ``e``.  1 * ``top_k`` under
    an even load; the gradient flows through the scores alone."""
    tokens = ids.shape[0]
    load = jnp.sum(_one_of(ids.reshape(-1), n_experts), axis=0,
                   dtype=jnp.float32) / tokens
    return n_experts * jnp.sum(load * scores.astype(jnp.float32).mean(0))


def pair_capacity(pairs, held, routed, tile):
    """Rows one pass over the sorted pairs holds: twice this chip's mean
    share of the ``pairs`` (``held`` of the ``routed`` experts the router
    scores), rounded up to the row ``tile``, never above ``pairs`` — and
    all of them where that is one tile (a decode step: see the module's
    readings) or ``routed`` is not given.  From shapes alone: 12,288 of
    49,152 for 8,192 tokens at 6 choices and 20 of 160, 384 of 384 for
    64."""
    if routed is None:
        return pairs
    capacity = -(-2 * held * pairs // (routed * tile)) * tile
    return capacity if tile < capacity < pairs else pairs


def pair_passes(counts, pairs, routed, tile):
    """Passes over the ``pairs`` sorted pairs that :func:`held_experts_ffn`
    makes for ``counts`` as it returns them (int32 scalar): 1 while the
    held pairs fit one pass's capacity, ``ceil(held pairs / capacity)``
    past that."""
    capacity = pair_capacity(pairs, counts.size - 1, routed, tile)
    return jnp.maximum(-(-counts[:-1].sum() // capacity), 1)


def rows_moved_share(counts, pairs, routed, tile):
    """Rows the ways in and out of a ``reverse`` layer move for ``counts``
    as :func:`held_experts_ffn` returns them, over what moving every row of
    every pass would (fp32 scalar, from the counts and shapes alone).  A
    pass moves ``capacity`` rows three ways in (``x[token]`` forward and
    recomputed, ``g[token]`` backward) and its held pairs' rows two ways
    out (the choices' weighted sum and d x), where every pair's would be
    ``pairs`` rows each way out."""
    capacity = pair_capacity(pairs, counts.size - 1, routed, tile)
    held = counts[:-1].sum()
    passes = jnp.maximum(-(-held // capacity), 1)
    moved = 3 * capacity * passes + 2 * held
    every = passes * (3 * capacity + 2 * pairs)
    return moved.astype(jnp.float32) / every.astype(jnp.float32)


def _sum_of_rows(way, src, row, group, c=None, start=None):
    """``start + sum_j c[t, j] src[row[t, j]]`` (``c`` None: 1, ``start``
    None: 0) over the pairs with ``group[t, j] >= 0``, in fp32, the choices
    in their own order.  ``way`` is ``(held, interpret)`` for the kernel
    that moves those pairs' rows alone
    (``ops/transformer/gather_combine.py``), or None for ``top_k`` gathers
    of ``[tokens, hidden]`` masked and summed in one fusion."""
    if way is not None:
        total = moe_gather_combine(src, row, group, c, held=way[0],
                                   interpret=way[1])
        return total if start is None else start + total
    total = start
    if total is None:
        total = jnp.zeros((row.shape[0], src.shape[1]), jnp.float32)
    for j in range(row.shape[1]):
        rows = src[row[:, j]].astype(jnp.float32)
        total = total + jnp.where(
            group[:, j, None] >= 0,
            rows if c is None else c[:, j, None] * rows, 0.0)
    return total


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _rows_of_pairs(way, x, token, row, group):
    """``x[token]``: the rows of ``x [tokens, hidden]`` a pass's sorted
    pairs read, ``token [capacity]``.  ``row [tokens, top_k]`` is where each
    (token, choice) pair stands in the pass and ``group`` its expert among
    the held (negative: not in the pass): on the way back a token's
    gradient is the sum of its pairs' rows (:func:`_sum_of_rows`), not a
    scatter of the rows."""
    return x[token]


def _rows_of_pairs_fwd(way, x, token, row, group):
    return x[token], (row, group)


def _rows_of_pairs_bwd(way, res, g):
    row, group = res
    return _sum_of_rows(way, g, row, group).astype(g.dtype), None, None, None


_rows_of_pairs.defvjp(_rows_of_pairs_fwd, _rows_of_pairs_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _weighted_rows(way, y, out, weights, row, group, pair, pair_weight,
                   held_rows):
    """``y + sum_j w[t, j] out[row[t, j]]`` over the pairs in the pass
    (``group[t, j] >= 0``), in fp32, the choices in their own order
    (:func:`_sum_of_rows`).  On the way back row ``r`` of ``out`` belongs to
    one pair, ``pair[r]`` (``token * top_k + choice``) of weight
    ``pair_weight[r]``, so its gradient is that weight times its token's
    — one gather of rows; the rows past ``held_rows`` are nobody's."""
    return _sum_of_rows(way, out, row, group, weights, y)


def _weighted_rows_fwd(way, y, out, weights, row, group, pair, pair_weight,
                       held_rows):
    return (_weighted_rows(way, y, out, weights, row, group, pair,
                           pair_weight, held_rows),
            (out, weights, pair, pair_weight, held_rows))


def _weighted_rows_bwd(way, res, g):
    out, weights, pair, pair_weight, held_rows = res
    mine = jnp.arange(out.shape[0]) < held_rows
    g_rows = g[pair // weights.shape[1]]   # each sorted pair's token's, once
    d_out = jnp.where(mine[:, None], pair_weight[:, None] * g_rows,
                      0.0).astype(out.dtype)
    # d w of a pair is its row's dot with its token's gradient: formed in
    # the sorted order, and the held rows' scattered back to (token,
    # choice) — half as many scalars as a gather by ``row`` would read; a
    # row that is nobody's lands past the pairs, each in a place of its own.
    # The indices are unique because the held pairs sort first: a row below
    # ``held_rows`` stands at a sorted position below the number of held
    # pairs (``ends[-1] <= pairs`` in ``held_experts_ffn``), so it is a pair
    # of its own and never the last pair again that fills the last pass
    dots = jnp.sum(g_rows * out.astype(jnp.float32), axis=-1)
    rows = jnp.arange(out.shape[0])
    d_weights = jnp.zeros((weights.size + rows.size,), weights.dtype).at[
        jnp.where(mine, pair, weights.size + rows)].set(
        dots.astype(weights.dtype), unique_indices=True)
    return (g, d_out, d_weights[:weights.size].reshape(weights.shape), None,
            None, None, None, None)


_weighted_rows.defvjp(_weighted_rows_fwd, _weighted_rows_bwd)


def sorted_pairs(group, weights, groups, capacity):
    """The pairs sorted by ``group [pairs]`` (integers below ``groups``), a
    group's pairs in their own order: ``(order, sorted weights, place,
    sizes)`` — the pair that stands at each sorted position and its entry
    of ``weights [pairs]`` (it rides the sort, for the backward's use
    alone: no gradient flows through it), each pair's position among the
    sorted, and the pairs a group got.  The first two are filled up to
    whole passes of ``capacity`` with the last pair, so that any pass is a
    slice of them (:func:`pairs_of_pass`).

    Places and sizes are counted, not scattered: a pair stands after its
    group's start, its group's pairs in the blocks of 128 pairs before its
    own (a histogram a block by compare-and-sum, summed along the blocks)
    and those before it in its block (a compare of the block with itself)
    — dense arithmetic in three fusions, 0.37 ms where XLA's scatter of
    262,144 places took 1.2 and its scatter-add of as many into 17 bins
    2.3 (blocks of 512: 0.38).  Each result carries its name of
    ``SAVED_NAMES``."""
    pairs, block = group.size, 128
    _, order, weights = jax.lax.sort(
        (group, jnp.arange(pairs, dtype=jnp.int32),
         jax.lax.stop_gradient(weights)), num_keys=1, is_stable=True)
    # a block's filling belongs to no group and stands after its pairs
    blocks = jnp.pad(group, (0, -pairs % block),
                     constant_values=groups).reshape(-1, block)
    hot = _one_of(blocks, groups)
    per_block = jnp.sum(hot, axis=1, dtype=jnp.int32)
    sizes = per_block.sum(axis=0)
    before = (jnp.cumsum(sizes) - sizes
              + jnp.cumsum(per_block, axis=0) - per_block)
    earlier = jnp.tril(jnp.ones((block, block), bool), -1)
    place = jnp.sum((blocks[:, :, None] == blocks[:, None, :]) & earlier,
                    axis=2, dtype=jnp.int32) + jnp.sum(
        jnp.where(hot, before[:, None, :], 0), axis=2)
    order, weights = (jnp.pad(x, (0, -pairs % capacity), mode="edge")
                      for x in (order, weights))
    return tuple(checkpoint_name(x, n) for x, n in zip(
        (order, weights, place.reshape(-1)[:pairs], sizes), SAVED_NAMES[2:]))


def pairs_of_pass(filled, p, capacity):
    """Sorted positions ``[p * capacity, (p + 1) * capacity)`` of
    :func:`sorted_pairs`' order or sorted weights (``filled`` to whole
    passes): a static slice where ``p`` is a Python integer (a ``reverse``
    program's passes), a dynamic one where it is a loop's counter — never
    a gather of ``capacity`` scalars."""
    return jax.lax.dynamic_slice_in_dim(filled, p * capacity, capacity)


@functools.partial(jax.jit, static_argnames=(
    "first_expert", "interpret", "tiling", "routed", "reverse"))
def held_experts_ffn(x, weights, ids, valid, experts, *, first_expert,
                     interpret, tiling, routed=None, reverse=False):
    """``sum over the chosen experts held here of w_e F_e(x)`` for
    ``x [tokens, hidden]``, with what the sum ran over.

    ``experts`` holds ``gate_up [held, hidden, 2 * width]`` and
    ``down [held, width, hidden]``; ``valid [tokens]`` marks the rows that
    are tokens (a bucket's padding and dead slots are routed nowhere);
    ``routed`` is the number of experts the router scores (its kernel's
    width), which sets the rows of a pass (:func:`pair_capacity`).
    Returns ``(y [tokens, hidden] in fp32, counts)``, ``counts`` the number
    of pairs each held expert got, then of the pairs held elsewhere.

    The pairs are sorted by expert, the held ones first.  A pass gathers
    ``x`` for ``capacity`` sorted pairs, runs both grouped products over
    the held experts' rows among them (one last group fills the pass and
    is computed by nobody), and adds ``w[t, j] * out[place of (t, j)]`` to
    ``y`` in fp32, the choices ``j`` in their own order, a pair of another
    pass or another chip masked: ``top_k`` gathers of ``[tokens, hidden]``
    summed in one fusion.  The passes are :func:`pair_passes` trips of one
    loop — one wherever the capacity holds — so no pair is dropped under
    any routing.  Where a pass holds every pair (a decode step) there is
    no loop, and the way out is one gather of all the pairs and their
    weighted sum: the program the layer always was.  Traced once a
    geometry: a model's expert layers share the equations, and one log
    line says what capacity a traced geometry got.

    ``reverse`` (a training program) spells the passes out in a form that
    has a reverse rule — the first as it is, each further one under a
    ``cond`` and recomputed on the way back — with the same sums.
    """
    tokens, top_k = ids.shape
    held, width = experts["down"].shape[:2]
    pairs = tokens * top_k
    capacity = pair_capacity(pairs, held, routed, tiling[0])
    # once a geometry: the jit's cache answers a model's later layers
    logger.info(
        "held_experts_ffn geometry: tokens=%d top_k=%d pairs=%d held=%d of "
        "%s -> capacity=%d rows a pass%s", tokens, top_k, pairs, held, routed,
        capacity, ", reversible" if reverse else "")
    local = ids - first_expert
    here = (local >= 0) & (local < held) & valid[:, None]
    # group ``held`` is everything not computed here; it sorts last
    group = jnp.where(here, local, held)
    order, sorted_weights, place, sizes = sorted_pairs(
        group.reshape(-1), weights.reshape(-1), held + 1, capacity)
    place = place.reshape(tokens, top_k)
    # the padding's pairs are rows of the last group to the product, and
    # nobody's to the counters
    counts = sizes.at[held].add(
        -top_k * (tokens - valid.sum().astype(jnp.int32)))

    def products(rows, groups, experts=experts):
        """Both grouped products over ``rows``, the sorted pairs' rows of
        ``x``, in groups of ``groups`` rows."""
        hidden = moe_grouped_matmul(rows, experts["gate_up"], groups,
                                    tiling=tiling, interpret=interpret)
        return moe_grouped_matmul(gated_silu(hidden, width), experts["down"],
                                  groups, tiling=tiling, interpret=interpret)

    if capacity == pairs:
        # a decode step's few row tiles: one gather back to (token, choice)
        # order and the choices' weighted sum; pairs held elsewhere come
        # back zero
        out = products(x[order // top_k], sizes)[place].reshape(
            tokens, top_k, -1)
        return jnp.einsum("tk,tkh->th", jnp.where(here, weights, 0.0),
                          out.astype(jnp.float32)), counts
    ends = jnp.cumsum(sizes[:held])
    way = (held, interpret) if reverse else None

    def one_pass(p, y, x, weights, experts):
        """``y`` with sorted pairs ``[p * capacity, (p + 1) * capacity)``
        added."""
        lo = p * capacity
        pair = pairs_of_pass(order, p, capacity)
        mine = jnp.diff(jnp.clip(ends, lo, lo + capacity), prepend=lo)
        row = place - lo
        inside = here & (row >= 0) & (row < capacity)
        row = jnp.where(inside, row, 0)
        group = jnp.where(inside, local, -1)
        held_rows = mine.sum()
        out = products(_rows_of_pairs(way, x, pair // top_k, row, group),
                       jnp.append(mine, capacity - held_rows), experts)
        return _weighted_rows(
            way, y, out, weights, row, group, pair,
            pairs_of_pass(sorted_weights, p, capacity), held_rows)

    passes = pair_passes(counts, pairs, routed, tiling[0])
    zeros = jnp.zeros((tokens, x.shape[1]), jnp.float32)
    if reverse:
        y = one_pass(0, zeros, x, weights, experts)
        for p in range(1, -(-pairs // capacity)):
            y = y + jax.lax.cond(
                p < passes,
                jax.checkpoint(functools.partial(one_pass, p, zeros)),
                lambda *_: zeros, x, weights, experts)
        return y, counts
    _, y = jax.lax.while_loop(
        lambda carry: carry[0] < passes,
        lambda carry: (carry[0] + 1, one_pass(*carry, x, weights, experts)),
        (jnp.int32(0), zeros))
    return y, counts


def tokens_without_held_expert(ids, valid, first_expert, held):
    """Share of the ``valid`` tokens none of whose chosen experts ``ids
    [tokens, top_k]`` is held here (fp32 scalar): for them this chip's
    routed sum is empty."""
    local = (ids >= first_expert) & (ids < first_expert + held)
    nowhere = valid & ~local.any(axis=-1)
    return nowhere.sum().astype(jnp.float32) / jnp.maximum(
        valid.sum().astype(jnp.float32), 1.0)


def load_counters(counts):
    """(share of the pairs that fell to held experts, the busiest held
    expert's load over the mean held load) from ``counts`` as
    :func:`held_experts_ffn` returns them, both fp32 scalars."""
    held = counts[:-1].astype(jnp.float32)
    total = jnp.maximum(counts.sum().astype(jnp.float32), 1.0)
    mean = jnp.maximum(held.mean(), 1e-9)
    return held.sum() / total, held.max() / mean
