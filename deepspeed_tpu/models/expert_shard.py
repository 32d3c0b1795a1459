"""One chip's share of an expert-parallel mixture-of-experts layer, for
serving: routing over ALL the experts at the published width, and the part
of the result that the experts held here give.

Two published routers run through :func:`route`: DeepSeek-V2's
(``softmax`` scores, group-limited, the chosen scores themselves as
weights, not renormalised) and the DeepSeek-V3 lineage's that K-EXAONE
uses (``sigmoid`` scores, the choice made on ``score + bias``, the weights
the chosen experts' scores renormalised over ALL the chosen, held here or
not; one group, so the group limit does nothing).

The layer is told which experts it holds (``first_expert``, and as many as
its weights have: a routing group, or any contiguous slice).  Every token
is routed over all the experts the router scores;
the (token, choice) pairs that fall to held experts are sorted by expert
and multiplied by a grouped matrix product
(``ops/transformer/grouped_matmul.py``) — no capacity, so no token is ever
dropped, whatever the imbalance; pairs that fall to experts held elsewhere
are left out of the sum, and no code stands in for the other chips or for
their exchange.  ``models/moe.py`` is the other expert layer: GShard
capacity routing that drops overflow, for training.
"""

import jax
import jax.numpy as jnp

from ..ops.transformer.grouped_matmul import moe_grouped_matmul
from .layers import gated_silu


def group_limited_topk(scores, n_group, topk_group, top_k):
    """``group_limited_greedy``: a group's score is its best expert's;
    only the experts of the ``topk_group`` best groups stay; of those the
    ``top_k`` best are chosen.  ``scores [tokens, experts]`` (fp32) ->
    ``(weights [tokens, top_k], expert ids [tokens, top_k])``, the weights
    the chosen experts' scores themselves."""
    tokens, experts = scores.shape
    grouped = scores.reshape(tokens, n_group, experts // n_group)
    _, best_groups = jax.lax.top_k(grouped.max(axis=-1), topk_group)
    keep = jnp.zeros((tokens, n_group), bool).at[
        jnp.arange(tokens)[:, None], best_groups].set(True)
    masked = jnp.where(keep[:, :, None], grouped, 0.0).reshape(
        tokens, experts)
    return jax.lax.top_k(masked, top_k)


def route(x, router_kernel, *, n_group, topk_group, top_k, scaling,
          scoring="softmax", bias=None, renormalise=False):
    """Scores over every expert in fp32 (``x`` as the norm gave it, not
    rounded to the compute dtype first, and the product too: a near-tie
    between two experts must not flip on a bf16 product) — ``softmax`` or
    ``sigmoid`` of the router's logits — then the group-limited choice,
    made on ``score + bias`` where the router has a selection ``bias``;
    weights ``scaling * score`` of the chosen (the bias chooses, it does
    not weigh), with ``renormalise`` divided by their sum over all
    ``top_k`` chosen, wherever those experts are held."""
    logits = jnp.matmul(x.astype(jnp.float32),
                        router_kernel.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    scores = (jax.nn.sigmoid(logits) if scoring == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    if bias is None:
        weights, ids = group_limited_topk(scores, n_group, topk_group, top_k)
    else:
        _, ids = group_limited_topk(scores + bias.astype(jnp.float32),
                                    n_group, topk_group, top_k)
        weights = jnp.take_along_axis(scores, ids, axis=-1)
    if renormalise:
        weights = weights / weights.sum(axis=-1, keepdims=True)
    return scaling * weights, ids


def held_experts_ffn(x, weights, ids, valid, experts, *, first_expert,
                     interpret, tiling):
    """``sum over the chosen experts held here of w_e F_e(x)`` for
    ``x [tokens, hidden]``, with what the sum ran over.

    ``experts`` holds ``gate_up [held, hidden, 2 * width]`` and
    ``down [held, width, hidden]``; ``valid [tokens]`` marks the rows that
    are tokens (a bucket's padding and dead slots are routed nowhere).
    Returns ``(y [tokens, hidden] in fp32, counts)``, ``counts`` the number
    of pairs each held expert got, then of the pairs held elsewhere.
    """
    tokens, top_k = ids.shape
    held, width = experts["down"].shape[:2]
    local = ids - first_expert
    here = (local >= 0) & (local < held) & valid[:, None]
    # group ``held`` is everything not computed here; it sorts last
    group = jnp.where(here, local, held).reshape(-1)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)
    # the padding's pairs are rows of the last group to the product, and
    # nobody's to the counters
    counts = sizes.at[held].add(
        -top_k * (tokens - valid.sum().astype(jnp.int32)))
    rows = x[order // top_k]
    hidden = moe_grouped_matmul(rows, experts["gate_up"], sizes,
                                tiling=tiling, interpret=interpret)
    out = moe_grouped_matmul(gated_silu(hidden, width), experts["down"],
                             sizes, tiling=tiling, interpret=interpret)
    # back to (token, choice) order; pairs held elsewhere come back zero
    back = jnp.zeros_like(order).at[order].set(jnp.arange(order.size))
    pairs = out[back].reshape(tokens, top_k, -1)
    w = jnp.where(here, weights, 0.0)
    return jnp.einsum("tk,tkh->th", w, pairs.astype(jnp.float32)), counts


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
