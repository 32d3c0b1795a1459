"""Xing-4.0 (``model_type`` ``xing4_0``), plain: the full forward over whole
sequences in float32 at ``highest``, no cache, no kernels, every position
attending to every earlier one through expanded keys and values, the four
residual streams mixed by maps worked out a token at a time in ``jax.numpy``.
Written from the published ``config.json`` of XingChen-AGI/Xing4.0-29B-A4B
and the papers its keys name (mHC, arXiv:2512.24880; Hyper-Connections,
arXiv:2409.19606; the latent attention and YaRN of deepseek-ai/DeepSeek-V2's
``modeling_deepseek.py``), nothing from the program.

The stream is ``X in R^{n x C}`` a token (``n`` = ``hc_mult``, ``C`` =
``hidden_size``), ``X_0`` the token's embedding in all ``n`` rows.  A layer is
two sublayers ``F`` — latent attention, then the dense MLP (layers below
``first_k_dense_replace``) or the experts — each with its own ``Phi [n C, 2n
+ n^2]``, ``b [2n + n^2]``, ``alpha = (alpha_pre, alpha_post, alpha_res)``:

    x^    = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)        (no learned scale)
    [p ; q ; r] = x^ Phi                                       (widths n, n, n^2)
    H_pre = sigmoid(alpha_pre p + b_pre);   H_post = 2 sigmoid(alpha_post q + b_post)
    M     = exp(clip(alpha_res mat(r) + b_res, mhc_h_res_clamp_min, mhc_h_res_clamp_max))
    hc_sinkhorn_iters times:  M <- M / (row sums + hc_eps);  M <- M / (column sums + hc_eps)
    H_res = M
    u     = sum_i H_pre[i] X_i;    y = F(RMSNorm_w(u))
    X'_i  = sum_j H_res[i, j] X_j + H_post[i] y

    logits = RMSNorm(sum_i X_i; g_f) W_head                              (untied)

``F`` = attention (``h`` the normed ``u``; eps ``rms_norm_eps``):

    c_q = RMSNorm(h W_DQ);  [q_nope_i ; q_rope_i] = c_q W_UQ    (i = 1..heads)
    [c_kv ; k_r] = h W_DKV;  c_kv <- RMSNorm(c_kv)
    q_rope_i <- R_p q_rope_i;  k_r <- R_p k_r                 (one k_r a token)
    [k_nope_i ; v_i] = c_kv W_UKV                                  (per head)
    a_i(t, s) = softmax_{s<=t}((q_nope_i.k_nope_i(s) + q_rope_i.k_r(s))
                               * (nope + rope)^-1/2 * m^2)
    y = [sum_s a_i v_i(s)]_i W_O

with ``R_p`` YaRN's rotation and ``m = 0.1 * mscale_all_dim * ln(factor) +
1``.  ``F`` = dense MLP: ``W_down(silu(W_gate z) * W_up z)``.  ``F`` =
experts: ``s = sigmoid(z W_g)`` (float32), ``C`` the ``num_experts_per_tok``
best of ``s + b_sel`` (``noaux_tc``, one group), ``w_e = routed_scaling_factor
* s_e / sum_{e' in C} s_e'`` (``norm_topk_prob``), ``y = sum_{e in C} w_e
F_e(z) + F_shared(z)``.

ASSUMED, because the keys do not say (the configuration file repeats each
with what it moves): step 1's norm has no scale; rows are normalised before
columns, ``hc_eps`` is added to each sum and the clamp is on ``R`` before the
exponential; the embedding goes into all ``n`` rows and the rows are summed
on the way out; the rotary pairs and the fused ``gate_up`` are DeepSeek-V2's.
LEFT OUT: the multi-token-prediction module (``num_nextn_predict_layers``), a
draft head on which the main model's logits do not depend.

Departures, each also a property of the configuration:
- every expert is held (``ep_size`` 1), so nothing of the routed sum is left
  out; ``first_expert`` / ``n_routed_experts`` are read as in the other
  expert references all the same.
- the rotary pairs are rotated in place (a dot product does not see the
  order); gate and up are read out of one fused ``gate_up`` (gate first).
- the router's product and steps 1-4 of the maps stay float32 under the
  float8 control too: the configuration states float32 for them, and the
  control is the precision below for what it states as bfloat16.
- what is not judged.  The router's choice is discrete: where the last expert
  chosen and the first not chosen stand within a few hundredths of a router
  logit, a sound bfloat16 program and this float32 reference may choose
  differently, and with every expert held every swap changes the sum
  (``reference/deepseek_v2.py`` has the measurements behind this).  So the
  reference works out, per token and expert layer, the margin by which its
  own choice stands (:func:`choice_margin`), and ``position_logits`` returns
  flat logits at positions where the least margin over the layers is under
  the configuration's ``judge_routing_margin``: those positions are not
  judged.  The margin is the float32 pass's alone.
- sequences are walked one row at a time (a row hands back only the
  positions asked of it), heads one after another with the queries in blocks,
  the MLPs' positions in blocks and experts one after another (each expert's 11 M weights widened to float32
  where it is used: all 64 of a layer at once would be 2.8 GB), the layers in
  a plain loop (stacking them for a scan would copy 5.7 GB of weights), the
  head in blocks of the vocabulary, so that the float32 activations of 13,312
  positions fit beside 8.35 GB of weights.  A pass in a lower precision (the
  control, of which the harness reads the argmax alone) returns its argmax as
  int8 one-hot rows, so that it fits beside the float32 pass's 3.2 GB of
  logits.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import ops

QUERY_BLOCK = 1024     # queries a softmax is taken over at once, a head
ROW_BLOCK = 2048       # positions an MLP's intermediate is held for
VOCAB_BLOCKS = 8


def yarn_inv_freq(cfg):
    rs, dim = cfg["rope_scaling"], cfg["qk_rope_head_dim"]
    base = float(cfg["rope_theta"])
    j = np.arange(0, dim, 2, dtype=np.float64) / dim
    extrapolated = base ** -j
    interpolated = extrapolated / rs["factor"]

    def correction_dim(rotations):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return interpolated * ramp + extrapolated * (1.0 - ramp)


def _mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def softmax_scale(cfg):
    rs = cfg["rope_scaling"]
    m = _mscale(rs["factor"], rs["mscale_all_dim"])
    return m * m / math.sqrt(cfg["qk_nope_head_dim"]
                             + cfg["qk_rope_head_dim"])


def rotate(x, cfg):
    """``x [seq, ..., rope]`` rotated by its position: pair (x_2j, x_2j+1)
    turns by ``p * inv_freq_j``."""
    rs = cfg["rope_scaling"]
    factor = _mscale(rs["factor"], rs["mscale"]) \
        / _mscale(rs["factor"], rs["mscale_all_dim"])
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(yarn_inv_freq(cfg), jnp.float32)
    angles = angles.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(angles) * factor, jnp.sin(angles) * factor
    pairs = x.reshape(x.shape[:-1] + (-1, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def rms_norm(p, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) \
        * p["scale"].astype(jnp.float32)


def _w(p):
    return p["kernel"].astype(jnp.float32)


# -- the four streams ---------------------------------------------------------

def sinkhorn(m, iters, eps):
    """``m [..., n, n]`` positive: ``iters`` times every row over its sum,
    then every column over its."""
    for _ in range(iters):
        m = m / (m.sum(axis=-1, keepdims=True) + eps)
        m = m / (m.sum(axis=-2, keepdims=True) + eps)
    return m


def connection_maps(p, xs, cfg):
    """``(H_pre [seq, n], H_post [seq, n], H_res [seq, n, n])`` of one
    sublayer for the streams ``xs`` (``n`` arrays ``[seq, C]``, or one
    ``[seq, n, C]``): float32 throughout.  ``x^ Phi`` is taken a stream at a
    time, ``sum_i X_i Phi_i`` over ``Phi``'s ``n`` blocks of ``C`` rows,
    and the division by the root mean square after the product, which is
    linear: no copy of the whole stream is made."""
    xs = _streams(xs)
    n, width = len(xs), xs[0].shape[-1]
    phi = p["phi"].astype(jnp.float32)
    square = sum(jnp.sum(jnp.square(x), axis=-1, keepdims=True) for x in xs)
    pqr = sum(jnp.matmul(x, phi[i * width:(i + 1) * width])
              for i, x in enumerate(xs)) * jax.lax.rsqrt(
        square / (n * width) + cfg["rms_norm_eps"])
    bias = p["bias"].astype(jnp.float32)
    alpha = p["alpha"].astype(jnp.float32)
    h_pre = jax.nn.sigmoid(alpha[0] * pqr[:, :n] + bias[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * pqr[:, n:2 * n] + bias[n:2 * n])
    r = (alpha[2] * pqr[:, 2 * n:] + bias[2 * n:]).reshape(-1, n, n)
    m = jnp.exp(jnp.clip(r, cfg["mhc_h_res_clamp_min"],
                         cfg["mhc_h_res_clamp_max"]))
    return h_pre, h_post, sinkhorn(m, cfg["hc_sinkhorn_iters"],
                                   cfg["hc_eps"])


def _streams(x):
    """The ``n`` streams as a list of ``[seq, C]`` arrays (the forward holds
    them so: a ``[seq, n, C]`` array of 13,312 positions is 0.76 GB a
    copy)."""
    return list(x) if isinstance(x, (list, tuple)) else [
        x[:, i] for i in range(x.shape[1])]


def connected(p, xs, cfg, f):
    """One sublayer ``f`` on the streams: ``(X' as a list of n streams,
    whatever f returns beside its output)``."""
    xs = _streams(xs)
    h_pre, h_post, h_res = connection_maps(p, xs, cfg)
    u = sum(h_pre[:, i, None] * x for i, x in enumerate(xs))
    y, extra = f(u)
    return [h_post[:, i, None] * y
            + sum(h_res[:, i, j, None] * x for j, x in enumerate(xs))
            for i in range(len(xs))], extra


# -- the sublayers ------------------------------------------------------------

def attention(p, h, cfg, mm):
    """Expanded latent attention of one sequence ``h [seq, hidden]``: a head
    at a time, its queries, keys and values sliced out of the projections
    where they are used, the queries in blocks."""
    heads, nope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    rank, s = cfg["kv_lora_rank"], h.shape[0]
    c_q = rms_norm(p["q_a_norm"], mm(h, _w(p["q_a"])), cfg["rms_norm_eps"])
    q = mm(c_q, _w(p["q_b"])).reshape(s, heads, -1)
    ckv = mm(h, _w(p["kv_a"]))
    c_kv = rms_norm(p["kv_a_norm"], ckv[:, :rank], cfg["rms_norm_eps"])
    k_r = rotate(ckv[:, rank:], cfg)
    kv = mm(c_kv, _w(p["kv_b"])).reshape(s, heads, -1)
    scale = softmax_scale(cfg)
    block = math.gcd(s, QUERY_BLOCK)
    keys = jnp.arange(s)

    def head(i):
        qi, kvi = q[:, i], kv[:, i]
        qh = jnp.concatenate([qi[:, :nope], rotate(qi[:, nope:], cfg)], -1)
        kh = jnp.concatenate([kvi[:, :nope], k_r], -1)
        vh = kvi[:, nope:]

        def queries(start):
            qb = jax.lax.dynamic_slice_in_dim(qh, start, block, axis=0)
            scores = jnp.einsum("qd,kd->qk", qb, kh) * scale
            seen = keys[None, :] <= (start + jnp.arange(block))[:, None]
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf),
                                   axis=-1)
            return jnp.einsum("qk,kd->qd", probs, vh)

        return jax.lax.map(queries, jnp.arange(0, s, block)).reshape(
            s, vh.shape[-1])

    ctx = jax.lax.map(head, jnp.arange(heads))          # [heads, seq, value]
    return mm(ctx.transpose(1, 0, 2).reshape(s, -1), _w(p["o"]))


def by_rows(f, z):
    """``f`` over ``z [seq, .]`` in blocks of ``ROW_BLOCK`` positions (the
    whole where that does not divide)."""
    seq = z.shape[0]
    if seq % ROW_BLOCK:
        return f(z)
    out = jax.lax.map(f, z.reshape(seq // ROW_BLOCK, ROW_BLOCK, -1))
    return out.reshape(seq, -1)


def gated_mlp(gate_up, down, z, mm):
    width = down.shape[0]

    def rows(zb):
        gu = mm(zb, gate_up)
        return mm(jax.nn.silu(gu[:, :width]) * gu[:, width:], down)

    return by_rows(rows, z)


def choice_margin(edge, scores):
    """How far a token's choice is from another one, as a difference of
    router logits to first order.  ``edge [..., 2]``: ``s + b_sel`` of the
    last expert chosen and of the first not chosen; ``scores [..., 2]``:
    their sigmoid scores ``s``.  The two swap when the gap between them
    closes; a router logit moves its score by ``s (1 - s)`` a unit, so the
    gap over the steeper of the two slopes is the least move of one logit
    that closes it.  Every expert is held here, so any swap at that edge
    changes the sum."""
    slope = jnp.max(scores * (1.0 - scores), axis=-1)
    return (edge[..., 0] - edge[..., 1]) / jnp.maximum(slope, 1e-6)


def route(p, z, cfg):
    """(weights, expert ids) ``[seq, k]`` — sigmoid scores, the choice on
    ``s + b_sel``, the weights ``routed_scaling_factor * s`` renormalised
    over the chosen — and the margin ``[seq]`` of the choice."""
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(jnp.matmul(z, _w(p["router"])))
    choice = scores + p["router"]["bias"].astype(jnp.float32)
    top, ids = jax.lax.top_k(choice, k + 1)
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    weights = chosen[:, :k]
    if cfg["norm_topk_prob"]:
        weights = weights / weights.sum(axis=-1, keepdims=True)
    return (cfg["routed_scaling_factor"] * weights, ids[:, :k],
            choice_margin(top[:, k - 1:], chosen[:, k - 1:]))


def experts_layer(p, z, cfg, mm):
    """The shared expert plus the routed sum over the experts held (all of
    them as published), and the routing's margin."""
    weights, ids, margin = route(p, z, cfg)
    first = cfg.get("first_expert", 0)

    def one(carry, expert):
        index, gate_up, down = expert
        w = jnp.sum(jnp.where(ids == first + index, weights, 0.0), axis=-1)
        y = gated_mlp(gate_up.astype(jnp.float32),
                      down.astype(jnp.float32), z, mm)
        return carry + w[:, None] * y, None

    held = p["experts"]["gate_up"].shape[0]
    routed, _ = jax.lax.scan(one, jnp.zeros_like(z), (
        jnp.arange(held), p["experts"]["gate_up"], p["experts"]["down"]))
    shared = gated_mlp(_w(p["shared"]["gate_up"]), _w(p["shared"]["down"]),
                       z, mm)
    return routed + shared, margin


def layer(p, x, cfg, mm):
    """One layer on the streams ``x`` (``n`` arrays ``[seq, C]``): ``(the
    streams, the routing margin [seq])`` (infinite for a dense layer)."""
    eps = cfg["rms_norm_eps"]
    x, _ = connected(p["hc_attn"], x, cfg, lambda u: (
        attention(p, rms_norm(p["input_norm"], u, eps), cfg, mm), None))

    def mlp(u):
        z = rms_norm(p["post_norm"], u, eps)
        if "moe" in p:
            return experts_layer(p["moe"], z, cfg, mm)
        return gated_mlp(_w(p["mlp"]["gate_up"]), _w(p["mlp"]["down"]), z,
                         mm), jnp.full(z.shape[:1], jnp.inf)

    return connected(p["hc_mlp"], x, cfg, mlp)


def stream_of_row(params, row_ids, cfg, mm):
    """One sequence ``[seq]`` -> (``[seq, hidden]`` after the streams' sum
    and the final norm, ``[seq]`` the least routing margin over the
    layers)."""
    e = params["embed"][row_ids].astype(jnp.float32)
    xs = [e] * cfg["hc_mult"]
    margin = jnp.full(e.shape[:1], jnp.inf)
    for i in range(cfg["num_hidden_layers"]):
        xs, m = layer(params["layers"][f"layer_{i}"], xs, cfg, mm)
        margin = jnp.minimum(margin, m)
    return rms_norm(params["final_norm"], sum(xs),
                    cfg["rms_norm_eps"]), margin


def hidden(params, ids, cfg, mm):
    """[rows, seq] token ids -> ([rows, seq, hidden], [rows, seq] margins),
    one row at a time."""
    return jax.lax.map(lambda r: stream_of_row(params, r, cfg, mm), ids)


def hidden_at(params, ids, rows, cols, cfg, mm):
    """The final stream and the margins at the (row, column) positions
    alone, ``[positions, hidden]`` and ``[positions]``: a row hands back
    only the positions asked of it, so that nothing of ``[rows, seq,
    hidden]`` is held (1.14 GB at the cell's size).  A row is given room for
    ``ceil(positions / rows)`` of them — what the harness asks for, each
    request's answer at most the longest; a position past its row's room
    comes back NaN, which no limit passes."""
    n_rows, n = ids.shape[0], rows.shape[0]
    room = -(-n // n_rows)
    order = jnp.argsort(rows, stable=True)
    starts = jnp.searchsorted(rows[order], jnp.arange(n_rows))
    rank = jnp.zeros(n, jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32) - starts[rows[order]].astype(
            jnp.int32))
    fits = rank < room
    asked = jnp.zeros((n_rows, room), jnp.int32).at[
        rows, jnp.where(fits, rank, room)].set(cols, mode="drop")

    def row(args):
        row_ids, wanted = args
        x, margin = stream_of_row(params, row_ids, cfg, mm)
        return x[wanted], margin[wanted]

    x, margin = jax.lax.map(row, (ids, asked))
    at = (rows, jnp.minimum(rank, room - 1))
    return (jnp.where(fits[:, None], x[at], jnp.nan),
            jnp.where(fits, margin[at], jnp.nan))


def _head_blocks(params, x, mm, start, each):
    """``each(carry, first column, logits of one block of the vocabulary)``
    folded over the head's blocks from ``start``: a block's columns are
    widened to float32 where they are used."""
    kernel = params["lm_head"]["kernel"]
    vocab = kernel.shape[1]
    blocks = math.gcd(vocab, VOCAB_BLOCKS)
    width = vocab // blocks

    def body(j, carry):
        w = jax.lax.dynamic_slice_in_dim(kernel, j * width, width, axis=1)
        return each(carry, j * width, mm(x, w.astype(jnp.float32)))

    return jax.lax.fori_loop(0, blocks, body, start)


def logits_and_margins(params, ids, rows, cols, cfg, mm):
    """Logits and routing margins at the (row, column) positions."""
    with jax.default_matmul_precision("highest"):
        x, margin = hidden_at(params, ids, rows, cols, cfg, mm)
        vocab = params["lm_head"]["kernel"].shape[1]
        logits = _head_blocks(
            params, x, mm, jnp.zeros((x.shape[0], vocab), jnp.float32),
            lambda out, at, block: jax.lax.dynamic_update_slice_in_dim(
                out, block, at, axis=1))
        return logits, margin


def _argmax_rows(params, ids, rows, cols, cfg, mm):
    """The best token at the (row, column) positions as int8 one-hot rows,
    the head a block of the vocabulary at a time."""
    with jax.default_matmul_precision("highest"):
        x, _ = hidden_at(params, ids, rows, cols, cfg, mm)

        def each(carry, at, block):
            best, token = carry
            here = jnp.max(block, axis=-1)
            return (jnp.maximum(best, here), jnp.where(
                here > best, at + jnp.argmax(block, axis=-1), token))

        _, token = _head_blocks(
            params, x, mm, (jnp.full(x.shape[:1], -jnp.inf),
                            jnp.zeros(x.shape[:1], jnp.int32)), each)
        return jax.nn.one_hot(token, params["lm_head"]["kernel"].shape[1],
                              dtype=jnp.int8)


def position_logits(params, ids, rows, cols, cfg, mm):
    """Logits at the (row, column) positions of ``ids`` [n, L]: one full
    forward over every row, no cache.  A position whose routing stands by
    less than ``judge_routing_margin`` (module docstring) is not judged:
    its logits come back flat, so no token there lies below the best.  A
    pass in a lower precision (the control) returns the token it puts first
    as int8 one-hot rows: the harness reads their argmax alone, and judges
    it where the float32 pass judges."""
    if mm is not ops.MATMULS["float32"]:
        return _argmax_rows(params, ids, rows, cols, cfg, mm)
    logits, margin = logits_and_margins(params, ids, rows, cols, cfg, mm)
    decided = margin >= cfg.get("judge_routing_margin", 0.0)
    return jnp.where(decided[:, None], logits, 0.0)
