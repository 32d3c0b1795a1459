"""K-EXAONE (``model_type`` ``exaone_moe``), plain: the full forward over
whole sequences in float32 at ``highest``, no cache, no kernels, every
position attending through an explicit [seq, seq] mask.  Written from the
published ``config.json`` of LGAI-EXAONE/K-EXAONE-236B-A23B, nothing from
the program.  Per layer ``n`` (``h`` 6144, 64 query heads, 8 KV heads, ``d``
128, no biases, eps ``rms_norm_eps``), its type ``layer_types[n]`` (window of
``sliding_window`` keys, or full) and MLP kind ``mlp_layer_types[n]``:

    u   = RMSNorm(x; g_in)
    q_i = RMSNorm_d(u W_Q |head i; g_q)  i = 0..63     k_j, v_j = u W_K, u W_V |head j  j = 0..7
    k_j = RMSNorm_d(k_j; g_k)                          (g_q, g_k: one [d] scale each a layer)
    window layers only:  q_i <- R_p q_i,  k_j <- R_p k_j   (theta 1e6, pairs (c, c + d/2), whole head)
    a_i(t, s) = softmax over allowed(t) of q_i(t).k_{i//8}(s) / sqrt(d)
        full:   allowed(t) = {s <= t}      window: allowed(t) = {t - 127 <= s <= t}   (128 keys, t among them)
    x  <- x + [sum_s a_i(t, s) v_{i//8}(s)]_i W_O
    z   = RMSNorm(x; g_post)
    dense:   x <- x + W_down(silu(W_gate z) * W_up z)
    sparse:  s = sigmoid(z W_g) in R^E (float32);  C = top-k of (s + b)
             w_e = routed_scaling_factor * s_e / sum_{e' in C} s_e'          (all k chosen, held or not)
             x <- x + sum_{e in C, e held here} w_e F_e(z) + F_shared(z)     F: gated SiLU
    logits = RMSNorm(x_L; g_f) W_head                                        (untied)

ASSUMED, because the ``config.json`` does not say (the configuration file
repeats each with this reason): QK-norm and rotary positions on window
layers only are the EXAONE-4.0 family's published convention
(arXiv:2507.11407), which this ``model_type`` extends; pre-norm placement
and the selection bias ``b`` (a weight; zero in the benchmark's init) follow
the DeepSeek-V3 lineage that ``n_group`` / ``topk_group`` /
``routed_scaling_factor`` / ``num_nextn_predict_layers`` come from.  LEFT
OUT: the multi-token-prediction module (``num_nextn_predict_layers``: one
more full-attention expert layer behind a projection of ``[RMSNorm(x_L);
RMSNorm(E[next])]``), a draft head for self-speculation on which the main
model's logits do not depend.

Departures, each also a property of the configuration:
- the share.  Only the experts the configuration holds (``first_expert``
  onward, ``num_experts`` of them) add to the sum; the normaliser runs over
  all the chosen, as a deployment's would; what the chosen experts on other
  chips would have added is left out, as in the program, and that partial
  result goes on to the next layer.  The vocabulary is the held slice.
  (``tests/benchmarks`` adds the eight shares up to the uncut layer.)
- q, k and v are read out of one fused ``qkv`` kernel (q's heads first),
  gate and up out of one fused ``gate_up`` (gate first).
- the router's product stays float32 under the float8 control too.
- what is not judged.  The router's choice is discrete: where the last
  expert chosen and the first not chosen stand within a few hundredths of a
  router logit, a sound bfloat16 program and this float32 reference may
  choose differently and then disagree on the next token as much as a
  float8 program would (``reference/deepseek_v2.py`` has the measurements
  behind this).  So the reference works out, per token and sparse layer,
  the margin by which its own choice stands where it matters to the experts
  HELD here (:func:`held_margin`: a held expert crossing the edge between
  the k-th and the k+1-th; a swap between two experts held elsewhere moves
  only the normaliser, by their difference, and is judged), and
  ``position_logits`` returns flat logits at positions where the least
  margin over the layers is under ``judge_routing_margin``.
- sequences are walked one row at a time, heads in blocks, the MLPs' rows
  in blocks and experts one after another, so that the float32 activations
  of 12,288 positions fit beside the weights; the weights are read in the
  dtype they are stored in and widened to float32 where they are used.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import ops

HEAD_BLOCK = 1
ROW_BLOCK = 2048
WINDOW = "sliding_attention"


def rms_norm(p, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) \
        * p["scale"].astype(jnp.float32)


def _w(p):
    return p["kernel"].astype(jnp.float32)


def rotate(x, cfg):
    """``x [seq, heads, d]`` rotated by its position: value ``c`` and value
    ``c + d/2`` turn together by ``p * theta^(-2c/d)``."""
    d = x.shape[-1]
    inv_freq = 1.0 / float(cfg["rope_parameters"]["rope_theta"]) ** (
        np.arange(0, d, 2, dtype=np.float64) / d)
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def allowed(seq, window):
    """[seq, seq]: may position t (row) see position s (column)."""
    t, s = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
    ok = s <= t
    return ok if window is None else ok & (t - s < window)


def by_rows(fn, x):
    """``fn`` over ``x [seq, .]`` in blocks of rows, one after another."""
    seq = x.shape[0]
    block = math.gcd(seq, ROW_BLOCK)
    out = jax.lax.map(fn, x.reshape(seq // block, block, -1))
    return out.reshape(seq, -1)


def attention(p, u, cfg, mm, window):
    """Grouped-head attention of one sequence ``u [seq, hidden]``; ``window``
    None for a full layer."""
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, s, eps = cfg["head_dim"], u.shape[0], cfg["rms_norm_eps"]
    group = heads // kv_heads
    qkv = by_rows(lambda rows: mm(rows, _w(p["qkv"])), u)
    q = rms_norm(p["q_norm"], qkv[:, :heads * d].reshape(s, heads, d), eps)
    k = rms_norm(p["k_norm"], qkv[:, heads * d:(heads + kv_heads) * d]
                 .reshape(s, kv_heads, d), eps)
    v = qkv[:, (heads + kv_heads) * d:].reshape(s, kv_heads, d)
    if window is not None:
        q, k = rotate(q, cfg), rotate(k, cfg)
    mask = allowed(s, window)
    k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)   # [kv_heads, seq, d]

    def block(args):
        first_head, qb = args                            # [block, seq, d]
        kb, vb = k[first_head // group], v[first_head // group]
        scores = jnp.einsum("hqd,kd->hqk", qb, kb) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,kd->hqd", probs, vb)

    assert group % HEAD_BLOCK == 0      # a block's heads share a KV head
    ctx = jax.lax.map(block, (
        jnp.arange(0, heads, HEAD_BLOCK),
        q.transpose(1, 0, 2).reshape(heads // HEAD_BLOCK, HEAD_BLOCK, s, d)))
    ctx = ctx.reshape(heads, s, d).transpose(1, 0, 2).reshape(s, heads * d)
    return by_rows(lambda rows: mm(rows, _w(p["o"])), ctx)


def gated_mlp(gate_up, down, z, mm):
    width = down.shape[0]

    def rows(zb):
        gu = mm(zb, gate_up)
        return mm(jax.nn.silu(gu[:, :width]) * gu[:, width:], down)

    return by_rows(rows, z)


def route(p, z, cfg):
    """(weights, expert ids) ``[seq, k]`` — sigmoid scores, the choice on
    ``s + b``, the weights ``routed_scaling_factor * s`` renormalised over
    all ``k`` chosen — and the margin ``[seq]`` by which the choice stands
    where it matters to the experts held here (:func:`held_margin`)."""
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(jnp.matmul(z, _w(p["router"])))
    choice = scores + p["router"]["bias"].astype(jnp.float32)
    top, ids = jax.lax.top_k(choice, k + 1)
    weights = jnp.take_along_axis(scores, ids[:, :k], axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / weights.sum(axis=-1, keepdims=True)
    return (cfg["routed_scaling_factor"] * weights, ids[:, :k],
            held_margin(choice, top[:, k - 1], top[:, k], cfg))


def _logit(c):
    c = jnp.clip(c, 1e-6, 1.0 - 1e-6)
    return jnp.log(c) - jnp.log1p(-c)


def held_margin(choice, last_in, first_out, cfg):
    """How far a token's routing is from giving the experts HELD here
    another part in the sum, as a difference of logits of ``choice = s + b``
    (of router logits where ``b`` is zero).

    ``choice [seq, experts]``; ``last_in [seq]`` the k-th best value,
    ``first_out [seq]`` the k+1-th.  A held expert that is chosen falls out
    when it drops below the first not chosen; one that is not chosen comes
    in when it rises above the last chosen: the margin is the least such
    distance over the held experts.  Two experts held elsewhere that swap
    at the edge change only the normaliser, by the difference of two nearly
    equal scores: that is judged."""
    first = cfg.get("first_expert", 0)
    expert = jnp.arange(choice.shape[1])
    held = (expert >= first) & (expert < first + cfg["num_experts"])
    value = _logit(choice)
    last_in, first_out = _logit(last_in)[:, None], _logit(first_out)[:, None]
    distance = jnp.where(value >= last_in, value - first_out,
                         last_in - value)
    return jnp.where(held, distance, jnp.inf).min(axis=1)


def experts_layer(p, z, cfg, mm):
    """The shared expert plus the held experts' part of the routed sum, and
    the routing's margin."""
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


def layer(p, x, cfg, mm, kind):
    """One layer of attention ``kind``: ``(x, the routing margin [seq])``
    (infinite for a dense layer)."""
    eps = cfg["rms_norm_eps"]
    window = cfg["sliding_window"] if kind == WINDOW else None
    x = x + attention(p, rms_norm(p["input_norm"], x, eps), cfg, mm, window)
    z = rms_norm(p["post_norm"], x, eps)
    if "moe" in p:
        y, margin = experts_layer(p["moe"], z, cfg, mm)
        return x + y, margin
    return x + gated_mlp(_w(p["mlp"]["gate_up"]), _w(p["mlp"]["down"]),
                         z, mm), jnp.full(x.shape[:1], jnp.inf)


def hidden(params, ids, cfg, mm):
    """[rows, seq] token ids -> ([rows, seq, hidden] after the final norm,
    [rows, seq] the least routing margin over the layers), one row at a
    time."""
    def row(row_ids):
        x = params["embed"][row_ids].astype(jnp.float32)
        margin = jnp.full(x.shape[:1], jnp.inf)
        for n in range(cfg["num_hidden_layers"]):
            x, m = layer(params["layers"][f"layer_{n}"], x, cfg, mm,
                         cfg["layer_types"][n])
            margin = jnp.minimum(margin, m)
        return rms_norm(params["final_norm"], x, cfg["rms_norm_eps"]), margin

    return jax.lax.map(row, ids)


def logits_and_margins(params, ids, rows, cols, cfg, mm):
    """Logits and routing margins at the (row, column) positions; the head
    is applied to the positions in blocks (36,864 picked rows of float32
    side by side would be 0.9 GB more)."""
    with jax.default_matmul_precision("highest"):
        x, margin = hidden(params, ids, cfg, mm)
        x = x.reshape(-1, x.shape[-1])
        at = rows * ids.shape[1] + cols
        head = _w(params["lm_head"])
        logits = by_rows(lambda block: mm(x[block[:, 0]], head), at[:, None])
        return logits, margin.reshape(-1)[at]


def position_logits(params, ids, rows, cols, cfg, mm):
    """Logits at the (row, column) positions of ``ids`` [n, L]: one full
    forward over every row, no cache.  A position whose routing stands by
    less than ``judge_routing_margin`` (module docstring) is not judged:
    its logits come back flat, so no token there lies below the best.  The
    margins are the float32 pass's alone: a pass in a lower precision (the
    control) returns its logits as they are, and the tokens it puts first
    are judged where the float32 pass judges."""
    logits, margin = logits_and_margins(params, ids, rows, cols, cfg, mm)
    if mm is not ops.MATMULS["float32"]:
        return logits
    decided = margin >= cfg.get("judge_routing_margin", 0.0)
    return jnp.where(decided[:, None], logits, 0.0)
