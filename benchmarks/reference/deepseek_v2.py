"""DeepSeek-V2, plain: the full forward over whole sequences in float32 at
``highest``, no cache, no kernels, every position attending to every
earlier one through expanded keys and values.  Written from the published
``config.json`` and modelling code (``modeling_deepseek.py`` of
deepseek-ai/DeepSeek-V2), nothing from the program.  Per layer, with
``h = RMSNorm(x)`` (eps ``rms_norm_eps``):

    c_q = RMSNorm(h W_DQ);  [q_nope_i ; q_rope_i] = c_q W_UQ   (i = 1..heads)
    [c_kv ; k_r] = h W_DKV;  c_kv <- RMSNorm(c_kv)
    q_rope_i <- R_p q_rope_i;  k_r <- R_p k_r                (one k_r a token)
    [k_nope_i ; v_i] = c_kv W_UKV                                 (per head)
    a_i(t, s) = softmax_{s<=t}((q_nope_i.k_nope_i(s) + q_rope_i.k_r(s))
                               * (nope + rope)^-1/2 * m^2)
    x <- x + [sum_s a_i v_i(s)]_i W_O
    z = RMSNorm(x);  x <- x + MLP(z)

``R_p`` is YaRN's rotation (``yarn_inv_freq``), ``m = 0.1 * mscale_all_dim *
ln(factor) + 1``.  A dense layer's MLP is ``W_down(silu(W_gate z) * W_up z)``;
an expert layer's is ``sum_{e chosen} w_e F_e(z) + F_shared(z)`` with
``s = softmax(z W_g)`` over all published experts, a group's score its best
expert's, the ``topk_group`` best groups kept, the ``num_experts_per_tok``
best experts of those chosen, ``w_e = routed_scaling_factor * s_e`` (not
renormalised).

Departures, each also a property of the configuration:
- the share.  Only the experts the configuration holds (``first_expert``
  onward, ``n_routed_experts`` of them) add to the sum; what the chosen
  experts on other chips would have added is left out, as in the program,
  and that partial result goes on to the next layer.  The vocabulary is
  the held slice.  (``tests/benchmarks`` adds the eight shares up to the
  uncut layer.)
- the rotary pairs.  The published code reads the rotary part as
  interleaved pairs (x0, x1), (x2, x3), ... and leaves them de-interleaved;
  a dot product does not see the order, so this writes the rotation on the
  interleaved pairs in place.
- the router's product stays float32 under the float8 control too, as the
  published gate computes it (``F.linear`` on float32 copies).
- gate and up are read out of one fused ``gate_up`` kernel (gate first).
- what is not judged.  The router's choice is discrete: where two experts
  (or two groups) score within a few parts in a hundred of each other, a
  sound bfloat16 program and this float32 reference may choose differently,
  and with seeded weights (a routed expert's weight is 0.4 to 2, its output
  as large as the stream) the two then disagree on the next token as much
  as a float8 program would.  A comparison that counted those positions
  could not tell bfloat16 from float8.  So the reference works out, per
  token and expert layer, the margin by which its own routing stands where
  it matters to the experts held here (:func:`held_margin`), and
  ``position_logits`` returns flat logits (every token as good as the best)
  at positions where the least margin over the layers is under the
  configuration's ``judge_routing_margin``: those positions are not judged.
  The margin is the reference's own (the float32 router on the float32
  stream), the same for every program; PERF.md says what share of the
  positions it leaves and how the threshold was read.
- sequences are walked one row at a time, heads in blocks and experts one
  after another, so that the float32 activations of 12,288 positions fit
  beside the weights; the weights are read in the dtype they are stored in
  and widened to float32 leaf by leaf where they are used.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import ops

HEAD_BLOCK = 2


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


def attention(p, h, cfg, mm):
    """Expanded latent attention of one sequence ``h [seq, hidden]``."""
    heads, nope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    rank, s = cfg["kv_lora_rank"], h.shape[0]
    c_q = rms_norm(p["q_a_norm"], mm(h, _w(p["q_a"])), cfg["rms_norm_eps"])
    q = mm(c_q, _w(p["q_b"])).reshape(s, heads, -1)
    ckv = mm(h, _w(p["kv_a"]))
    c_kv = rms_norm(p["kv_a_norm"], ckv[:, :rank], cfg["rms_norm_eps"])
    k_r = rotate(ckv[:, rank:], cfg)
    kv = mm(c_kv, _w(p["kv_b"])).reshape(s, heads, -1)
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], cfg)], -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_r[:, None], (s, heads, k_r.shape[-1]))], -1)
    v = kv[..., nope:]
    causal = jnp.tril(jnp.ones((s, s), bool))
    scale = softmax_scale(cfg)

    def block(qkv):
        qb, kb, vb = qkv                               # [block, seq, .]
        scores = jnp.einsum("hqd,hkd->hqk", qb, kb) * scale
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", probs, vb)

    def by_block(x):
        return x.transpose(1, 0, 2).reshape(
            heads // HEAD_BLOCK, HEAD_BLOCK, s, x.shape[-1])

    ctx = jax.lax.map(block, (by_block(q), by_block(k), by_block(v)))
    ctx = ctx.reshape(heads, s, -1).transpose(1, 0, 2).reshape(s, -1)
    return mm(ctx, _w(p["o"]))


def gated_mlp(gate_up, down, z, mm):
    width = down.shape[0]
    gu = mm(z, gate_up)
    return mm(jax.nn.silu(gu[:, :width]) * gu[:, width:], down)


def route(p, z, cfg):
    """(weights, expert ids) ``[seq, k]`` by ``group_limited_greedy``, and
    the margin ``[seq]`` by which the choice stands where it matters to the
    experts held here (:func:`held_margin`)."""
    n_group, k = cfg["n_group"], cfg["num_experts_per_tok"]
    scores = jax.nn.softmax(jnp.matmul(z, _w(p["router"])), axis=-1)
    seq, experts = scores.shape
    grouped = scores.reshape(seq, n_group, experts // n_group)
    best, groups = jax.lax.top_k(grouped.max(axis=-1), cfg["topk_group"] + 1)
    kept = (groups[:, :-1, None] == jnp.arange(n_group)).any(axis=1)
    masked = jnp.where(kept[:, :, None], grouped, 0.0).reshape(seq, experts)
    top, ids = jax.lax.top_k(masked, k + 1)
    margin = held_margin(jnp.log(grouped), kept, jnp.log(best[:, -2:]),
                         jnp.log(top[:, -2:]), cfg)
    return cfg["routed_scaling_factor"] * top[:, :k], ids[:, :k], margin


def held_margin(log_scores, kept, group_edge, expert_edge, cfg):
    """How far, in log score (a difference of router logits), a token's
    routing is from giving the experts HELD here another part in the sum.

    ``log_scores [seq, groups, experts a group]``; ``kept [seq, groups]``;
    ``group_edge [seq, 2]`` the log scores of the last group kept and the
    first left out; ``expert_edge [seq, 2]`` of the last expert chosen and
    the first not chosen among the kept groups' experts.  The routing can
    change the held experts' part in three ways, and the margin is the
    least of the three distances: a held group crosses the groups' edge (a
    group that is left out: its best expert against the last kept group's);
    two other groups swap there while a held group is kept (the experts
    that come in compete for the same places); a held expert of a kept
    group crosses the experts' edge.  Infinite where no held group is near
    any edge: then the held experts' part is the same whatever the others
    do."""
    per_group = log_scores.shape[2]
    first = cfg.get("first_expert", 0)
    expert = jnp.arange(log_scores.shape[1] * per_group).reshape(
        log_scores.shape[1:])
    held = (expert >= first) & (expert < first + cfg["n_routed_experts"])
    held_group = held.any(axis=1)
    inf = jnp.inf
    group_best = log_scores.max(axis=-1)
    last_kept, first_out = group_edge[:, :1], group_edge[:, 1:]
    # a held group that is left out, against the last group kept; a held
    # group that is kept stands at least as far from the first left out as
    # the last kept group does
    left_out = jnp.where(held_group & ~kept, last_kept - group_best, inf)
    swap = jnp.where((held_group & kept).any(axis=1),
                     (last_kept - first_out)[:, 0], inf)
    last_in, first_not = expert_edge[:, :1, None], expert_edge[:, 1:, None]
    # a chosen expert falls out below the first not chosen; one not chosen
    # comes in above the last chosen
    distance = jnp.where(log_scores >= last_in, log_scores - first_not,
                         last_in - log_scores)
    experts = jnp.where(held & kept[:, :, None], distance, inf)
    return jnp.minimum(jnp.minimum(left_out.min(axis=1), swap),
                       experts.min(axis=(1, 2)))


def experts_layer(p, z, cfg, mm):
    """Shared experts plus the held experts' part of the routed sum, and
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


def layer(p, x, cfg, mm):
    """One layer: ``(x, the routing margin [seq])`` (infinite for a dense
    layer)."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(p, rms_norm(p["input_norm"], x, eps), cfg, mm)
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
            x, m = layer(params["layers"][f"layer_{n}"], x, cfg, mm)
            margin = jnp.minimum(margin, m)
        return rms_norm(params["final_norm"], x, cfg["rms_norm_eps"]), margin

    return jax.lax.map(row, ids)


def logits_and_margins(params, ids, rows, cols, cfg, mm):
    """Logits and routing margins at the (row, column) positions."""
    with jax.default_matmul_precision("highest"):
        x, margin = hidden(params, ids, cfg, mm)
        return mm(x[rows, cols], _w(params["lm_head"])), margin[rows, cols]


def position_logits(params, ids, rows, cols, cfg, mm):
    """Logits at the (row, column) positions of ``ids`` [n, L]: one full
    forward over every row, no cache.  A position whose routing stands by
    less than ``judge_routing_margin`` (module docstring) is not judged:
    its logits come back flat, so no token there lies below the best.
    The margins are the float32 pass's alone: a pass in a lower precision
    (the control) returns its logits as they are, and the tokens it puts
    first are judged where the float32 pass judges."""
    logits, margin = logits_and_margins(params, ids, rows, cols, cfg, mm)
    if mm is not ops.MATMULS["float32"]:
        return logits
    decided = margin >= cfg.get("judge_routing_margin", 0.0)
    return jnp.where(decided[:, None], logits, 0.0)
