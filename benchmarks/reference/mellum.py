"""Mellum 2 (``model_type`` ``mellum``), plain: forward, loss and — through
``jax.value_and_grad`` in ``follow.follow_steps`` — gradients, in float32 at
``highest``, no kernels, every position attending through an explicit mask.
Written from the published ``config.json`` of
JetBrains/Mellum2-12B-A2.5B-Instruct, nothing from the program.  Per layer
``n`` (``h`` 2304, 32 query heads, 4 KV heads, ``d`` 128, no biases, eps
``rms_norm_eps``), its type ``layer_types[n]``:

    u   = RMSNorm(x; g_in)
    q_i = RMSNorm_d(u W_Q |head i; g_q)  i = 0..31     k_j, v_j = u W_K, u W_V |head j  j = 0..3
    k_j = RMSNorm_d(k_j; g_k)                          (g_q, g_k: one [d] scale each a layer)
    q_i <- R_p q_i,  k_j <- R_p k_j     pairs (c, c + d/2), whole head, by the layer's type:
        sliding: angle p * theta^(-2c/d), theta 500000
        full:    YaRN — frequency c blended between theta^(-2c/d) / factor and theta^(-2c/d) by the
                 linear ramp between the dimensions that turn beta_fast and beta_slow times over
                 original_max_position_embeddings; cos and sin times attention_factor
    a_i(t, s) = softmax over allowed(t) of q_i(t).k_{i//8}(s) / sqrt(d)
        full:   allowed(t) = {s <= t}      sliding: allowed(t) = {t - 1023 <= s <= t}  (1024 keys, t among them)
    x  <- x + [sum_s a_i(t, s) v_{i//8}(s)]_i W_O
    z   = RMSNorm(x; g_post)
    p = softmax(z W_g) in R^64 (float32);  C = the 8 largest
    w_e = p_e / sum_{e' in C} p_e'                                  (all 8 chosen, held or not)
    x <- x + sum_{e in C, e held here} w_e W_down_e (silu(W_gate_e z) * W_up_e z)
    logits = RMSNorm(x_L; g_f) W_head                                        (untied)
    loss = mean next-token cross-entropy
           + router_aux_loss_coef * mean over rows and layers of  E * sum_e f_e P_e
           f_e = the row's (token, choice) pairs routed to e / its tokens,  P_e = its tokens' mean p_e

ASSUMED, because the ``config.json`` does not say (the configuration file
repeats each with its reason): QK-norm before the rotary; the halves layout
of the rotary; the load-balancing loss's form, its coefficient and that it
is taken a sequence at a time (a data-parallel chip's own tokens); no MTP
head, no dropout.

Departures, each also a property of the configuration:
- the share.  Only the experts the configuration holds (``first_expert``
  onward, ``num_experts`` of them) add to the sum; the normaliser and the
  auxiliary loss run over all ``published_num_experts``; what the chosen
  experts on other chips would have added is left out, as in the program,
  and that partial result goes on to the next layer.  The vocabulary is
  the held slice.  (``tests/unit/test_mellum.py`` adds the four shares up
  to the uncut layer.)
- q, k and v are read out of one fused ``qkv`` kernel (q's heads first),
  gate and up out of one fused ``gate_up`` (gate first).
- the router's product stays float32 under the float8 control too.
- blocking, the same arithmetic in pieces: a row at a time; attention by
  blocks of ``QUERY_BLOCK`` query rows against every key, each block
  recomputed on the way back so that one block's [heads, block, seq]
  float32 scores fit; the held experts one after another over every token,
  weighted where the token chose them; each layer recomputed on the way
  back.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import ops

QUERY_BLOCK = 256
WINDOW = "sliding_attention"


def rms_norm(p, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * p["scale"]


def inv_freq(cfg, kind):
    """The rotary frequencies ``[d / 2]`` (float64) of a layer of ``kind``
    and the factor on cos and sin."""
    rp, d = cfg["rope_parameters"][kind], cfg["head_dim"]
    plain = float(rp["rope_theta"]) ** (
        -np.arange(0, d, 2, dtype=np.float64) / d)
    if rp["rope_type"] == "default":
        return plain, 1.0

    def dimension(turns):   # the pair that makes ``turns`` over the context
        return d * math.log(rp["original_max_position_embeddings"]
                            / (turns * 2 * math.pi)) \
            / (2 * math.log(rp["rope_theta"]))

    low = max(math.floor(dimension(rp["beta_fast"])), 0)
    high = min(math.ceil(dimension(rp["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 0.001), 0, 1)
    return (plain / rp["factor"] * ramp + plain * (1 - ramp),
            float(rp["attention_factor"]))


def rotate(x, cfg, kind):
    """``x [seq, heads, d]`` rotated by its position."""
    d = x.shape[-1]
    freq, factor = inv_freq(cfg, kind)
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(freq, jnp.float32)
    cos, sin = (jnp.cos(angles) * factor)[:, None], \
        (jnp.sin(angles) * factor)[:, None]
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def attention(p, u, cfg, mm, kind):
    """Grouped-head attention of one sequence ``u [seq, hidden]``."""
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, s, eps = cfg["head_dim"], u.shape[0], cfg["rms_norm_eps"]
    window = cfg["sliding_window"] if kind == WINDOW else None
    qkv = mm(u, p["qkv"]["kernel"])
    q = rms_norm(p["q_norm"], qkv[:, :heads * d].reshape(s, heads, d), eps)
    k = rms_norm(p["k_norm"], qkv[:, heads * d:(heads + kv_heads) * d]
                 .reshape(s, kv_heads, d), eps)
    v = qkv[:, (heads + kv_heads) * d:].reshape(s, kv_heads, d)
    q, k = rotate(q, cfg, kind), rotate(k, cfg, kind)
    k, v = (jnp.repeat(a, heads // kv_heads, axis=1) for a in (k, v))
    block = math.gcd(s, QUERY_BLOCK)

    @jax.checkpoint
    def rows(args):
        first, qb = args                                 # [block, heads, d]
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(d)
        t = first + jnp.arange(block)[:, None]
        key = jnp.arange(s)[None, :]
        allowed = key <= t
        if window is not None:
            allowed &= t - key < window
        probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    ctx = jax.lax.map(rows, (jnp.arange(0, s, block),
                             q.reshape(s // block, block, heads, d)))
    return mm(ctx.reshape(s, heads * d), p["attn_out"]["kernel"])


def experts(p, z, cfg, mm):
    """``(the held experts' part of the layer for one sequence z [seq,
    hidden], the sequence's load-balancing loss)``."""
    routed, top_k = cfg["published_num_experts"], cfg["num_experts_per_tok"]
    width, first = cfg["moe_intermediate_size"], cfg.get("first_expert", 0)
    probs = jax.nn.softmax(jnp.matmul(z, p["router"]["kernel"]), axis=-1)
    chosen, ids = jax.lax.top_k(probs, top_k)
    if cfg["norm_topk_prob"]:
        chosen = chosen / chosen.sum(axis=-1, keepdims=True)
    pairs = (ids[:, :, None] == jnp.arange(routed)).astype(jnp.float32)
    aux = routed * jnp.sum(pairs.sum(axis=(0, 1)) / z.shape[0]
                           * probs.mean(axis=0))
    weight = jnp.einsum("tk,tke->et", chosen, pairs)     # [routed, seq]
    held = p["experts"]["down"].shape[0]

    @jax.checkpoint
    def one(y, expert):
        w_gate_up, w_down, w = expert
        gate_up = mm(z, w_gate_up)
        act = jax.nn.silu(gate_up[:, :width]) * gate_up[:, width:]
        return y + w[:, None] * mm(act, w_down), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(z), (
        p["experts"]["gate_up"], p["experts"]["down"],
        weight[first:first + held]))
    return y, aux


def layer(p, x, cfg, mm, kind):
    eps = cfg["rms_norm_eps"]
    x = x + attention(p, rms_norm(p["input_norm"], x, eps), cfg, mm, kind)
    y, aux = experts(p["moe"], rms_norm(p["post_norm"], x, eps), cfg, mm)
    return x + y, aux


def hidden(params, ids, cfg, mm):
    """One sequence ``ids [seq]`` -> ``(x [seq, hidden] after the final
    norm, the layers' mean load-balancing loss)``."""
    x, auxes = params["embed"][ids], []
    for n in range(cfg["num_hidden_layers"]):
        x, aux = jax.checkpoint(
            lambda p, x, kind=cfg["layer_types"][n]: layer(
                p, x, cfg, mm, kind))(params["layers"][f"layer_{n}"], x)
        auxes.append(aux)
    return (rms_norm(params["final_norm"], x, cfg["rms_norm_eps"]),
            sum(auxes) / len(auxes))


def block_loss(params, block, cfg, traffic, key, rates, mm, totals):
    """This block of rows' share of the batch's loss: its positions' of the
    mean cross-entropy and its rows' of the mean auxiliary loss."""
    def row(ids):
        x, aux = hidden(params, ids, cfg, mm)
        nll, _ = ops.nll(mm(x[:-1], params["lm_head"]["kernel"]), ids[1:])
        return jnp.sum(nll), aux

    with jax.default_matmul_precision("highest"):
        nll, aux = jax.lax.map(row, block["input_ids"])
        return jnp.sum(nll) / totals["labels"] \
            + cfg["router_aux_loss_coef"] * jnp.sum(aux) / totals["rows"]


def batch_totals(batch):
    rows, seq = batch["input_ids"].shape
    return {"labels": float(rows * (seq - 1)), "rows": float(rows)}


def eval_logits(params, block, rows, cols, cfg, mm):
    """Next-token logits at the (row, column) positions of ``block``."""
    with jax.default_matmul_precision("highest"):
        x = jax.lax.map(lambda ids: hidden(params, ids, cfg, mm)[0],
                        block["input_ids"])
        return mm(x[rows, cols], params["lm_head"]["kernel"])
