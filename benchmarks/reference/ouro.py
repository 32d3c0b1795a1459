"""Ouro (``model_type`` ``ouro``), plain: the full forward over whole
sequences in float32 at ``highest``, no cache, no kernels, every position
attending through an explicit [seq, seq] mask.  Written from the published
``config.json`` of ByteDance/Ouro-2.6B, nothing from the program.  With
``N(.; g)`` an RMSNorm (eps ``rms_norm_eps``), ``h`` 2048, 16 heads of ``d``
128 (a KV head a query head), no biases, ``L`` layers, ``R`` =
``total_ut_steps`` loop steps over the SAME ``L`` weight trees:

    x = E[token]
    for r = 0 .. R-1:
        for i = 0 .. L-1:
            u = N(x; g_i1)
            q_j, k_j, v_j = u W_Q, u W_K, u W_V |head j          j = 0..15
            q_j <- R_p q_j,  k_j <- R_p k_j      (theta rope_theta, pairs (c, c + d/2), whole head)
            a_j(t, s) = softmax over s <= t of q_j(t).k_j(s) / sqrt(d)
            x <- x + N([sum_s a_j(t, s) v_j(s)]_j W_O; g_i2)
            z = N(x; g_i3)
            x <- x + N(W_down(silu(W_gate z) * W_up z); g_i4)
        x <- N(x; g_f)                      h^r: step r's state AND step r+1's input
        lambda_r = sigmoid(h^r . w_g + b_g)
    p_r = lambda_r prod_{s<r} (1 - lambda_s)  for r < R-1;   p_{R-1} = prod_{s<R-1} (1 - lambda_s)
    logits = h^{R-1} W_head                 (untied; early_exit_threshold 1 is reached at the last step only)

Keys and values of step ``r`` are step ``r``'s own: a query of step ``r``
attends the keys that step ``r`` made at the positions before it, never
another step's.

ASSUMED, because the ``config.json`` has no key for them (the configuration
file repeats each with this reason): the four norms a layer (one before and
one after each sublayer), the final norm after EVERY loop step, and the
gate's form are the Ouro report's ("Scaling Latent Reasoning via Looped
Language Models", ByteDance Seed, October 2025) and its released modelling
code's, as far as they are known here.

Departures, each also a property of the configuration:
- q, k and v are read out of one fused ``qkv`` kernel (q's heads first),
  gate and up out of one fused ``gate_up`` (gate first).
- the layers run as one scanned, recomputed body (``ops.through_layers``),
  walked once a loop step under a scan over the steps, so the reference
  compiles in seconds and the 48 trees are stacked once; sequences are
  walked one row at a time; the weights are read in the dtype they are
  stored in and widened to float32 where they are used.
- the gate's product stays float32 under the float8 control too.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import ops


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
    inv_freq = 1.0 / float(cfg["rope_theta"]) ** (
        np.arange(0, d, 2, dtype=np.float64) / d)
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def attention(p, u, cfg, mm):
    """Causal attention of one sequence ``u [seq, hidden]``."""
    heads, d, s = cfg["num_attention_heads"], cfg["head_dim"], u.shape[0]
    q, k, v = mm(u, _w(p["qkv"])).reshape(s, 3, heads, d).transpose(
        1, 0, 2, 3)
    q, k = rotate(q, cfg), rotate(k, cfg)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(d)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    ctx = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, heads * d)
    return mm(ctx, _w(p["o"]))


def gated_mlp(p, z, mm):
    width = p["down"]["kernel"].shape[0]
    gu = mm(z, _w(p["gate_up"]))
    return mm(jax.nn.silu(gu[:, :width]) * gu[:, width:], _w(p["down"]))


def layer(p, x, cfg, mm):
    """One layer: a norm before and after each sublayer."""
    eps = cfg["rms_norm_eps"]
    a = attention(p, rms_norm(p["norm_attn_in"], x, eps), cfg, mm)
    x = x + rms_norm(p["norm_attn_out"], a, eps)
    m = gated_mlp(p, rms_norm(p["norm_mlp_in"], x, eps), mm)
    return x + rms_norm(p["norm_mlp_out"], m, eps)


def states(params, ids, cfg, mm):
    """[rows, seq] token ids -> every loop step's state ``h^r``
    [rows, steps, seq, hidden], one row at a time."""
    layers = [params["layers"][f"layer_{n}"]
              for n in range(cfg["num_hidden_layers"])]
    keys = ops.keys_for(None, len(layers))

    def walk(x, _):
        x = ops.through_layers(lambda p, x, key: layer(p, x, cfg, mm),
                               layers, x, keys)
        x = rms_norm(params["final_norm"], x, cfg["rms_norm_eps"])
        return x, x

    def row(row_ids):
        x = params["embed"][row_ids].astype(jnp.float32)
        return jax.lax.scan(walk, x, None, length=cfg["total_ut_steps"])[1]

    return jax.lax.map(row, ids)


def masses(params, h):
    """The exit distribution of states ``h [..., steps, seq, hidden]``:
    ``p [..., steps, seq]``, summing to 1 over the steps."""
    gate = params["exit_gate"]
    lam = jax.nn.sigmoid(
        jnp.sum(h * gate["kernel"].astype(jnp.float32)[:, 0], axis=-1)
        + gate["bias"].astype(jnp.float32))
    steps = lam.shape[-2]
    stay, out = jnp.ones_like(lam[..., 0, :]), []
    for r in range(steps):
        out.append(stay if r == steps - 1 else lam[..., r, :] * stay)
        stay = stay * (1.0 - lam[..., r, :])
    return jnp.stack(out, axis=-2)


def exit_mass(params, ids, rows, cols, cfg, mm):
    """The exit distribution ``[n, steps]`` at the (row, column) positions
    of ``ids`` [rows, L]."""
    with jax.default_matmul_precision("highest"):
        return masses(params, states(params, ids, cfg, mm))[
            rows, :, cols]


def position_logits(params, ids, rows, cols, cfg, mm):
    """Logits at the (row, column) positions of ``ids`` [n, L]: one full
    forward over every row, no cache; the last step's state through the
    head (the cumulated exit mass reaches ``early_exit_threshold`` = 1 at
    the last step only).  Every position is judged."""
    assert cfg["early_exit_threshold"] >= 1
    with jax.default_matmul_precision("highest"):
        h = states(params, ids, cfg, mm)[:, -1]
        return mm(h[rows, cols], _w(params["lm_head"]))
