"""GPT-2 (Radford et al. 2019): pre-LN decoder with a tied LM head —
the language-model loss for training and the logits for serving."""

import jax
import jax.numpy as jnp

from . import ops


def _layer(p, x, causal_bias, key, rates, heads, eps, mm):
    k_attn, k_out, k_mlp = ops.keys_for(key, 3)
    b, s, h = x.shape
    y = ops.layer_norm(p["ln_attn"], x, eps)
    q, k, v = ops.split_heads(ops.dense(p["qkv"], y, mm), heads)
    ctx = ops.attention(q, k, v, causal_bias, k_attn, rates["attention"])
    a = ops.dense(p["attn_out"], ctx.reshape(b, s, h), mm)
    x = x + ops.dropout(k_out, a, rates["hidden"])
    z = ops.layer_norm(p["ln_mlp"], x, eps)
    m = ops.dense(p["fc2"], ops.gelu(ops.dense(p["fc1"], z, mm)), mm)
    return x + ops.dropout(k_mlp, m, rates["hidden"])


def hidden(params, ids, cfg, key, rates, mm):
    """[rows, seq] token ids -> [rows, seq, hidden] after the final
    layer norm."""
    n_layers, s = cfg["num_layers"], ids.shape[1]
    keys = ops.keys_for(key, n_layers + 1)
    x = ops.dropout(keys[0], params["wte"][ids] + params["wpe"][None, :s],
                    rates["embedding"])
    causal_bias = jnp.where(jnp.tril(jnp.ones((s, s), bool)), 0.0,
                            -1e9)[None, None]
    x = ops.through_layers(
        lambda p, x, k: _layer(p, x, causal_bias, k, rates,
                               cfg["num_heads"], cfg["layer_norm_eps"], mm),
        [params["blocks"][f"layer_{n}"] for n in range(n_layers)], x,
        keys[1:])
    return ops.layer_norm(params["ln_f"], x, cfg["layer_norm_eps"])


def block_loss(params, block, cfg, traffic, key, rates, mm, totals):
    """This block of rows' share of the batch's next-token loss."""
    ids = block["input_ids"]
    with jax.default_matmul_precision("highest"):
        x = hidden(params, ids, cfg, key, rates, mm)
        logits = mm(x[:, :-1], params["wte"].T)
        nll, _ = ops.nll(logits, ids[:, 1:])
        return jnp.sum(nll) / totals["labels"]


def batch_totals(batch):
    rows, seq = batch["input_ids"].shape
    return {"labels": float(rows * (seq - 1))}


NO_DROPOUT = {"embedding": 0.0, "hidden": 0.0, "attention": 0.0}


def position_logits(params, ids, rows, cols, cfg, mm):
    """Logits at the (row, column) positions of ``ids`` [n, L]: one full
    forward over every row, no cache."""
    with jax.default_matmul_precision("highest"):
        x = hidden(params, ids, cfg, None, NO_DROPOUT, mm)
        return mm(x[rows, cols], params["wte"].T)


def eval_logits(params, block, rows, cols, cfg, mm):
    """Next-token logits at the (row, column) positions of ``block``, no
    dropout."""
    return position_logits(params, block["input_ids"], rows, cols, cfg, mm)
