"""BERT pretraining loss (Devlin et al., arXiv:1810.04805): post-LN
encoder, MLM over the labelled positions + next-sentence prediction."""

import jax
import jax.numpy as jnp

from . import ops


def _layer(p, x, mask_bias, key, rates, heads, eps, mm):
    k_attn, k_out, k_mlp = ops.keys_for(key, 3)
    b, s, h = x.shape
    q, k, v = ops.split_heads(ops.dense(p["qkv"], x, mm), heads)
    ctx = ops.attention(q, k, v, mask_bias, k_attn, rates["attention"])
    a = ops.dense(p["attn_out"], ctx.reshape(b, s, h), mm)
    x = ops.layer_norm(p["ln_attn"], x + ops.dropout(k_out, a,
                                                     rates["hidden"]), eps)
    m = ops.dense(p["fc2"], ops.gelu(ops.dense(p["fc1"], x, mm)), mm)
    return ops.layer_norm(p["ln_mlp"], x + ops.dropout(k_mlp, m,
                                                       rates["hidden"]), eps)


def _encode(params, block, cfg, key, rates, mm):
    """[rows, seq, hidden] out of the last encoder layer."""
    eps, heads = cfg["layer_norm_eps"], cfg["num_attention_heads"]
    n_layers = cfg["num_hidden_layers"]
    ids = block["input_ids"]
    s = ids.shape[1]
    keys = ops.keys_for(key, n_layers + 1)
    emb = params["bert"]["embeddings"]
    x = (emb["word"][ids] + emb["position"][None, :s]
         + emb["token_type"][block["token_type_ids"]])
    x = ops.dropout(keys[0], ops.layer_norm(emb["ln"], x, eps),
                    rates["hidden"])
    visible = block["attention_mask"].astype(jnp.float32)
    mask_bias = (1.0 - visible)[:, None, None, :] * -1e9
    return ops.through_layers(
        lambda p, x, k: _layer(p, x, mask_bias, k, rates, heads, eps, mm),
        [params["bert"]["encoder"][f"layer_{n}"] for n in range(n_layers)],
        x, keys[1:])


def _mlm_logits(params, rows, cfg, mm):
    cls = params["cls"]
    t = ops.layer_norm(cls["transform_ln"],
                       ops.gelu(ops.dense(cls["transform"], rows, mm)),
                       cfg["layer_norm_eps"])
    return (mm(t, params["bert"]["embeddings"]["word"].T)
            + cls["decoder_bias"])


def block_loss(params, block, cfg, traffic, key, rates, mm, totals):
    """This block of rows' share of the batch's loss: its MLM negative
    log-likelihoods over the batch's count of labelled positions, plus its
    NSP ones over the batch's rows."""
    labels = block["masked_lm_labels"]
    with jax.default_matmul_precision("highest"):
        x = _encode(params, block, cfg, key, rates, mm)
        pooled = jnp.tanh(ops.dense(params["bert"]["pooler"], x[:, 0], mm))
        # the labelled positions of each row, in order (unlabelled fill
        # positions carry the label -100 and weigh nothing)
        n_pred = traffic["predictions_per_seq"]
        _, pos = jax.lax.top_k((labels >= 0).astype(jnp.int32), n_pred)
        rows = jnp.take_along_axis(x, pos[..., None], axis=1)
        row_labels = jnp.take_along_axis(labels, pos, axis=1)
        mlm, _ = ops.nll(_mlm_logits(params, rows, cfg, mm), row_labels)
        nsp, _ = ops.nll(ops.dense(params["cls"]["seq_relationship"],
                                   pooled, mm),
                         block["next_sentence_labels"])
        return (jnp.sum(mlm) / totals["labels"]
                + jnp.sum(nsp) / totals["rows"])


NO_DROPOUT = {"hidden": 0.0, "attention": 0.0}


def eval_logits(params, block, rows, cols, cfg, mm):
    """MLM logits at the (row, column) positions of ``block``, no
    dropout."""
    with jax.default_matmul_precision("highest"):
        x = _encode(params, block, cfg, None, NO_DROPOUT, mm)
        return _mlm_logits(params, x[rows, cols], cfg, mm)


def batch_totals(batch):
    return {"labels": float((batch["masked_lm_labels"] >= 0).sum()),
            "rows": float(batch["input_ids"].shape[0])}
