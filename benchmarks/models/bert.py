"""BERT (Devlin et al., arXiv:1810.04805) for MLM + NSP pretraining."""

from . import _init

REFERENCE = "benchmarks.reference.bert"


def _dense(i, o):
    return {"kernel": (i, o), "bias": (o,)}


def _ln(h):
    return {"scale": (h,), "bias": (h,)}


def param_shapes(cfg):
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    layer = {"qkv": _dense(h, 3 * h), "attn_out": _dense(h, h),
             "fc1": _dense(h, i), "fc2": _dense(i, h),
             "ln_attn": _ln(h), "ln_mlp": _ln(h)}
    return {
        "bert": {
            "embeddings": {"word": (cfg["vocab_size"], h),
                           "position": (cfg["max_position_embeddings"], h),
                           "token_type": (cfg["type_vocab_size"], h),
                           "ln": _ln(h)},
            "encoder": {f"layer_{n}": layer
                        for n in range(cfg["num_hidden_layers"])},
            "pooler": _dense(h, h)},
        "cls": {"transform": _dense(h, h), "transform_ln": _ln(h),
                "decoder_bias": (cfg["vocab_size"],),
                "seq_relationship": _dense(h, 2)},
    }


def init_params(cfg, seed, out_shardings=None):
    return _init.init_from_shapes(param_shapes(cfg), seed,
                                  cfg["initializer_range"], out_shardings)


def build_program_model(cfg, traffic):
    """The program's model object for this configuration under this
    traffic (the MLM head gathers the mix's predictions per sequence)."""
    from deepspeed_tpu.models import BertConfig, BertForPreTrainingTPU

    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "intermediate_size",
            "max_position_embeddings", "type_vocab_size",
            "hidden_dropout_prob", "attention_probs_dropout_prob",
            "initializer_range", "layer_norm_eps")
    return BertForPreTrainingTPU(BertConfig(
        **{k: cfg[k] for k in keys},
        max_predictions_per_seq=traffic["predictions_per_seq"]))


def eval_inputs(batch, rows):
    """The first ``rows`` rows without labels: ``engine.eval_batch`` then
    returns the MLM logits at every position."""
    return {k: batch[k][:rows] for k in ("input_ids", "attention_mask",
                                         "token_type_ids")}


def train_flops_per_step(cfg, traffic, global_batch):
    from .. import counts

    return counts.bert_train_flops_per_step(
        cfg, global_batch, traffic["seq_len"], traffic["predictions_per_seq"])


def attention_shape(cfg, traffic, global_batch):
    """(layers, batch, heads, seq, head_dim, causal) of the attention
    calls in one step."""
    return (cfg["num_hidden_layers"], global_batch,
            cfg["num_attention_heads"], traffic["seq_len"],
            cfg["hidden_size"] // cfg["num_attention_heads"], False)


def dropout_rates(cfg):
    return {"hidden": cfg["hidden_dropout_prob"],
            "attention": cfg["attention_probs_dropout_prob"]}
