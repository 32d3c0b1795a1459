"""GPT-2 (Radford et al. 2019): pre-LN decoder, tied LM head."""

from . import _init

REFERENCE = "benchmarks.reference.gpt2"


def _dense(i, o):
    return {"kernel": (i, o), "bias": (o,)}


def _ln(h):
    return {"scale": (h,), "bias": (h,)}


def param_shapes(cfg):
    h = cfg["hidden_size"]
    layer = {"qkv": _dense(h, 3 * h), "attn_out": _dense(h, h),
             "fc1": _dense(h, 4 * h), "fc2": _dense(4 * h, h),
             "ln_attn": _ln(h), "ln_mlp": _ln(h)}
    return {"wte": (cfg["vocab_size"], h),
            "wpe": (cfg["max_position_embeddings"], h),
            "blocks": {f"layer_{n}": layer
                       for n in range(cfg["num_layers"])},
            "ln_f": _ln(h)}


def init_params(cfg, seed, out_shardings=None):
    return _init.init_from_shapes(param_shapes(cfg), seed,
                                  cfg["initializer_range"], out_shardings)


def build_program_model(cfg, traffic):
    from deepspeed_tpu.models import GPT2Config, GPT2LMHeadTPU

    keys = ("vocab_size", "hidden_size", "num_layers", "num_heads",
            "max_position_embeddings", "embd_dropout", "attn_dropout",
            "resid_dropout", "initializer_range", "layer_norm_eps")
    extra = {k: cfg[k] for k in ("remat", "loss_chunk") if k in cfg}
    return GPT2LMHeadTPU(GPT2Config(**{k: cfg[k] for k in keys}, **extra))


def eval_inputs(batch, rows):
    """The first ``rows`` rows: ``engine.eval_batch`` on ids without labels
    returns the logits at every position."""
    return {"input_ids": batch["input_ids"][:rows]}


def train_flops_per_step(cfg, traffic, global_batch):
    from .. import counts

    return counts.gpt2_train_flops_per_step(cfg, global_batch,
                                            traffic["seq_len"])


def attention_shape(cfg, traffic, global_batch):
    return (cfg["num_layers"], global_batch, cfg["num_heads"],
            traffic["seq_len"], cfg["hidden_size"] // cfg["num_heads"], True)


def decode_bytes_per_step(cfg, live_context_tokens):
    """Lower bound of the bytes one decode iteration of the served model
    must read (``decode_roofline``'s count)."""
    from .. import counts

    return counts.gpt2_decode_bytes_per_step(cfg, live_context_tokens)


def dropout_rates(cfg):
    return {"embedding": cfg["embd_dropout"], "hidden": cfg["resid_dropout"],
            "attention": cfg["attn_dropout"]}
