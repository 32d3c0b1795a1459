"""Ouro-2.6B (ByteDance ``config.json``, ``model_type`` ``ouro``): a looped
decoder.  48 layers of 16 heads of 128 (a KV head a query head), gated-SiLU
MLPs 5632 wide, an RMSNorm before and after each sublayer, whole-head rotary
positions, an untied head — run ``total_ut_steps`` = 4 times over the same
weights, the final norm after each walk, an exit gate ``[hidden, 1]`` read
after each.  The configuration is the model whole: nothing is reduced."""

import jax
import jax.numpy as jnp

from . import _init

REFERENCE = "benchmarks.reference.ouro"

CACHE_BYTES = 2          # a cached value, bfloat16


def _kernel(i, o):
    return {"kernel": (i, o)}


def kv_row(cfg):
    """Width of a token's K (or V) row in one cache plane."""
    return cfg["num_key_value_heads"] * cfg["head_dim"]


def cache_planes(cfg):
    """Places a token leaves a K and a V row at: one a (loop step, layer)."""
    return cfg["total_ut_steps"] * cfg["num_hidden_layers"]


def param_shapes(cfg):
    h, width = cfg["hidden_size"], cfg["intermediate_size"]
    q_width = cfg["num_attention_heads"] * cfg["head_dim"]
    layer = {"norm_attn_in": {"scale": (h,)},
             "qkv": _kernel(h, q_width + 2 * kv_row(cfg)),
             "norm_attn_out": {"scale": (h,)},
             "o": _kernel(q_width, h),
             "norm_mlp_in": {"scale": (h,)},
             "gate_up": _kernel(h, 2 * width),
             "down": _kernel(width, h),
             "norm_mlp_out": {"scale": (h,)}}
    return {"embed": (cfg["vocab_size"], h),
            "layers": {f"layer_{n}": dict(layer)
                       for n in range(cfg["num_hidden_layers"])},
            "final_norm": {"scale": (h,)},
            "exit_gate": {"kernel": (h, 1), "bias": (1,)},
            "lm_head": _kernel(h, cfg["vocab_size"])}


def init_params(cfg, seed):
    """Seeded weights in the serving dtype, made leaf by leaf on the device
    (``exaone_moe.py``'s recipe): N(0, initializer_range) drawn in float32
    and rounded to ``weights_dtype``, ones for the norm scales, zero for the
    gate's bias."""
    dtype = jnp.dtype(cfg.get("weights_dtype", "bfloat16"))
    std, key = cfg["initializer_range"], _init.seed_key(seed)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))

    def draw(shape):
        return jax.jit(lambda k: (std * jax.random.normal(
            k, shape, jnp.float32)).astype(dtype))

    out = []
    for index, (path, shape) in enumerate(leaves):
        if path[-1].key == "scale":
            out.append(jnp.ones(shape, dtype))
        elif path[-1].key == "bias":
            out.append(jnp.zeros(shape, dtype))
        else:
            out.append(draw(shape)(jax.random.fold_in(key, index)))
    return jax.tree_util.tree_unflatten(treedef, out)


def build_program_model(cfg, traffic):
    from deepspeed_tpu.models.ouro import OuroConfig, OuroForServing

    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "intermediate_size", "total_ut_steps", "early_exit_threshold",
            "rms_norm_eps", "rope_theta", "max_position_embeddings")
    return OuroForServing(OuroConfig(**{k: cfg[k] for k in keys}))


# -- counts (the yardstick's own; nothing of the program's) ----------------

def layer_params(cfg):
    """Parameters of one layer: q, k, v, o; gate, up, down; four norms."""
    h = cfg["hidden_size"]
    q_width = cfg["num_attention_heads"] * cfg["head_dim"]
    return (h * (q_width + 2 * kv_row(cfg)) + q_width * h
            + 3 * h * cfg["intermediate_size"] + 4 * h)


def param_count(cfg):
    """Every parameter: the layers ONCE (the loop shares them), embedding
    and untied head, the final norm, the gate and its bias."""
    h = cfg["hidden_size"]
    return (cfg["num_hidden_layers"] * layer_params(cfg)
            + 2 * cfg["vocab_size"] * h + h + h + 1)


def cache_bytes_per_token(cfg):
    """A K and a V row in every plane."""
    return cache_planes(cfg) * 2 * kv_row(cfg) * CACHE_BYTES


def decode_bytes_per_step(cfg, live_context_tokens, dtype_bytes=2):
    """Lower bound of the bytes one decode iteration must read
    (``decode_roofline``'s count): the layers' weights ``total_ut_steps``
    TIMES, the head, the final norm and the gate once (the embedding is read
    by row, not counted), plus the K and V rows of every live token in every
    plane.

    Why the layers count four times: the chip's 128 MiB of VMEM holds about
    1/37 of the 4.93 GB of layer weights, and step ``r + 1`` of layer 0
    waits on step ``r`` of layer 47, so between two uses of a layer's
    weights every other layer's pass through, and no order of the work
    keeps them on the chip: each walk reads them from HBM again.  The head
    is used after the last step alone; the final norm and the gate are 4 k
    values."""
    h = cfg["hidden_size"]
    weights = (cfg["total_ut_steps"] * cfg["num_hidden_layers"]
               * layer_params(cfg)
               + cfg["vocab_size"] * h + h + h + 1)
    return (weights * dtype_bytes
            + live_context_tokens * cache_bytes_per_token(cfg))
