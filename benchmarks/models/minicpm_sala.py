"""MiniCPM-SALA (openbmb ``config.json``, ``model_type`` ``minicpm_sala``):
``minicpm4`` layers (block-selected sparse attention: 32 query heads over 2 KV
heads of 128, 64 chosen blocks of 64 keys past ``dense_len``, an output gate)
beside ``lightning-attn`` layers (linear attention: 32 heads of 128 whose
per-request state is a float32 ``[128, 128]`` matrix a head, QK-norm, rotary,
output norm and gate), gated-SiLU MLPs 16384 wide, an untied head, muP
scalings.  The configuration gives one pipeline stage: ``mixer_types`` lists
the layers held (a contiguous slice of the published list),
``num_hidden_layers`` their count, ``mup_denominator`` the published depth the
residual scale divides by; heads, widths and vocabulary are whole."""

import math

import jax
import jax.numpy as jnp

from . import _init

REFERENCE = "benchmarks.reference.minicpm_sala"

CACHE_BYTES = 2          # a cached key or value, bfloat16
STATE_BYTES = 4          # a value of a lightning state, float32
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def _kernel(i, o):
    return {"kernel": (i, o)}


def widths(cfg):
    """(query width, K-or-V row width) of a sparse layer, and the lightning
    layers' width."""
    d = cfg["head_dim"]
    return (cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d,
            cfg["lightning_nh"] * cfg["lightning_head_dim"])


def layers_of(cfg, kind):
    return [n for n, t in enumerate(cfg["mixer_types"]) if t == kind]


def param_shapes(cfg):
    h, w = cfg["hidden_size"], cfg["intermediate_size"]
    q_width, kv_row, l_width = widths(cfg)
    d = cfg["lightning_head_dim"]

    def layer(kind):
        out = {"input_norm": {"scale": (h,)}, "post_norm": {"scale": (h,)},
               "mlp": {"gate_up": _kernel(h, 2 * w), "down": _kernel(w, h)}}
        if kind == SPARSE:
            out["qkvg"] = _kernel(h, 2 * q_width + 2 * kv_row)
            out["o"] = _kernel(q_width, h)
        else:
            out["qkvg"] = _kernel(h, 4 * l_width)
            out["o"] = _kernel(l_width, h)
            for name in ("q_norm", "k_norm", "o_norm"):
                out[name] = {"scale": (d,)}
        return out

    assert len(cfg["mixer_types"]) == cfg["num_hidden_layers"]
    return {"embed": (cfg["vocab_size"], h),
            "layers": {f"layer_{n}": layer(kind)
                       for n, kind in enumerate(cfg["mixer_types"])},
            "final_norm": {"scale": (h,)},
            "lm_head": _kernel(h, cfg["vocab_size"])}


def init_params(cfg, seed, out_shardings=None):
    """Seeded weights in the serving dtype, made leaf by leaf on the device:
    N(0, initializer_range) drawn in float32 and rounded to ``weights_dtype``
    (the program is handed these leaves as they are, and the reference reads
    the same rounded values), ones for the norm scales."""
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
        else:
            out.append(draw(shape)(jax.random.fold_in(key, index)))
    return jax.tree_util.tree_unflatten(treedef, out)


def build_program_model(cfg, traffic):
    from deepspeed_tpu.models.minicpm_sala import (MiniCPMSALAConfig,
                                                   MiniCPMSALAForServing)

    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "intermediate_size", "lightning_nh", "lightning_nkv",
            "lightning_head_dim", "mixer_types", "sparse_config",
            "rms_norm_eps", "rope_theta", "scale_emb", "scale_depth",
            "dim_model_base", "mup_denominator", "max_position_embeddings",
            "initializer_range")
    return MiniCPMSALAForServing(MiniCPMSALAConfig(
        **{k: cfg[k] for k in keys}))


# -- counts (the yardstick's own; nothing of the program's) ----------------

def layer_params(cfg, kind):
    h, w = cfg["hidden_size"], cfg["intermediate_size"]
    q_width, kv_row, l_width = widths(cfg)
    mlp_and_norms = 3 * h * w + 2 * h
    if kind == SPARSE:
        return mlp_and_norms + h * (2 * q_width + 2 * kv_row) + q_width * h
    return (mlp_and_norms + h * 4 * l_width + l_width * h
            + 3 * cfg["lightning_head_dim"])


def param_count(cfg):
    """Every parameter this chip holds: its layers, the embedding, the
    final norm and the head."""
    h = cfg["hidden_size"]
    return (sum(layer_params(cfg, kind) for kind in cfg["mixer_types"])
            + 2 * cfg["vocab_size"] * h + h)


def cache_bytes_per_token(cfg):
    """What a token leaves in the paged caches: a K and a V row in every
    sparse layer and a compressed key every ``kernel_stride`` tokens."""
    kv_row = widths(cfg)[1]
    return len(layers_of(cfg, SPARSE)) * CACHE_BYTES * kv_row * (
        2 + 1 / cfg["sparse_config"]["kernel_stride"])


def state_bytes_per_slot(cfg):
    """What a request keeps whatever its length: a float32 ``[d, d]``
    matrix a head in every lightning layer."""
    d = cfg["lightning_head_dim"]
    return len(layers_of(cfg, LIGHTNING)) * cfg["lightning_nh"] * d * d \
        * STATE_BYTES


def pages_read(cfg, context_tokens):
    """(pages a sparse layer's decode reads a (slot, KV head), pages the
    context holds) for a slot whose context is ``context_tokens``."""
    sc = cfg["sparse_config"]
    live = math.ceil(context_tokens / sc["block_size"])
    if context_tokens <= sc["dense_len"]:
        return live, live
    return min(sc["topk"], live), live


def decode_bytes_per_step(cfg, live_context_tokens, dtype_bytes=2):
    """Lower bound of the bytes one decode iteration must move
    (``decode_roofline``'s count): every weight a step uses, once (the
    layers, the final norm and the head; the embedding is read by row, not
    counted); per slot of ``decode_batch_for_counts`` the lightning states
    read AND written; per (slot, sparse layer) ``min(top_k, live pages)``
    pages of K and of V for each KV head's lanes (every page while the
    context is at most ``dense_len``) and the compressed keys of the live
    context.  ``live_context_tokens`` is the sum over the slots; each
    slot is taken at the mean."""
    h = cfg["hidden_size"]
    slots = cfg["decode_batch_for_counts"]
    sc = cfg["sparse_config"]
    kv_row = widths(cfg)[1]
    weights = sum(layer_params(cfg, kind) for kind in cfg["mixer_types"]) \
        + cfg["vocab_size"] * h + h
    context = live_context_tokens / slots
    read, _ = pages_read(cfg, context)
    paged = len(layers_of(cfg, SPARSE)) * slots * CACHE_BYTES * kv_row * (
        2 * read * sc["block_size"] + context / sc["kernel_stride"])
    return (weights * dtype_bytes + 2 * slots * state_bytes_per_slot(cfg)
            + paged)


def counts(cfg, live_context_tokens, slots, seq):
    """FLOPs and lower-bound HBM bytes of ONE call (one layer) of each new
    kernel: the three decode calls of a step over ``live_context_tokens``
    cached tokens in ``slots`` slots, the two prefill calls of a request of
    ``seq`` (bucket) positions.  UNREAD today: the harness puts no
    ``counts()`` of a serving cell into ``ctx["counts"]``
    (``benchmarks/serve.py``), so no metric takes these; ISSUE 40 asked for
    them for the ``benchmark`` PR that wires per-kernel roofline shares
    (PERF.md, Open question 10), and one test holds them to numbers worked
    by hand.

    - ``sparse_block_select``: per slot, query head and compressed key a
      score over ``head_dim`` (2 FLOPs a value); the compressed keys read
      once, the queries in, the scores out (float32).
    - ``sparse_paged_decode_attention``: per slot and query head, a score
      and a value sum over the keys of the pages read (``4 * head_dim``
      FLOPs a key); each KV head's lanes of those pages of K and V, the
      queries in and the context out.
    - ``sparse_prefill_attention``: the dense causal triangle the masked
      flash kernel computes (``4 * head_dim`` FLOPs a query head and
      visible pair); q, k, v in, the context out, the mask in.
    - ``lightning_prefill_scan``: per head and chunk of ``C`` = 256 rows
      the products ``Q K^T`` and ``(.) V`` (``4 C^2 d``), ``Q S`` twice (the
      state's high and low halves) and ``K^T V`` (``6 C d^2``); q, k, v in
      (bfloat16), o out (float32).
    - ``lightning_decode_update``: per slot and head ``d * d`` values
      decayed, updated and read out (5 FLOPs a value); the state read and
      written (float32)."""
    sc = cfg["sparse_config"]
    heads, kv_heads, d = (cfg["num_attention_heads"],
                          cfg["num_key_value_heads"], cfg["head_dim"])
    q_width, kv_row, l_width = widths(cfg)
    context = live_context_tokens / slots
    kernels = max((context - sc["kernel_size"]) // sc["kernel_stride"] + 1,
                  0)
    read, _ = pages_read(cfg, context)
    keys = read * sc["block_size"]
    lh, ld = cfg["lightning_nh"], cfg["lightning_head_dim"]
    chunk = 256
    return {
        "sparse_select_flops": 2 * slots * heads * d * kernels,
        "sparse_select_bytes": slots * (
            CACHE_BYTES * (kernels * kv_row + q_width)
            + 4 * kv_heads * kernels),
        "sparse_decode_flops": 4 * slots * heads * d * keys,
        "sparse_decode_bytes": CACHE_BYTES * slots * (
            2 * keys * kv_row + 2 * q_width),
        "sparse_prefill_flops": 4 * heads * d * seq * (seq + 1) // 2,
        "sparse_prefill_bytes": CACHE_BYTES * seq * (
            2 * q_width + 2 * kv_row
            + kv_heads * seq // sc["block_size"]),
        "lightning_prefill_flops": lh * (seq // chunk) * (
            4 * chunk * chunk * ld + 6 * chunk * ld * ld),
        "lightning_prefill_bytes": seq * l_width * (3 * CACHE_BYTES + 4),
        "lightning_decode_flops": 5 * slots * lh * ld * ld,
        "lightning_decode_bytes": 2 * slots * lh * ld * ld * STATE_BYTES,
    }
