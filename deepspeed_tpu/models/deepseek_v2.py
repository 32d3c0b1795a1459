"""DeepSeek-V2 family, for serving: multi-head latent attention (MLA) with
a decoupled YaRN rotary part, RMSNorm, gated-SiLU MLPs, an untied head,
and group-limited routed experts beside shared ones, of which this chip
holds one routing group (``models/expert_shard.py``).

Written from the published ``config.json`` and modelling code
(``modeling_deepseek.py`` of deepseek-ai/DeepSeek-V2).  Per layer, with
``h = RMSNorm(x)``:

- ``c_q = RMSNorm(h W_DQ)``; per head ``[q_nope ; q_rope] = c_q W_UQ``;
  ``[c_kv ; k_r] = h W_DKV``, ``c_kv <- RMSNorm(c_kv)``; ``q_rope`` and the
  one shared ``k_r`` are rotated by position (YaRN frequencies; the
  published code takes the rotary part as interleaved pairs
  ``(x0, x1), (x2, x3), ...`` and leaves them de-interleaved, and so does
  this); ``k_nope = c_kv W_UK``, ``v = c_kv W_UV`` (``kv_b`` split per
  head); scores ``(q_nope.k_nope + q_rope.k_r) * (128 + 64)^-1/2 * m^2``
  with ``m = 0.1 * mscale_all_dim * ln(factor) + 1``.
- what is CACHED per token and layer is the one row ``[c_kv ; k_r]``, after
  the norm and the rotation, padded to whole lane tiles
  (``inference/kv_cache.py``).  **Prefill** expands: ``k_nope`` and ``v``
  from ``c_kv`` for the whole bucket, causal flash attention at key width
  192 / value width 128, the bucket's rows written as whole pages.
  **Decode** absorbs: ``q~ = q_nope W_UK^T``, scores straight against the
  cached rows, ``u = sum_s a(s) c_kv(s)`` by the paged latent kernel
  (``ops/transformer/mla_paged_attention.py``), ``o = u W_UV``.
- the dense layers' MLP is ``down(silu(gate z) * up z)``; an expert layer is
  ``sum_{e chosen} w_e F_e(z) + F_shared(z)`` with ``w_e = scaling * s_e``
  for the 6 experts chosen group-limited from ``softmax(z W_g)`` in fp32.
- precision: the residual stream, the norms, the softmax and the router
  are fp32; every other product takes operands in the weights' dtype
  (bfloat16 when served) and accumulates in fp32.  The stream stays fp32
  because the router's choice is discrete: rounding ``x`` to bfloat16 at
  every layer moves ``z`` by a few parts in a thousand, enough to flip a
  near-tie between two experts for one token in a few dozen.

Parameter tree: ``embed``, ``layers/layer_<i>/{input_norm, q_a, q_a_norm,
q_b, kv_a, kv_a_norm, kv_b, o, post_norm, mlp | moe}``, ``final_norm``,
``lm_head``; every matrix a ``kernel [in, out]`` with no bias, gate and up
fused as ``gate_up`` (gate first); ``moe`` holds ``router``, ``shared`` and
``experts/{gate_up [held, in, 2w], down [held, w, in]}``.  That is the tree
a caller hands the engine.  The programs take the tree
:meth:`DeepseekV2Serving.prepare_params` makes of it, once: each layer's
``kv_b`` replaced by ``w_uk [heads, nope, latent]`` and ``w_uv [heads,
latent, value]``, the two halves its kernel interleaves per head, each
laid out as the absorbed decode's product takes it.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.transformer.flash_attention import flash_attention_forward
from ..ops.transformer.mla_paged_attention import (
    check_tpu_geometry, mla_paged_decode_attention, padded_row_width)
from ..inference.kv_cache import CacheGroup
from ..parallel.mesh import current_platform
from . import expert_shard
from .layers import gated_silu_mlp, rms_norm


class DeepseekV2Config:
    """The published ``config.json`` keys that shape the model, plus the
    share this chip holds: ``experts_held`` routed experts starting at
    ``first_expert`` (a routing group of an expert-parallel deployment;
    the router still scores all ``n_routed_experts``)."""

    def __init__(self, vocab_size=102400, hidden_size=5120,
                 num_hidden_layers=60, num_attention_heads=128,
                 q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128,
                 intermediate_size=12288, moe_intermediate_size=1536,
                 first_k_dense_replace=1, n_routed_experts=160,
                 n_shared_experts=2, num_experts_per_tok=6, n_group=8,
                 topk_group=3, routed_scaling_factor=16.0,
                 rms_norm_eps=1e-6, rope_theta=10000.0, rope_scaling=None,
                 max_position_embeddings=163840, initializer_range=0.02,
                 experts_held=None, first_expert=0):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.first_k_dense_replace = first_k_dense_replace
        self.n_routed_experts = n_routed_experts
        self.n_shared_experts = n_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.n_group = n_group
        self.topk_group = topk_group
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = float(rope_theta)
        self.rope_scaling = dict(rope_scaling or {
            "type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1,
            "mscale": 0.707, "mscale_all_dim": 0.707,
            "original_max_position_embeddings": 4096})
        self.max_position_embeddings = max_position_embeddings
        self.initializer_range = initializer_range
        self.experts_held = (n_routed_experts if experts_held is None
                             else experts_held)
        self.first_expert = first_expert
        assert n_routed_experts % n_group == 0
        assert 0 <= first_expert \
            and first_expert + self.experts_held <= n_routed_experts

    @property
    def latent_row(self):
        """What one token caches in one layer: ``[c_kv ; k_r]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def is_expert_layer(self, i):
        return i >= self.first_k_dense_replace


# -- the latent attention, as functions of a config --------------------------
# What DeepSeek-V2's programs and Xing-4.0's (``models/xing.py``) both call:
# other ranks, another head count, another YaRN factor, the same equations.
# ``c`` is a model's config (the published keys ``num_attention_heads``,
# ``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_head_dim``,
# ``qk_rope_head_dim``, ``v_head_dim``, ``rms_norm_eps``, ``rope_theta``,
# ``rope_scaling``), ``lp`` one layer's parameters with ``kv_b`` already
# split (:func:`split_kv_b`).  The two attention bodies take the layer's
# input AFTER its norm, in the compute dtype, and return the output
# projection's result in fp32 with the cache written: what a model does with
# it — add it to one stream, or mix it into four — is the model's.

def cache_row(c):
    """The latent row as the cache stores it: whole 128-lane tiles."""
    return padded_row_width(c.latent_row)


def _yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(config):
    """YaRN's inverse frequencies for the rotary part: ``theta^(-2j/d)``
    (extrapolated) and that over ``factor`` (interpolated), blended by the
    linear ramp between the correction dimensions of ``beta_fast`` and
    ``beta_slow`` rotations over the original context."""
    rs, dim, base = config.rope_scaling, config.qk_rope_head_dim, \
        config.rope_theta
    exponents = np.arange(0, dim, 2, dtype=np.float64) / dim
    extrapolated = 1.0 / base ** exponents
    interpolated = extrapolated / rs["factor"]

    def correction_dim(rotations):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 0.001), 0.0, 1.0)
    return (interpolated * ramp + extrapolated * (1.0 - ramp)).astype(
        np.float32)


def yarn_softmax_scale(config):
    """``(nope + rope)^-1/2 * m^2``, ``m`` YaRN's ``mscale_all_dim``
    factor.  (The cos/sin factor ``mscale(f, mscale) / mscale(f,
    mscale_all_dim)`` is applied in :func:`rotate`; 1 as published.)"""
    rs = config.rope_scaling
    m = _yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return m * m / math.sqrt(config.qk_nope_head_dim
                             + config.qk_rope_head_dim)


def rotate(x, positions, config):
    """Rotary embedding of ``x [..., tokens, (heads,) rope]`` at
    ``positions [tokens]``: the last dimension read as interleaved pairs,
    written de-interleaved (first halves, then second halves), as the
    published code does; fp32 inside."""
    rs = config.rope_scaling
    factor = _yarn_mscale(rs["factor"], rs["mscale"]) \
        / _yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    angles = positions.astype(jnp.float32)[:, None] * yarn_inv_freq(config)
    cos, sin = jnp.cos(angles) * factor, jnp.sin(angles) * factor
    if x.ndim == 3:     # [tokens, heads, rope]
        cos, sin = cos[:, None], sin[:, None]
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.concatenate([even * cos - odd * sin, odd * cos + even * sin],
                           axis=-1).astype(x.dtype)


def queries(c, lp, h, positions):
    """``(q_nope, q_rope)`` ``[tokens, heads, .]``, the rotary part
    rotated."""
    c_q = rms_norm(lp["q_a_norm"], h @ lp["q_a"]["kernel"], c.rms_norm_eps)
    q = (c_q @ lp["q_b"]["kernel"]).reshape(
        h.shape[0], c.num_attention_heads, -1)
    return (q[..., :c.qk_nope_head_dim],
            rotate(q[..., c.qk_nope_head_dim:], positions, c))


def latent_rows(c, lp, h, positions):
    """``(c_kv, k_r, row)``: the normalised latent, the rotated shared
    key, and the cache row ``[c_kv ; k_r ; 0 pad]``."""
    ckv = h @ lp["kv_a"]["kernel"]
    c_kv = rms_norm(lp["kv_a_norm"], ckv[:, :c.kv_lora_rank],
                    c.rms_norm_eps)
    k_r = rotate(ckv[:, c.kv_lora_rank:], positions, c)
    pad = jnp.zeros((h.shape[0], cache_row(c) - c.latent_row), h.dtype)
    return c_kv, k_r, jnp.concatenate([c_kv, k_r, pad], axis=-1)


def split_kv_b(c, layers):
    """``layers`` (``{name: layer parameters}``) with each layer's
    ``kv_b/kernel [latent, heads * (nope + value)]`` split into ``w_uk
    [heads, nope, latent]`` and ``w_uv [heads, latent, value]``, batched
    over the leading axis as decode's two absorbed products take them.
    Inside the program the split was a transpose of the whole kernel for
    each product, in every step, of a weight that does not change between
    steps.  ``kv_b`` is not kept: the served tree holds the same bytes."""
    @jax.jit
    def split(kernel):
        w = kernel.reshape(c.kv_lora_rank, c.num_attention_heads, -1)
        return (w[..., :c.qk_nope_head_dim].transpose(1, 2, 0),
                w[..., c.qk_nope_head_dim:].transpose(1, 0, 2))

    out = {}
    for name, lp in layers.items():
        lp = dict(lp)
        lp["w_uk"], lp["w_uv"] = split(lp.pop("kv_b")["kernel"])
        out[name] = lp
    return out


def prefill_attention(c, lp, h, cache, i, block_table, positions, *,
                      kv_block_size, block, interpret):
    """The expanded path over one request's bucket: ``h [s, hidden]`` (the
    layer's normed input in the compute dtype) -> ``(the output
    projection's result [s, hidden] in fp32, cache)`` with the bucket's
    latent rows written into plane ``i`` as whole pages through
    ``block_table``; causal flash attention at key width nope + rope, value
    width ``v_head_dim``, blocks of ``block`` positions."""
    s = h.shape[0]
    n_pages = s // kv_block_size
    q_nope, q_rope = queries(c, lp, h, positions)
    c_kv, k_r, rows = latent_rows(c, lp, h, positions)
    # the bucket's latent rows as whole pages, one scatter
    cache = cache.at[i, block_table[:n_pages]].set(
        rows.reshape(n_pages, kv_block_size, -1).astype(cache.dtype),
        unique_indices=True)
    k_nope = jnp.einsum("sc,hdc->shd", c_kv, lp["w_uk"])
    v = jnp.einsum("sc,hcd->shd", c_kv, lp["w_uv"])
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_r[:, None], q_rope.shape)], axis=-1)
    # the kernel scales by (nope + rope)^-1/2; YaRN's m^2 rides on the
    # query
    m2 = yarn_softmax_scale(c) * math.sqrt(k.shape[-1])
    q = (jnp.concatenate([q_nope, q_rope], axis=-1).astype(
        jnp.float32) * m2).astype(h.dtype)
    # causality alone hides the bucket's padding from the positions that
    # are tokens
    ctx = flash_attention_forward(
        q[None], k[None], v[None], causal=True, block_q=block,
        block_k=block, interpret=interpret,
        name="mla_prefill_attention")[0]
    return jnp.matmul(ctx.reshape(s, -1), lp["o"]["kernel"],
                      preferred_element_type=jnp.float32), cache


def decode_attention(c, lp, h, cache, i, block_tables, ctx_lens, target, *,
                     pages_per_step, interpret):
    """The absorbed path over the batch's slots: ``h [slots, hidden]`` ->
    ``(the output projection's result [slots, hidden] in fp32, cache)``
    with every slot's new latent row appended at ``target`` (block ids,
    offsets) of plane ``i``; scores straight against the cached rows by
    the paged latent kernel."""
    n_slots = h.shape[0]
    q_nope, q_rope = queries(c, lp, h, ctx_lens)
    _, _, rows = latent_rows(c, lp, h, ctx_lens)
    # the append: every slot's new row in one scatter
    cache = cache.at[(i, *target)].set(rows.astype(cache.dtype))
    q_abs = jnp.einsum("bhd,hdc->bhc", q_nope, lp["w_uk"])
    pad = jnp.zeros(q_abs.shape[:2] + (cache_row(c) - c.latent_row,),
                    q_abs.dtype)
    u = mla_paged_decode_attention(
        jnp.concatenate([q_abs, q_rope, pad], axis=-1), cache,
        block_tables, ctx_lens, layer=i, value_width=c.kv_lora_rank,
        scale=yarn_softmax_scale(c), pages_per_step=pages_per_step,
        interpret=interpret)
    o = jnp.einsum("bhc,hcd->bhd", u, lp["w_uv"])
    return jnp.matmul(o.reshape(n_slots, -1), lp["o"]["kernel"],
                      preferred_element_type=jnp.float32), cache


class DeepseekV2ForServing:
    """The served model: its configuration, the shapes of its parameter
    tree, and the serving programs (:meth:`serving`)."""

    def __init__(self, config: DeepseekV2Config):
        self.config = config

    def param_shapes(self):
        c = self.config
        h, heads = c.hidden_size, c.num_attention_heads

        def mlp(width):
            return {"gate_up": {"kernel": (h, 2 * width)},
                    "down": {"kernel": (width, h)}}

        def layer(i):
            out = {
                "input_norm": {"scale": (h,)},
                "q_a": {"kernel": (h, c.q_lora_rank)},
                "q_a_norm": {"scale": (c.q_lora_rank,)},
                "q_b": {"kernel": (c.q_lora_rank, heads * (
                    c.qk_nope_head_dim + c.qk_rope_head_dim))},
                "kv_a": {"kernel": (h, c.latent_row)},
                "kv_a_norm": {"scale": (c.kv_lora_rank,)},
                "kv_b": {"kernel": (c.kv_lora_rank, heads * (
                    c.qk_nope_head_dim + c.v_head_dim))},
                "o": {"kernel": (heads * c.v_head_dim, h)},
                "post_norm": {"scale": (h,)}}
            if c.is_expert_layer(i):
                w = c.moe_intermediate_size
                out["moe"] = {
                    "router": {"kernel": (h, c.n_routed_experts)},
                    "shared": mlp(c.n_shared_experts * w),
                    "experts": {"gate_up": (c.experts_held, h, 2 * w),
                                "down": (c.experts_held, w, h)}}
            else:
                out["mlp"] = mlp(c.intermediate_size)
            return out

        return {"embed": (c.vocab_size, h),
                "layers": {f"layer_{i}": layer(i)
                           for i in range(c.num_hidden_layers)},
                "final_norm": {"scale": (h,)},
                "lm_head": {"kernel": (h, c.vocab_size)}}

    def serving(self):
        return DeepseekV2Serving(self.config)


class DeepseekV2Serving:
    """DeepSeek-V2's side of :class:`~deepspeed_tpu.inference.engine.
    InferenceEngine`'s model interface (``inference/model.py`` says what
    that is): one latent cache buffer, an expanded prefill per bucket, an
    absorbed decode."""

    # what the v5e measured best (PERF.md section 6, PR 27): tiles of the
    # grouped product (rows, k, n) for a decode step's few rows an expert
    # and for a bucket's hundreds; the flash blocks of prefill; the pages
    # the decode kernel multiplies at once
    DECODE_TILING = (128, 5120, 512)
    PREFILL_TILING = (256, 2560, 1024)
    PREFILL_BLOCK = 1024
    DECODE_PAGES = 16

    def __init__(self, config):
        self.config = config
        self.num_layers = config.num_hidden_layers
        self.row = cache_row(config)
        self.interpret = current_platform() != "tpu"

    def cache_buffers(self, icfg):
        """name -> row width of every buffer ``[layers, blocks, block,
        row]`` a layer keeps (all donated)."""
        return {"latent_cache": self.row}

    def cache_groups(self, icfg):
        return [CacheGroup("latent", self.num_layers,
                           self.cache_buffers(icfg))]

    def check_tpu_geometry(self, icfg):
        check_tpu_geometry(self.row, self.config.kv_lora_rank,
                           icfg.kv_block_size)

    # -- pieces shared by the two programs --------------------------------
    def prepare_params(self, params):
        """The tree the programs take: each layer's ``kv_b`` split into
        ``w_uk`` and ``w_uv`` (:func:`split_kv_b`), once."""
        return {**params,
                "layers": split_kv_b(self.config, params["layers"])}

    def _mlp(self, lp, z32, dtype, valid, tiling):
        """The layer's MLP of the normed stream ``z32`` (fp32), computed in
        ``dtype``, and, for an expert layer, its load counters; the result
        in fp32."""
        z = z32.astype(dtype)
        if "mlp" in lp:
            return gated_silu_mlp(lp["mlp"], z, jnp.float32), None
        c, moe = self.config, lp["moe"]
        with jax.named_scope("router"):
            weights, ids = expert_shard.route(
                z32, moe["router"]["kernel"], n_group=c.n_group,
                topk_group=c.topk_group, top_k=c.num_experts_per_tok,
                scaling=c.routed_scaling_factor)
        with jax.named_scope("experts"):
            y, counts = expert_shard.held_experts_ffn(
                z, weights, ids, valid, moe["experts"],
                first_expert=c.first_expert, interpret=self.interpret,
                tiling=tiling, routed=moe["router"]["kernel"].shape[-1])
        with jax.named_scope("shared_experts"):
            y = y + gated_silu_mlp(moe["shared"], z, jnp.float32)
        return y, counts

    def _next_token(self, params, x):
        head = params["lm_head"]["kernel"]
        with jax.named_scope("final_norm"):
            x = rms_norm(params["final_norm"], x, self.config.rms_norm_eps)
        with jax.named_scope("lm_head"):
            logits = jnp.matmul(x.astype(head.dtype), head,
                                preferred_element_type=jnp.float32)
        with jax.named_scope("sample"):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    # -- the two programs --------------------------------------------------
    def build_prefill(self, icfg, bucket_len):
        """``(params, caches, input_ids[1, S], true_len, block_tables,
        next_tokens, slot) -> (out, caches, next_tokens)``: the expanded
        path over one request padded to the bucket; its first token is
        also put into lane ``slot`` of the next decode's input."""
        c = self.config
        bs = icfg.kv_block_size
        assert bucket_len % bs == 0
        block = math.gcd(bucket_len, self.PREFILL_BLOCK)

        def layer(lp, x, cache, i, block_table, positions, valid, dtype):
            with jax.named_scope("attention"):
                h = rms_norm(lp["input_norm"], x, c.rms_norm_eps).astype(
                    dtype)
                y, cache = prefill_attention(
                    c, lp, h, cache, i, block_table, positions,
                    kv_block_size=bs, block=block, interpret=self.interpret)
                x = x + y
            with jax.named_scope("mlp" if "mlp" in lp else "moe"):
                z = rms_norm(lp["post_norm"], x, c.rms_norm_eps)
                y, _ = self._mlp(lp, z, dtype, valid, self.PREFILL_TILING)
                x = x + y
            return x, cache

        def prefill(params, caches, input_ids, true_len, block_tables,
                    next_tokens, slot):
            (cache,), (block_table,) = caches, block_tables
            s = input_ids.shape[1]
            positions = jnp.arange(s)
            valid = positions < true_len
            dtype = params["embed"].dtype
            with jax.named_scope("embed"):
                x = jnp.take(params["embed"], input_ids[0], axis=0).astype(
                    jnp.float32)
            for i in range(self.num_layers):
                lp = params["layers"][f"layer_{i}"]
                with jax.named_scope(f"layer_{i}"):
                    x, cache = layer(lp, x, cache, i, block_table,
                                     positions, valid, dtype)
            with jax.named_scope("final_norm"):
                last = jax.lax.dynamic_slice(
                    x, (true_len - 1, 0), (1, c.hidden_size))
            token = self._next_token(params, last)[0]
            with jax.named_scope("sample"):
                next_tokens = next_tokens.at[slot].set(token)
            return {"tokens": token}, (cache,), next_tokens

        return prefill

    def build_decode(self, icfg):
        """``(params, caches, block_tables, ctx_lens, tokens) -> (out,
        caches)`` for the fixed ``max_batch_slots``-wide batch: the
        absorbed path.  ``out`` carries the next tokens and, in the same
        fetch, the expert layers' load counters (means over the layers)."""
        c = self.config
        bs = icfg.kv_block_size
        n_slots = icfg.max_batch_slots

        def layer(lp, x, cache, i, block_tables, ctx_lens, target, valid,
                  dtype):
            with jax.named_scope("attention"):
                h = rms_norm(lp["input_norm"], x, c.rms_norm_eps).astype(
                    dtype)
                y, cache = decode_attention(
                    c, lp, h, cache, i, block_tables, ctx_lens, target,
                    pages_per_step=min(self.DECODE_PAGES,
                                       icfg.max_blocks_per_seq),
                    interpret=self.interpret)
                x = x + y
            with jax.named_scope("mlp" if "mlp" in lp else "moe"):
                z = rms_norm(lp["post_norm"], x, c.rms_norm_eps)
                y, counts = self._mlp(lp, z, dtype, valid,
                                      self.DECODE_TILING)
                x = x + y
                load = None if counts is None else (
                    *expert_shard.load_counters(counts),
                    expert_shard.pair_passes(
                        counts, n_slots * c.num_experts_per_tok,
                        lp["moe"]["router"]["kernel"].shape[-1],
                        self.DECODE_TILING[0]))
            return x, cache, load

        def decode(params, caches, block_tables, ctx_lens, tokens):
            (cache,), (block_tables,) = caches, block_tables
            dtype = params["embed"].dtype
            with jax.named_scope("embed"):
                x = jnp.take(params["embed"], tokens, axis=0).astype(
                    jnp.float32)
                block_ids = jnp.take_along_axis(
                    block_tables, (ctx_lens // bs)[:, None], axis=1)[:, 0]
                offsets = ctx_lens % bs
                # a slot that serves a request decodes at position >= 1:
                # the dead ones (parked at 0) are routed to no expert
                valid = ctx_lens > 0
            counters = []
            for i in range(self.num_layers):
                lp = params["layers"][f"layer_{i}"]
                with jax.named_scope(f"layer_{i}"):
                    x, cache, load = layer(
                        lp, x, cache, i, block_tables, ctx_lens,
                        (block_ids, offsets), valid, dtype)
                if load is not None:
                    counters.append(load)
            out = {"tokens": self._next_token(params, x)}
            if counters:
                with jax.named_scope("sample"):
                    share, peak, passes = (
                        jnp.mean(jnp.stack(v).astype(jnp.float32))
                        for v in zip(*counters))
                out["moe_local_assignment_share"] = share
                out["moe_expert_load_max_over_mean"] = peak
                out["moe_pair_passes"] = passes
            return out, (cache,)

        return decode
