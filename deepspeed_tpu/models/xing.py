"""Xing-4.0 (``model_type`` ``xing4_0``), for serving: DeepSeek-V2's latent
attention and the DeepSeek-V3 lineage's sigmoid-scored experts on a residual
path that is ``hc_mult`` = 4 streams, mixed around every sublayer by
Sinkhorn-normalised maps made from the streams themselves
(manifold-constrained hyper-connections, mHC).

Written from the published ``config.json`` of XingChen-AGI/Xing4.0-29B-A4B
(every size, the router's keys, ``hc_mult``, ``hc_sinkhorn_iters``,
``hc_eps``, ``mhc_h_res_clamp_min`` / ``max``) and from the papers its keys
name: mHC (DeepSeek-AI, arXiv:2512.24880) for the maps, Hyper-Connections
(Zhu et al., arXiv:2409.19606) for the way in and out.  What those do not
fix is ASSUMED and listed in the benchmark's configuration file.

The stream is ``X [tokens, n, C]`` in fp32, ``n`` = ``hc_mult``, ``C`` =
``hidden_size`` (the programs hold it as ``[tokens, n C]``, the rows side by
side: ``hyper_connection.py`` says why).  A layer has two sublayers ``F``: latent attention, then the
dense gated-SiLU MLP (layers below ``first_k_dense_replace``) or the expert
layer.  For each sublayer, with its own ``Phi [n C, 2n + n^2]``, ``b [2n +
n^2]`` and ``alpha_pre, alpha_post, alpha_res``:

1. ``x^ = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)`` — one RMS over all
   ``n C`` values, no learned scale;
2. ``[p ; q ; r] = x^ Phi`` (widths ``n``, ``n``, ``n^2``), fp32 at
   ``HIGHEST``;
3. ``H_pre = sigmoid(alpha_pre p + b_pre)``; ``H_post = 2 sigmoid(alpha_post
   q + b_post)``;
4. ``R = clip(alpha_res mat(r) + b_res, mhc_h_res_clamp_min,
   mhc_h_res_clamp_max)``; ``M = exp(R)``; ``hc_sinkhorn_iters`` times: every
   row / (its sum + ``hc_eps``), then every column / (its sum + ``hc_eps``);
   ``H_res = M``;
5. ``u = sum_i H_pre[i] X_i``; ``y = F(RMSNorm_w(u))`` — ``F`` with its own
   learned pre-norm, exactly DeepSeek-V2's sublayer on a ``C``-wide input;
6. ``X'_i = sum_j H_res[i, j] X_j + H_post[i] y``.

In: ``X_0`` is the token's embedding in all ``n`` rows.  Out: ``x = sum_i
X_i``, then the final RMSNorm and the untied head.  Steps 1-5 are
``ops/transformer/hyper_connection.py``'s ``mhc_pre_mix``, step 6 its
``mhc_post_res_mix``: one pass over the stream each.

- attention: the functions ``models/deepseek_v2.py``'s own programs call,
  at this config's ranks (``q_lora_rank`` 768, 32 heads, YaRN factor 64 with
  ``mscale`` = ``mscale_all_dim`` = 1, so ``m = 0.1 ln 64 + 1`` squared into
  the softmax scale).  What is cached, the expanded prefill and the absorbed
  decode are ``models/deepseek_v2.py``'s docstring with these numbers.
- an expert layer is ``sum_{e in C} w_e F_e(z) + F_shared(z)`` with ``s =
  sigmoid(z W_g)`` in fp32, ``C`` the top 4 of ``s + b`` (``noaux_tc``; one
  group), ``w_e = 2 s_e / sum_{e' in C} s_e'`` (``norm_topk_prob``,
  ``routed_scaling_factor``): ``expert_shard.route``'s path for K-EXAONE.
  ``ep_size`` 1 is the model's own: a chip that holds a layer holds all 64
  experts (``experts_held`` / ``first_expert`` are there as in the other
  expert models).
- precision as the other decoders: the weights' dtype (bfloat16 when served)
  for the cache and every product's operands, fp32 for the accumulators, the
  stream, the norms, the softmax and the router — and every step 1-4 and
  both mixes.

Left out: the multi-token-prediction module (``num_nextn_predict_layers``),
a draft head the main model's logits do not depend on; the engine's step is
one token a slot.

Parameter tree: DeepSeek-V2's (``embed``, ``layers/layer_<i>/{input_norm,
q_a, q_a_norm, q_b, kv_a, kv_a_norm, kv_b, o, post_norm, mlp | moe}``,
``final_norm``, ``lm_head``; ``moe/router`` with the selection ``bias``
beside its ``kernel``) plus, a layer, ``hc_attn`` and ``hc_mlp`` = ``{phi [n
C, 2n + n^2], bias [2n + n^2], alpha [3]}``.  The programs take what
:meth:`XingServing.prepare_params` makes of it, once: ``kv_b`` split into
``w_uk`` / ``w_uv`` (``mla.split_kv_b``) and each ``hc_*`` packed for the
kernels (``hyper_connection.pack_maps``: ``phi_t``, ``affine``, fp32).
"""

import math

import jax
import jax.numpy as jnp

from ..inference.kv_cache import CacheGroup
from ..ops.transformer import hyper_connection as hc
from ..ops.transformer.mla_paged_attention import check_tpu_geometry
from ..parallel.mesh import current_platform
from . import deepseek_v2 as mla
from . import expert_shard
from .layers import gated_silu_mlp, rms_norm

SUBLAYERS = ("hc_attn", "hc_mlp")


class XingConfig:
    """The published ``config.json`` keys that shape the model, plus the
    share this chip holds: ``experts_held`` routed experts starting at
    ``first_expert`` (all ``n_routed_experts`` from 0 as published:
    ``ep_size`` 1; the router scores all of them either way)."""

    def __init__(self, vocab_size=131072, hidden_size=3584,
                 num_hidden_layers=40, num_attention_heads=32,
                 q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, intermediate_size=9216,
                 moe_intermediate_size=1024, first_k_dense_replace=2,
                 n_routed_experts=64, n_shared_experts=1,
                 num_experts_per_tok=4, n_group=1, topk_group=1,
                 routed_scaling_factor=2.0, norm_topk_prob=True, hc_mult=4,
                 hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30.0,
                 mhc_h_res_clamp_max=30.0, rms_norm_eps=1e-6,
                 rope_theta=10000.0, rope_scaling=None,
                 max_position_embeddings=262144, initializer_range=0.02,
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
        self.norm_topk_prob = bool(norm_topk_prob)
        self.hc_mult = int(hc_mult)
        self.hc_sinkhorn_iters = int(hc_sinkhorn_iters)
        self.hc_eps = float(hc_eps)
        self.mhc_h_res_clamp = (float(mhc_h_res_clamp_min),
                                float(mhc_h_res_clamp_max))
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = float(rope_theta)
        self.rope_scaling = dict(rope_scaling or {
            "type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
            "mscale": 1, "mscale_all_dim": 1,
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

    @property
    def hc_maps(self):
        """Values of a sublayer's three maps: ``2n + n^2``."""
        return 2 * self.hc_mult + self.hc_mult ** 2

    def is_expert_layer(self, i):
        return i >= self.first_k_dense_replace


class XingForServing:
    """The served model: its configuration, the shapes of its parameter
    tree, and the serving programs (:meth:`serving`)."""

    def __init__(self, config: XingConfig):
        self.config = config

    def param_shapes(self):
        c = self.config
        h, heads = c.hidden_size, c.num_attention_heads

        def mlp(width):
            return {"gate_up": {"kernel": (h, 2 * width)},
                    "down": {"kernel": (width, h)}}

        def maps():
            return {"phi": (c.hc_mult * h, c.hc_maps), "bias": (c.hc_maps,),
                    "alpha": (3,)}

        def layer(i):
            out = {
                "hc_attn": maps(), "hc_mlp": maps(),
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
                    "router": {"kernel": (h, c.n_routed_experts),
                               "bias": (c.n_routed_experts,)},
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
        return XingServing(self.config)


class XingServing:
    """Xing's side of :class:`~deepspeed_tpu.inference.engine.
    InferenceEngine`'s model interface (``inference/model.py``): one latent
    cache buffer, an expanded prefill per bucket, an absorbed decode; the
    four streams live inside a program and the engine sees none of them."""

    # the grouped product's tiles (rows, k, n) for a decode step's two rows
    # an expert and for a bucket's hundreds (hidden 3584 = 28 x 128 divides
    # by 1792; prefill's as DeepSeek-V2's, unswept here: PERF.md Open
    # questions); the flash blocks of prefill; the pages the decode kernel
    # multiplies at once.  Decode's blocks are WHOLE ROWS of an expert's
    # matrix ([512, 2048] of gate_up, [512, 3584] of down: one contiguous
    # read each).  A block of 512 columns is 16 KB every 64 KB, and what that
    # costs follows where the allocator put the weights: 1.075 or 1.126 ms a
    # product on the chip, a decode step 12.3 or 12.7 ms, fixed for the life
    # of a process (PERF.md section 6, PR 47)
    DECODE_TILING = (128, 512, 3584)
    PREFILL_TILING = (256, 1792, 1024)
    PREFILL_BLOCK = 1024
    DECODE_PAGES = 16
    # the kernels' tile of tokens: a program whose tokens fill whole tiles (a
    # prefill bucket) takes the two mixes as kernels, one of a few rows (a
    # decode step's 32) their jax.numpy forms, which the chip reads no
    # slower there (PERF.md section 6, PR 47)
    MIX_TILE = 128

    def __init__(self, config):
        self.config = config
        self.num_layers = config.num_hidden_layers
        self.row = mla.cache_row(config)
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
        hc.check_tpu_geometry(self.config.hidden_size, self.config.hc_mult)

    def prepare_params(self, params):
        """The tree the programs take: each layer's ``kv_b`` split into
        ``w_uk`` and ``w_uv`` and its two ``hc_*`` packed for the mixes
        (``phi`` transposed into groups of eight rows, ``alpha`` beside
        ``bias``, fp32), once."""
        pack = jax.jit(lambda m: hc.pack_maps(m, self.config.hc_mult))
        layers = {}
        for name, lp in mla.split_kv_b(self.config,
                                       params["layers"]).items():
            layers[name] = {**lp, **{k: pack(lp[k]) for k in SUBLAYERS}}
        return {**params, "layers": layers}

    # -- pieces shared by the two programs --------------------------------
    def _mixes(self, tokens):
        """``(pre, post)``: steps 1-5 ``(X, packed) -> (u, maps)`` and step
        6 ``(X, y, maps) -> X'`` for a program of ``tokens`` rows: the
        kernels where they fill whole tiles, else the plain forms."""
        c = self.config
        maps = dict(n=c.hc_mult, eps=c.rms_norm_eps,
                    sinkhorn_iters=c.hc_sinkhorn_iters,
                    sinkhorn_eps=c.hc_eps, clamp=c.mhc_h_res_clamp)
        if tokens % self.MIX_TILE == 0:
            return (lambda x, packed: hc.mhc_pre_mix(
                        x, packed, tile=self.MIX_TILE,
                        interpret=self.interpret, **maps),
                    lambda x, y, m: hc.mhc_post_res_mix(
                        x, y, m, n=c.hc_mult, tile=self.MIX_TILE,
                        interpret=self.interpret))
        return (lambda x, packed: hc.mhc_pre_mix_xla(x, packed, **maps),
                lambda x, y, m: hc.mhc_post_res_mix_xla(x, y, m,
                                                        n=c.hc_mult))

    def _layer(self, lp, x, mixes, attention, dtype, valid, tiling):
        """One layer over the stream ``x [tokens, n C]``: ``(x, what
        attention returned beside its output, the expert layer's counts or
        None, the two sublayers' maps)``."""
        c = self.config
        pre, post = mixes
        with jax.named_scope("hc_pre"):
            u, attn_maps = pre(x, lp["hc_attn"])
        with jax.named_scope("attention"):
            h = rms_norm(lp["input_norm"], u, c.rms_norm_eps).astype(dtype)
            y, cache = attention(lp, h)
        with jax.named_scope("hc_post"):
            x = post(x, y, attn_maps)
        with jax.named_scope("hc_pre"):
            u, mlp_maps = pre(x, lp["hc_mlp"])
        with jax.named_scope("mlp" if "mlp" in lp else "moe"):
            z = rms_norm(lp["post_norm"], u, c.rms_norm_eps)
            y, counts = self._mlp(lp, z, dtype, valid, tiling)
        with jax.named_scope("hc_post"):
            x = post(x, y, mlp_maps)
        return x, cache, counts, (attn_maps, mlp_maps)

    def _mlp(self, lp, z32, dtype, valid, tiling):
        """The layer's MLP of the normed mix ``z32`` (fp32), computed in
        ``dtype``, and, for an expert layer, the pairs each held expert got;
        the result in fp32."""
        z = z32.astype(dtype)
        if "mlp" in lp:
            return gated_silu_mlp(lp["mlp"], z, jnp.float32), None
        c, moe = self.config, lp["moe"]
        with jax.named_scope("router"):
            weights, ids = expert_shard.route(
                z32, moe["router"]["kernel"], n_group=c.n_group,
                topk_group=c.topk_group, top_k=c.num_experts_per_tok,
                scaling=c.routed_scaling_factor, scoring="sigmoid",
                bias=moe["router"]["bias"], renormalise=c.norm_topk_prob)
        with jax.named_scope("experts"):
            y, counts = expert_shard.held_experts_ffn(
                z, weights, ids, valid, moe["experts"],
                first_expert=c.first_expert, interpret=self.interpret,
                tiling=tiling, routed=moe["router"]["kernel"].shape[-1])
        with jax.named_scope("shared_experts"):
            y = y + gated_silu_mlp(moe["shared"], z, jnp.float32)
        return y, counts

    def _streams(self, params, ids):
        """``X_0 [tokens, n C]``: a token's embedding in all ``n`` rows."""
        e = jnp.take(params["embed"], ids, axis=0).astype(jnp.float32)
        return jnp.concatenate([e] * self.config.hc_mult, axis=1)

    def _merged(self, x):
        """``sum_i X_i [tokens, C]``."""
        return x.reshape(x.shape[0], self.config.hc_mult, -1).sum(axis=1)

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
        mixes = self._mixes(bucket_len)

        def prefill(params, caches, input_ids, true_len, block_tables,
                    next_tokens, slot):
            (cache,), (block_table,) = caches, block_tables
            positions = jnp.arange(input_ids.shape[1])
            valid = positions < true_len
            dtype = params["embed"].dtype
            with jax.named_scope("embed"):
                x = self._streams(params, input_ids[0])
            for i in range(self.num_layers):
                lp = params["layers"][f"layer_{i}"]

                def attention(lp, h, cache=cache, i=i):
                    return mla.prefill_attention(
                        c, lp, h, cache, i, block_table, positions,
                        kv_block_size=bs, block=block,
                        interpret=self.interpret)

                with jax.named_scope(f"layer_{i}"):
                    x, cache, _, _ = self._layer(
                        lp, x, mixes, attention, dtype, valid,
                        self.PREFILL_TILING)
            with jax.named_scope("hc_merge"):
                last = self._merged(jax.lax.dynamic_slice(
                    x, (true_len - 1, 0), (1, x.shape[1])))
            token = self._next_token(params, last)[0]
            with jax.named_scope("sample"):
                next_tokens = next_tokens.at[slot].set(token)
            return {"tokens": token}, (cache,), next_tokens

        return prefill

    def build_decode(self, icfg):
        """``(params, caches, block_tables, ctx_lens, tokens) -> (out,
        caches)`` for the fixed ``max_batch_slots``-wide batch: the
        absorbed path.  ``out`` carries the next tokens and, in the same
        fetch, the expert layers' load counters (means over the layers) and
        the maps' (``hc_streams``; ``hc_res_stochastic_err_max``: the
        largest distance of a row or column sum of ``H_res`` from 1 over
        the live slots and the sublayers; ``hc_pre_mass_mean``: the mean of
        ``sum_i H_pre[i]`` over them)."""
        c = self.config
        bs = icfg.kv_block_size
        n_slots = icfg.max_batch_slots
        mixes = self._mixes(n_slots)
        pages = min(self.DECODE_PAGES, icfg.max_blocks_per_seq)

        def decode(params, caches, block_tables, ctx_lens, tokens):
            (cache,), (block_tables,) = caches, block_tables
            dtype = params["embed"].dtype
            with jax.named_scope("embed"):
                x = self._streams(params, tokens)
                block_ids = jnp.take_along_axis(
                    block_tables, (ctx_lens // bs)[:, None], axis=1)[:, 0]
                target = (block_ids, ctx_lens % bs)
                # a slot that serves a request decodes at position >= 1:
                # the dead ones (parked at 0) are routed to no expert
                valid = ctx_lens > 0
            loads, maps = [], []
            for i in range(self.num_layers):
                lp = params["layers"][f"layer_{i}"]

                def attention(lp, h, cache=cache, i=i):
                    return mla.decode_attention(
                        c, lp, h, cache, i, block_tables, ctx_lens, target,
                        pages_per_step=pages, interpret=self.interpret)

                with jax.named_scope(f"layer_{i}"):
                    x, cache, counts, layer_maps = self._layer(
                        lp, x, mixes, attention, dtype, valid,
                        self.DECODE_TILING)
                    maps.extend(layer_maps)
                    if counts is not None:
                        with jax.named_scope("moe"):
                            loads.append((
                                *expert_shard.load_counters(counts),
                                expert_shard.pair_passes(
                                    counts, n_slots * c.num_experts_per_tok,
                                    lp["moe"]["router"]["kernel"].shape[-1],
                                    self.DECODE_TILING[0])))
            with jax.named_scope("hc_merge"):
                x = self._merged(x)
            out = {"tokens": self._next_token(params, x)}
            with jax.named_scope("sample"):
                if loads:
                    share, peak, passes = (
                        jnp.mean(jnp.stack(v).astype(jnp.float32))
                        for v in zip(*loads))
                    out["moe_local_assignment_share"] = share
                    out["moe_expert_load_max_over_mean"] = peak
                    out["moe_pair_passes"] = passes
                pre, _, res = hc.unpack_maps(
                    jnp.concatenate(maps, axis=0), c.hc_mult)
                live = jnp.tile(valid, len(maps))
                off = jnp.maximum(jnp.abs(res.sum(axis=1) - 1.0),
                                  jnp.abs(res.sum(axis=2) - 1.0)).max(axis=1)
                out["hc_streams"] = jnp.float32(c.hc_mult)
                out["hc_res_stochastic_err_max"] = jnp.max(
                    jnp.where(live, off, 0.0))
                out["hc_pre_mass_mean"] = jnp.sum(
                    jnp.where(live, pre.sum(axis=1), 0.0)) / jnp.maximum(
                        live.sum().astype(jnp.float32), 1.0)
            return out, (cache,)

        return decode
