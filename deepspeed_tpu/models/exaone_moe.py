"""K-EXAONE (``model_type`` ``exaone_moe``), for serving: sliding-window
layers beside full-attention ones over grouped KV heads, RMSNorm on every
head's query and key, rotary positions on the window layers only, gated-SiLU
MLPs, an untied head, and a sigmoid-scored top-k expert layer beside a shared
expert, of which this chip holds a contiguous slice
(``models/expert_shard.py``).

Written from the published ``config.json`` of LGAI-EXAONE/K-EXAONE-236B-A23B
(every size, ``layer_types`` / ``sliding_window``, ``mlp_layer_types``,
sigmoid scoring, ``norm_topk_prob``, ``routed_scaling_factor``, ``rope_theta``)
and two conventions it does not spell out, ASSUMED and said so in the
benchmark's configuration file: QK-norm and rotary on window layers only are
the EXAONE-4.0 family's (arXiv:2507.11407), which this ``model_type``
extends; pre-norm placement and the selection bias ``b`` follow the
DeepSeek-V3 lineage its router keys come from.  Per layer ``n`` of type
``layer_types[n]``, with ``u = RMSNorm(x)``:

- ``[q ; k ; v] = u W_QKV`` (one fused kernel, q's 64 heads first, then the
  8 key heads, then the 8 value heads, each 128 wide); every head's q and k
  through RMSNorm over its 128 values (one ``[128]`` scale each a layer);
  on WINDOW layers q and k are then rotated by position (theta 1e6, pairs
  ``(c, c + 64)`` over the whole head); query head ``i`` reads KV head
  ``i // 8``; scores ``q.k / sqrt(128)`` over the positions ``s <= t``
  (full) or ``t - 127 <= s <= t`` (window: 128 keys, ``t`` among them).
- what is CACHED per token is the K row and the V row of ``8 x 128`` after
  norm and rotation, in TWO cache groups (``inference/kv_cache.py``): the
  full layers' pages cover the whole context, the window layers' a ring of
  ``ring_pages(128, block)`` pages a request whatever its length.
  **Prefill** runs the flash kernel over the bucket (grouped heads indexed,
  not repeated; the window layers' blocks outside the band skipped) and
  writes the bucket's pages (full) or the ring's pages that hold the last
  keys before ``true_len`` (window).  **Decode** appends one row a slot and
  calls the grouped paged kernel (``ops/transformer/paged_attention.py``),
  whose page loop is bounded below as well as above on window layers.
- a dense layer's MLP is ``down(silu(gate z) * up z)``; a sparse layer's is
  ``sum_{e in C, e held here} w_e F_e(z) + F_shared(z)`` with ``s =
  sigmoid(z W_g)`` in fp32, ``C`` the top 8 of ``s + b``, ``w_e = 2.5 * s_e
  / sum_{e' in C} s_e'`` — the normaliser over ALL eight chosen, held here
  or not.
- precision as ``models/deepseek_v2.py``: the residual stream, the norms,
  the softmax and the router in fp32, every other product in the weights'
  dtype with an fp32 accumulator.

Left out: the multi-token-prediction module (``num_nextn_predict_layers``),
a draft head for self-speculation that the main model's logits do not
depend on; the engine's step is one token a slot.

Parameter tree: ``embed``, ``layers/layer_<i>/{input_norm, qkv, q_norm,
k_norm, o, post_norm, mlp | moe}``, ``final_norm``, ``lm_head``; every
matrix a ``kernel [in, out]`` with no bias; ``moe`` holds ``router``
(``kernel`` and the selection ``bias``), ``shared`` and ``experts/{gate_up
[held, in, 2w], down [held, w, in]}``.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..inference.kv_cache import CacheGroup
from ..ops.transformer.flash_attention import flash_attention_forward
from ..ops.transformer.paged_attention import (check_gqa_tpu_geometry,
                                               paged_decode_attention,
                                               ring_pages)
from ..parallel.mesh import current_platform
from . import expert_shard
from .layers import gated_silu_mlp, rms_norm

WINDOW, FULL = "sliding_attention", "full_attention"


class ExaoneMoeConfig:
    """The published ``config.json`` keys that shape the model, plus the
    share this chip holds: ``experts_held`` routed experts from
    ``first_expert`` on (the router still scores all ``num_experts``).
    ``layer_types`` and ``mlp_layer_types`` may be the published lists
    whole: the first ``num_hidden_layers`` entries are the layers run."""

    def __init__(self, vocab_size=153600, hidden_size=6144,
                 num_hidden_layers=48, num_attention_heads=64,
                 num_key_value_heads=8, head_dim=128,
                 intermediate_size=18432, moe_intermediate_size=2048,
                 num_experts=128, num_experts_per_tok=8,
                 num_shared_experts=1, layer_types=None, sliding_window=128,
                 mlp_layer_types=None, n_group=1, topk_group=1,
                 routed_scaling_factor=2.5, norm_topk_prob=True,
                 rms_norm_eps=1e-5, rope_theta=1e6,
                 max_position_embeddings=262144, initializer_range=0.02,
                 experts_held=None, first_expert=0):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.num_shared_experts = num_shared_experts
        n = num_hidden_layers
        self.layer_types = list(layer_types or (
            [WINDOW, WINDOW, WINDOW, FULL] * n))[:n]
        self.mlp_layer_types = list(mlp_layer_types or (
            ["dense"] + ["sparse"] * n))[:n]
        self.sliding_window = sliding_window
        self.n_group = n_group
        self.topk_group = topk_group
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = float(rope_theta)
        self.max_position_embeddings = max_position_embeddings
        self.initializer_range = initializer_range
        self.experts_held = (num_experts if experts_held is None
                             else experts_held)
        self.first_expert = first_expert
        assert len(self.layer_types) == len(self.mlp_layer_types) == n
        assert set(self.layer_types) <= {WINDOW, FULL}
        assert num_attention_heads % num_key_value_heads == 0
        assert 0 <= first_expert \
            and first_expert + self.experts_held <= num_experts

    @property
    def kv_row(self):
        """What one token caches in one layer, once for K and once for V."""
        return self.num_key_value_heads * self.head_dim

    def layers_of(self, kind):
        return [i for i, t in enumerate(self.layer_types) if t == kind]


def rotary_inv_freq(config):
    d = config.head_dim
    return (1.0 / config.rope_theta ** (
        np.arange(0, d, 2, dtype=np.float64) / d)).astype(np.float32)


def rotate(x, positions, config):
    """Rotary embedding of ``x [tokens, heads, head_dim]`` at ``positions
    [tokens]`` over the whole head: value ``c`` pairs with ``c + d/2``
    (the ``rotate_half`` convention); fp32 inside."""
    half = config.head_dim // 2
    angles = positions.astype(jnp.float32)[:, None] * rotary_inv_freq(config)
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    x32 = x.astype(jnp.float32)
    lo, hi = x32[..., :half], x32[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin],
                           axis=-1).astype(x.dtype)


class ExaoneMoeForServing:
    """The served model: its configuration, the shapes of its parameter
    tree, and the serving programs (:meth:`serving`)."""

    def __init__(self, config: ExaoneMoeConfig):
        self.config = config

    def param_shapes(self):
        c = self.config
        h, d = c.hidden_size, c.head_dim
        q_width = c.num_attention_heads * d

        def mlp(width):
            return {"gate_up": {"kernel": (h, 2 * width)},
                    "down": {"kernel": (width, h)}}

        def layer(i):
            out = {"input_norm": {"scale": (h,)},
                   "qkv": {"kernel": (h, q_width + 2 * c.kv_row)},
                   "q_norm": {"scale": (d,)}, "k_norm": {"scale": (d,)},
                   "o": {"kernel": (q_width, h)},
                   "post_norm": {"scale": (h,)}}
            if c.mlp_layer_types[i] == "sparse":
                w = c.moe_intermediate_size
                out["moe"] = {
                    "router": {"kernel": (h, c.num_experts),
                               "bias": (c.num_experts,)},
                    "shared": mlp(c.num_shared_experts * w),
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
        return ExaoneMoeServing(self.config)


class ExaoneMoeServing:
    """K-EXAONE's side of :class:`~deepspeed_tpu.inference.engine.
    InferenceEngine`'s model interface (``inference/model.py``): K and V
    buffers in a ``full`` and a ``window`` cache group, a flash prefill per
    bucket, a paged decode."""

    # tiles of the grouped product (rows, k, n) for a decode step's few
    # rows an expert and for a bucket's hundreds, the flash blocks of
    # prefill (full layers; window layers, whose band is two blocks), and
    # the pages the full layers' decode kernel multiplies at once: what
    # the v5e measured best among those tried (PERF.md section 6, PR 31)
    DECODE_TILING = (128, 6144, 256)
    PREFILL_TILING = (256, 2048, 1024)
    PREFILL_BLOCK = 1024
    WINDOW_PREFILL_BLOCK = 512
    DECODE_PAGES = 4
    # cache group name -> the layer type whose layers it holds
    GROUP_KINDS = {"full": FULL, "window": WINDOW}

    def __init__(self, config):
        self.config = config
        self.num_layers = config.num_hidden_layers
        self.interpret = current_platform() != "tpu"

    def _ring(self, icfg):
        return ring_pages(self.config.sliding_window, icfg.kv_block_size)

    def cache_groups(self, icfg):
        """The ``full`` group (whole-context pages) then the ``window``
        group (a ring a request), each with a K and a V buffer; a kind
        with no layer has no group."""
        c, row = self.config, self.config.kv_row
        groups = []
        for name, kind in self.GROUP_KINDS.items():
            layers = len(c.layers_of(kind))
            if layers:
                groups.append(CacheGroup(
                    name, layers,
                    {f"{name}_k_cache": row, f"{name}_v_cache": row},
                    self._ring(icfg) if kind == WINDOW else None))
        return groups

    def cache_buffers(self, icfg):
        return {name: row for group in self.cache_groups(icfg)
                for name, row in group.buffers.items()}

    def check_tpu_geometry(self, icfg):
        check_gqa_tpu_geometry(self.config.num_key_value_heads,
                               self.config.head_dim, icfg.kv_block_size)

    def prepare_params(self, params):
        return params

    def _places(self, icfg):
        """layer -> (position of its K buffer in ``caches``, position of
        its group's table in ``block_tables``, its index inside the
        group's buffers)."""
        out = {}
        for g, group in enumerate(self.cache_groups(icfg)):
            for n, layer in enumerate(self.config.layers_of(
                    self.GROUP_KINDS[group.name])):
                out[layer] = (2 * g, g, n)
        return out

    # -- pieces shared by the two programs --------------------------------
    def _qkv(self, lp, u, positions, window):
        """``(q [tokens, heads, d], k, v [tokens, kv_heads, d])``: the
        fused projection, each head's q and k normed, and on a window
        layer rotated."""
        c = self.config
        tokens, d = u.shape[0], c.head_dim
        q_width = c.num_attention_heads * d
        qkv = u @ lp["qkv"]["kernel"]
        q = rms_norm(lp["q_norm"], qkv[:, :q_width].reshape(tokens, -1, d),
                     c.rms_norm_eps)
        k = rms_norm(lp["k_norm"], qkv[:, q_width:q_width + c.kv_row]
                     .reshape(tokens, -1, d), c.rms_norm_eps)
        v = qkv[:, q_width + c.kv_row:].reshape(tokens, -1, d)
        if window:
            q, k = rotate(q, positions, c), rotate(k, positions, c)
        return q, k, v

    def _mlp(self, lp, z32, dtype, valid, tiling):
        """The layer's MLP of the normed stream ``z32`` (fp32), computed in
        ``dtype``, and, for a sparse layer, its load counters ``(counts,
        share of the tokens none of whose experts is held here)``; the
        result in fp32."""
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
            nowhere = expert_shard.tokens_without_held_expert(
                ids, valid, c.first_expert, c.experts_held)
        with jax.named_scope("shared_experts"):
            y = y + gated_silu_mlp(moe["shared"], z, jnp.float32)
        return y, (counts, nowhere)

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
        next_tokens, slot) -> (out, caches, next_tokens)``: one request
        padded to the bucket; its first token is also put into lane
        ``slot`` of the next decode's input."""
        c = self.config
        bs, row = icfg.kv_block_size, c.kv_row
        ring = self._ring(icfg)
        n_pages = bucket_len // bs
        assert bucket_len % bs == 0 and n_pages >= ring, (
            f"prefill bucket {bucket_len} holds fewer than the window's "
            f"{ring} pages of {bs}")
        where = self._places(icfg)
        blocks = {False: math.gcd(bucket_len, self.PREFILL_BLOCK),
                  True: math.gcd(bucket_len, self.WINDOW_PREFILL_BLOCK)}

        def layer(lp, x, caches, i, block_tables, positions, ring_at, valid,
                  dtype):
            """Layer ``i`` over the stream; its K and V pages written into
            the list ``caches`` in place."""
            s = x.shape[0]
            window = c.layer_types[i] == WINDOW
            first_page, ring_slots = ring_at
            with jax.named_scope("attention"):
                u = rms_norm(lp["input_norm"], x, c.rms_norm_eps).astype(
                    dtype)
                q, k, v = self._qkv(lp, u, positions, window)
                at, table, index = where[i]
                for j, rows in enumerate((k, v)):
                    rows = rows.reshape(s, row).astype(caches[at + j].dtype)
                    if window:
                        pages = jax.lax.dynamic_slice(
                            rows, (first_page * bs, 0), (ring * bs, row))
                        ids = block_tables[table][ring_slots]
                    else:
                        pages, ids = rows, block_tables[table][:n_pages]
                    caches[at + j] = caches[at + j].at[index, ids].set(
                        pages.reshape(-1, bs, row), unique_indices=True)
                # causality alone hides the bucket's padding from the
                # positions that are tokens
                ctx = flash_attention_forward(
                    q[None], k[None], v[None], causal=True,
                    block_q=blocks[window], block_k=blocks[window],
                    window=c.sliding_window if window else None,
                    interpret=self.interpret,
                    name=("window_prefill_attention" if window
                          else "gqa_prefill_attention"))[0]
                x = x + jnp.matmul(ctx.reshape(s, -1), lp["o"]["kernel"],
                                   preferred_element_type=jnp.float32)
            with jax.named_scope("mlp" if "mlp" in lp else "moe"):
                z = rms_norm(lp["post_norm"], x, c.rms_norm_eps)
                y, _ = self._mlp(lp, z, dtype, valid, self.PREFILL_TILING)
                x = x + y
            return x

        def prefill(params, caches, input_ids, true_len, block_tables,
                    next_tokens, slot):
            caches = list(caches)
            s = input_ids.shape[1]
            positions = jnp.arange(s)
            valid = positions < true_len
            dtype = params["embed"].dtype
            # the ring's pages that hold the last keys before true_len
            # (not before the bucket's end): the page of the last token
            # and the ring - 1 before it, each at its number modulo ring
            first_page = jnp.maximum((true_len - 1) // bs - (ring - 1), 0)
            ring_slots = (first_page + jnp.arange(ring)) % ring
            with jax.named_scope("embed"):
                x = jnp.take(params["embed"], input_ids[0], axis=0).astype(
                    jnp.float32)
            for i in range(self.num_layers):
                lp = params["layers"][f"layer_{i}"]
                with jax.named_scope(f"layer_{i}"):
                    x = layer(lp, x, caches, i, block_tables, positions,
                              (first_page, ring_slots), valid, dtype)
            with jax.named_scope("final_norm"):
                last = jax.lax.dynamic_slice(
                    x, (true_len - 1, 0), (1, c.hidden_size))
            token = self._next_token(params, last)[0]
            with jax.named_scope("sample"):
                next_tokens = next_tokens.at[slot].set(token)
            return {"tokens": token}, tuple(caches), next_tokens

        return prefill

    def build_decode(self, icfg):
        """``(params, caches, block_tables, ctx_lens, tokens) -> (out,
        caches)`` for the fixed ``max_batch_slots``-wide batch.  ``out``
        carries the next tokens and, in the same fetch, the sparse layers'
        load counters (means over the layers)."""
        c = self.config
        bs, row = icfg.kv_block_size, c.kv_row
        n_slots = icfg.max_batch_slots
        ring = self._ring(icfg)
        where = self._places(icfg)

        def layer(lp, x, caches, i, block_tables, ctx_lens, target, valid,
                  dtype):
            """Layer ``i`` over the stream and, for a sparse layer, its
            load counters; the new K and V rows written into the list
            ``caches`` in place."""
            window = c.layer_types[i] == WINDOW
            targets, offsets = target
            with jax.named_scope("attention"):
                u = rms_norm(lp["input_norm"], x, c.rms_norm_eps).astype(
                    dtype)
                q, k, v = self._qkv(lp, u, ctx_lens, window)
                at, table, index = where[i]
                # the append: every slot's new row in one scatter a buffer
                for j, rows in enumerate((k, v)):
                    caches[at + j] = caches[at + j].at[
                        index, targets[table], offsets].set(
                            rows.reshape(n_slots, row).astype(
                                caches[at + j].dtype))
                ctx = paged_decode_attention(
                    q.reshape(n_slots, -1), caches[at], caches[at + 1],
                    block_tables[table], ctx_lens, layer=index,
                    num_heads=c.num_attention_heads,
                    window=c.sliding_window if window else None,
                    pages_per_step=ring if window else self.DECODE_PAGES,
                    interpret=self.interpret)
                x = x + jnp.matmul(ctx, lp["o"]["kernel"],
                                   preferred_element_type=jnp.float32)
            with jax.named_scope("mlp" if "mlp" in lp else "moe"):
                z = rms_norm(lp["post_norm"], x, c.rms_norm_eps)
                y, load = self._mlp(lp, z, dtype, valid, self.DECODE_TILING)
                x = x + y
                if load is not None:
                    load = (
                        *expert_shard.load_counters(load[0]), load[1],
                        expert_shard.pair_passes(
                            load[0], n_slots * c.num_experts_per_tok,
                            lp["moe"]["router"]["kernel"].shape[-1],
                            self.DECODE_TILING[0]))
            return x, load

        def decode(params, caches, block_tables, ctx_lens, tokens):
            caches = list(caches)
            dtype = params["embed"].dtype
            with jax.named_scope("embed"):
                x = jnp.take(params["embed"], tokens, axis=0).astype(
                    jnp.float32)
                page = ctx_lens // bs
                offsets = ctx_lens % bs
                # where the new token's row goes, by cache group: its page
                # of the whole context, or that page's place in the ring
                targets = [
                    jnp.take_along_axis(
                        block_tables[g], (page if group.pages is None
                                          else page % ring)[:, None],
                        axis=1)[:, 0]
                    for g, group in enumerate(self.cache_groups(icfg))]
                # a slot that serves a request decodes at position >= 1:
                # the dead ones (parked at 0) are routed to no expert
                valid = ctx_lens > 0
            counters = []
            for i in range(self.num_layers):
                lp = params["layers"][f"layer_{i}"]
                with jax.named_scope(f"layer_{i}"):
                    x, load = layer(lp, x, caches, i, block_tables, ctx_lens,
                                    (targets, offsets), valid, dtype)
                if load is not None:
                    counters.append(load)
            out = {"tokens": self._next_token(params, x)}
            if counters:
                with jax.named_scope("sample"):
                    share, peak, nowhere, passes = (
                        jnp.mean(jnp.stack(v).astype(jnp.float32))
                        for v in zip(*counters))
                out["moe_local_assignment_share"] = share
                out["moe_expert_load_max_over_mean"] = peak
                out["moe_tokens_without_local_expert"] = nowhere
                out["moe_pair_passes"] = passes
            return out, tuple(caches)

        return decode
