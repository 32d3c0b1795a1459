"""The served-model interface, and GPT-2's side of it.

:class:`~deepspeed_tpu.inference.engine.InferenceEngine` owns scheduling,
blocks, donation and the ``ds:`` spans; it does not know a layer.  A model
it can serve has a ``serving()`` method that returns an object with:

- ``num_layers``;
- ``cache_buffers(icfg) -> {name: row width}``: the paged buffers a layer
  keeps, each ``[layers, blocks, kv_block_size, row width]`` in the
  serving dtype, allocated once by the engine and DONATED to every program
  (GPT-2: ``k_cache`` and ``v_cache`` rows of ``hidden``; DeepSeek-V2: one
  ``latent_cache`` row of 512 + 64, ``models/deepseek_v2.py``);
- ``cache_groups(icfg) -> [CacheGroup]`` (``inference/kv_cache.py``): the
  same buffers, in the same order, by the span of a request their layers
  cache — each group's layer count, its buffers, and ``pages``: None for
  the whole context, or the fixed number of blocks a request holds
  whatever its length (a sliding-window layer's ring).  The engine keeps
  a pool and a block table a group.  GPT-2 and DeepSeek-V2 name one group;
  K-EXAONE (``models/exaone_moe.py``) a ``full`` and a ``window`` one.  A
  group's ``layers`` counts the buffers' PLANES, which need not be the
  model's layers: Ouro (``models/ouro.py``) runs its layers
  ``total_ut_steps`` times over shared weights and names ``steps x
  layers`` planes, with ``num_layers`` the weights' count.  A group need
  not hold keys and values, nor be in the serving dtype: MiniCPM-SALA
  (``models/minicpm_sala.py``) names ``kv`` (its sparse layers' pages),
  ``ckeys`` (their compressed keys, a fixed grant of pages a request) and
  ``state`` — ``pages=1``, ``dtype="float32"``: a linear-attention layer's
  recurrent state, ONE block a request, which ``prefill`` writes whole
  (the state after the prompt's last true position), ``decode`` reads and
  rewrites in place every step, a dead slot parks on the null block, and
  a requeued request's new prefill overwrites in a fresh grant;
- ``check_tpu_geometry(icfg)``: raise for a cache its decode kernel cannot
  tile on a TPU (called at construction there, never a second path);
- ``prepare_params(params) -> params``: the tree its programs take, made
  of the caller's ONCE, when the engine takes the weights (after the cast
  to the serving dtype; the result is ``engine.params``).  The rule it
  stands for: nothing that depends on the weights alone is computed
  inside ``decode`` or ``prefill`` — a split, a transpose or a fold of a
  weight written there is done again in every step.  A model with
  nothing to prepare returns the tree it was given, the same object
  (GPT-2, K-EXAONE); DeepSeek-V2 replaces each layer's ``kv_b`` by
  ``w_uk`` and ``w_uv`` in the layouts the absorbed decode's two products
  take.  Leaves it replaces it drops, so the served tree holds no weight
  twice;
- ``build_prefill(icfg, bucket) -> prefill(params, caches, input_ids[1, S],
  true_len, block_tables, next_tokens, slot) -> (out, caches,
  next_tokens)``, one program a bucket, and ``build_decode(icfg) ->
  decode(params, caches, block_tables, ctx_lens, tokens) -> (out,
  caches)``, one program for the serve; ``caches`` is the tuple of
  buffers in ``cache_buffers`` order, ``block_tables`` a tuple with one
  table a cache group (prefill: the request's row ``[width]``; decode:
  ``[slots, width]``), ``out`` a dict the engine fetches
  whole: ``"tokens"`` (prefill: the first token; decode: ``[slots]``) and
  any scalar counters the model reports (published as ``serving/<key>``
  gauges on the print cadence).  The functions are NAMED ``prefill`` and
  ``decode``: a device trace names a compiled program after its function
  (the engine names a bucket's program ``prefill_<bucket>``, so that a
  trace tells the buckets apart).  Inside them the work stands under the
  program's scope words (``telemetry/scopes.py``): ``embed``,
  ``layer_<i>`` ⊃ ``attention`` (⊃ ``sparse_select``, ``sparse_attention``
  on a block-selected layer, ⊃ ``lightning`` ⊃ ``state_update`` on a
  linear-attention one), ``mlp`` | ``moe``, then ``final_norm``,
  ``lm_head``, ``sample``.

The engine keeps one program in flight, so a program's tokens never
travel device -> host -> device: ``decode``'s ``tokens`` argument IS the
last decode's ``out["tokens"]`` array, and a ``prefill`` puts its first
token into lane ``slot`` of that array itself (``next_tokens``, returned
beside the caches) — lanes of free or finished slots carry whatever was
decoded there last and are parked by ``ctx_lens`` 0 over a null table
row.  ``block_tables`` is a device array the engine sends again only when
a slot's grant changes; ``ctx_lens`` comes from the host's own counts
each iteration.  The host reads ``out`` one enqueue late
(``InferenceEngine.step`` has the contract: a decode's tokens one
``step()`` after its enqueue, the token decoded past an EOS dropped).

The rest of this file is GPT-2: two program families, both closed over the
static model/cache geometry so every shape in the traced graph is fixed:

- ``prefill``: one request, padded to a declared bucket length — full
  causal self-attention over the padded prompt, per-layer K/V written
  into the request's cache blocks as whole pages (one scatter a layer
  and cache), next token read at the true last position.  One compiled
  program per bucket.
- ``decode``: the fixed-width continuous batch — one token per slot.
  Per layer, every slot's new K row (and V row) is appended through the
  block table in ONE scatter into the DONATED cache buffer, and the
  paged-attention kernel
  (:mod:`~deepspeed_tpu.ops.transformer.paged_attention`) reads, for
  each slot, only the blocks that hold live context — by the ids in the
  table, straight out of that buffer.  Nothing in the program has a
  ``max_seq_len`` dimension: HBM traffic follows the tokens cached.
  Exactly one compiled program for the whole serve, regardless of batch
  occupancy.  On a non-TPU platform the same kernel runs through
  Pallas' interpreter.

GPT-2's cache rows are ``heads * head_dim`` wide (:mod:`.kv_cache`): a
token's K or V is one row, exactly what the fused-QKV projection emits.

The math mirrors :class:`~deepspeed_tpu.models.layers.TransformerLayer`
(pre-LN path) and :meth:`~deepspeed_tpu.models.gpt2.GPT2LMHeadTPU.hidden`
operation for operation — fp32 layernorm, fused-QKV dense, fp32-softmax
attention, tanh-GELU MLP, tied LM head — so greedy decode through the
cache is token-identical to the naive full-forward reference (the e2e
parity test pins this).
"""

import jax
import jax.numpy as jnp
import numpy as np

from ..models.layers import dense, gelu, layer_norm
from ..ops.transformer.attention import dot_product_attention
from ..ops.transformer.paged_attention import (check_tpu_geometry,
                                               paged_decode_attention)
from ..parallel.mesh import current_platform
from .kv_cache import CacheGroup


def _write_prefill_blocks(cache, layer_idx, seq_kv, block_table, block_size):
    """Write one layer's ``[S, hidden]`` K-or-V rows into ``cache``
    through ``block_table`` as whole pages (S is a bucket, a multiple of
    the block size): ONE scatter over the bucket's blocks, which the
    allocator hands out distinct.  Returns the updated cache (aliased
    via donation)."""
    n = seq_kv.shape[0] // block_size
    pages = seq_kv.reshape(n, block_size, seq_kv.shape[-1])
    return cache.at[layer_idx, block_table[:n]].set(
        pages.astype(cache.dtype), unique_indices=True)


def build_prefill(model_config, icfg, bucket_len):
    """The bucket's prefill callable
    ``(params, k_cache, v_cache, input_ids[1, S], true_len, block_table)
    -> (next_token, k_cache, v_cache)`` — jit it with
    ``donate_argnums=(1, 2)`` so the cache writes alias in place."""
    c = model_config
    bs = icfg.kv_block_size
    heads, head_dim = c.num_heads, c.hidden_size // c.num_heads
    assert bucket_len % bs == 0

    def prefill(params, k_cache, v_cache, input_ids, true_len, block_table):
        s = input_ids.shape[1]
        with jax.named_scope("embed"):
            x = jnp.take(params["wte"], input_ids, axis=0) \
                + params["wpe"][None, :s]
        # pad keys masked out of every softmax row; the causal structure
        # already hides them from positions < true_len, so this only
        # pins the (discarded) pad rows
        visible = (jnp.arange(s)[None, :] < true_len).astype(jnp.float32)
        for i in range(c.num_layers):
            lp = params["blocks"][f"layer_{i}"]
            with jax.named_scope(f"layer_{i}"):
                with jax.named_scope("attention"):
                    y = layer_norm(lp["ln_attn"], x, c.layer_norm_eps)
                    qkv = dense(lp["qkv"], y).reshape(1, s, 3, c.hidden_size)
                    k_cache = _write_prefill_blocks(
                        k_cache, i, qkv[0, :, 1], block_table, bs)
                    v_cache = _write_prefill_blocks(
                        v_cache, i, qkv[0, :, 2], block_table, bs)
                    q, k, v = (qkv[:, :, j].reshape(1, s, heads, head_dim)
                               for j in range(3))
                    ctx = dot_product_attention(
                        q, k, v, key_padding_mask=visible, causal=True)
                    x = x + dense(lp["attn_out"],
                                  ctx.reshape(1, s, c.hidden_size))
                with jax.named_scope("mlp"):
                    z = layer_norm(lp["ln_mlp"], x, c.layer_norm_eps)
                    x = x + dense(lp["fc2"], gelu(dense(lp["fc1"], z)))
        with jax.named_scope("final_norm"):
            x = layer_norm(params["ln_f"], x, c.layer_norm_eps)
            last = jax.lax.dynamic_slice(
                x, (0, true_len - 1, 0), (1, 1, c.hidden_size))
        with jax.named_scope("lm_head"):
            logits = last[0, 0] @ params["wte"].T.astype(last.dtype)
        with jax.named_scope("sample"):
            token = jnp.argmax(logits).astype(jnp.int32)
        return token, k_cache, v_cache

    return prefill


def build_decode(model_config, icfg):
    """The decode callable ``(params, k_cache, v_cache, block_tables,
    ctx_lens, tokens) -> (next_tokens, k_cache, v_cache)`` for the fixed
    ``max_batch_slots``-wide continuous batch — jit it with
    ``donate_argnums=(1, 2)``.

    ``ctx_lens[b]`` is the context length BEFORE this token, i.e. the
    new token's position; inactive slots park at position 0 of the null
    block and their output is discarded on the host.  Two dead slots
    write the same scratch row, so the append scatter promises no
    unique indices."""
    c = model_config
    bs = icfg.kv_block_size
    n_slots = icfg.max_batch_slots
    heads = c.num_heads
    interpret = current_platform() != "tpu"

    def decode(params, k_cache, v_cache, block_tables, ctx_lens, tokens):
        with jax.named_scope("embed"):
            x = jnp.take(params["wte"], tokens, axis=0) \
                + jnp.take(params["wpe"], ctx_lens, axis=0)       # [B, h]
            block_ids = jnp.take_along_axis(
                block_tables, (ctx_lens // bs)[:, None], axis=1)[:, 0]
            offsets = ctx_lens % bs
        for i in range(c.num_layers):
            lp = params["blocks"][f"layer_{i}"]
            with jax.named_scope(f"layer_{i}"):
                with jax.named_scope("attention"):
                    y = layer_norm(lp["ln_attn"], x, c.layer_norm_eps)
                    qkv = dense(lp["qkv"], y).reshape(n_slots, 3,
                                                      c.hidden_size)
                    # the append: every slot's new row in one scatter a
                    # cache
                    k_cache = k_cache.at[i, block_ids, offsets].set(
                        qkv[:, 1].astype(k_cache.dtype))
                    v_cache = v_cache.at[i, block_ids, offsets].set(
                        qkv[:, 2].astype(v_cache.dtype))
                    # each slot's context now includes its own new token
                    # at position ctx_len; the kernel reads the live pages
                    # only
                    ctx = paged_decode_attention(
                        qkv[:, 0], k_cache, v_cache, block_tables, ctx_lens,
                        layer=i, num_heads=heads, interpret=interpret)
                    x = x + dense(lp["attn_out"], ctx)
                with jax.named_scope("mlp"):
                    z = layer_norm(lp["ln_mlp"], x, c.layer_norm_eps)
                    x = x + dense(lp["fc2"], gelu(dense(lp["fc1"], z)))
        with jax.named_scope("final_norm"):
            x = layer_norm(params["ln_f"], x, c.layer_norm_eps)
        with jax.named_scope("lm_head"):
            logits = x @ params["wte"].T.astype(x.dtype)
        with jax.named_scope("sample"):
            next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tokens, k_cache, v_cache

    return decode


class GPT2Serving:
    """GPT-2's side of the served-model interface (the module docstring):
    K and V buffers with rows of ``hidden``, :func:`build_prefill` and
    :func:`build_decode` under the interface's calling convention — the
    same operations in the same order, so the compiled programs are the
    ones the two functions always gave."""

    def __init__(self, model_config):
        self.config = model_config
        self.num_layers = model_config.num_layers

    def cache_buffers(self, icfg):
        return {"k_cache": self.config.hidden_size,
                "v_cache": self.config.hidden_size}

    def cache_groups(self, icfg):
        return [CacheGroup("kv", self.num_layers, self.cache_buffers(icfg))]

    def check_tpu_geometry(self, icfg):
        check_tpu_geometry(self.config.hidden_size, icfg.kv_block_size)

    def prepare_params(self, params):
        return params

    def build_prefill(self, icfg, bucket_len):
        inner = build_prefill(self.config, icfg, bucket_len)

        def prefill(params, caches, input_ids, true_len, block_tables,
                    next_tokens, slot):
            token, k_cache, v_cache = inner(params, *caches, input_ids,
                                            true_len, *block_tables)
            with jax.named_scope("sample"):
                next_tokens = next_tokens.at[slot].set(token)
            return {"tokens": token}, (k_cache, v_cache), next_tokens

        return prefill

    def build_decode(self, icfg):
        inner = build_decode(self.config, icfg)

        def decode(params, caches, block_tables, ctx_lens, tokens):
            next_tokens, k_cache, v_cache = inner(
                params, *caches, *block_tables, ctx_lens, tokens)
            return {"tokens": next_tokens}, (k_cache, v_cache)

        return decode


def reference_generate(model, params, prompt, max_new_tokens,
                       eos_token_id=-1):
    """The naive one-request-at-a-time reference: a full forward over the
    whole growing context per token, greedy argmax — no cache, no paging,
    no buckets, no batch.  O(n^2) recompute; it exists to be the parity
    oracle the cached engine must match token for token, not to be fast.

    The context is right-padded to the request's final length, which a
    causal model cannot see from the positions before it, so that ONE
    traced program serves every step of the request (a forward at each
    new length retraces every op: eight minutes for two requests at
    GPT-2-large width on the chip)."""
    n = len(prompt)
    # one fixed-length buffer: tokens fill it from the left as they come
    padded = np.zeros((1, n + max_new_tokens), np.int32)
    padded[0, :n] = [int(t) for t in prompt]

    @jax.jit
    def next_token(params, padded_ids, n):
        logits = model.logits(params, padded_ids)
        return jnp.argmax(jax.lax.dynamic_index_in_dim(
            logits[0], n - 1, axis=0, keepdims=False))

    out = []
    for _ in range(max_new_tokens):
        nxt = int(next_token(params, padded, n))
        out.append(nxt)
        padded[0, n] = nxt
        n += 1
        if eos_token_id >= 0 and nxt == eos_token_id:
            break
    return out
