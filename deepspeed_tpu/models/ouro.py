"""Ouro (``model_type`` ``ouro``), for serving: a looped decoder.  The
stack of ``num_hidden_layers`` layers is run ``total_ut_steps`` times over
the SAME weights, the output of one walk the input of the next, so a token
leaves a K and a V row at ``steps x layers`` places while the weights are
``layers`` layers.

Written from the published ``config.json`` of ByteDance/Ouro-2.6B (every
size, ``total_ut_steps``, ``early_exit_threshold``, ``rope_theta``,
``rms_norm_eps``) and three conventions it has no key for, ASSUMED and said
so in the benchmark's configuration file: the sandwich norms, the final
norm between steps, and the exit gate's form are the Ouro report's
("Scaling Latent Reasoning via Looped Language Models", ByteDance Seed,
October 2025) and its released modelling code's.  With ``N(.)`` an RMSNorm
(fp32, its own ``[hidden]`` scale) and ``x`` the fp32 residual stream:

- ``x <- E[token]``; for loop step ``r``, for layer ``i``:
  ``x <- x + N_i2(Attn_i(N_i1(x)))``, then ``x <- x + N_i4(MLP_i(N_i3(x)))``
  with ``MLP(z) = W_down(silu(W_gate z) * W_up z)``: a norm before AND
  after each sublayer.  After the last layer ``x <- N_f(x)``, the same
  final norm every step: its output is the next step's input and step
  ``r``'s state ``h^r``.
- ``Attn_i(u)``: ``[q ; k ; v] = u W_QKV`` (one fused kernel, heads of
  ``head_dim`` each, as many KV heads as query heads, no bias); q and k
  rotated by position over the whole head (``models/exaone_moe.py::rotate``:
  theta ``rope_theta``, pairs ``(c, c + d/2)``); causal softmax of ``q.k /
  sqrt(d)`` in fp32; ``W_O``.
- what is CACHED: K and V of a token at (step ``r``, layer ``i``), after
  rotation, in PLANE ``r * layers + i`` of the two paged buffers
  (``inference/kv_cache.py``); a query at step ``r`` reads that plane only.
  A cache plane is not a layer here: ``cache_groups`` names ``steps x
  layers`` planes, ``num_layers`` stays the weights' count.
- the exit gate: ``lambda_r = sigmoid(h^r . w_g + b_g)``; the mass that
  leaves at step ``r`` is ``p_r = lambda_r * prod_{s<r}(1 - lambda_s)``,
  what is left after the last step falls on it.  A token exits at the
  first step where the cumulated mass reaches ``early_exit_threshold``;
  the published threshold, 1, is reached at the last step only, so every
  token runs every step and the logits are ``h^{last} W_head``.  The decode
  program computes ``p`` and reports it (``exit_step_mean``,
  ``exit_mass_step_<r>``); it decides nothing.  Slots that leave the loop
  early need a step whose cost differs by slot, which the engine does not
  have (ROADMAP, mechanisms).
- precision as the other decoders': the stream, the norms, the softmax and
  the gate in fp32, every other product in the weights' dtype with an fp32
  accumulator, the cache in the serving dtype.

The programs trace the ``layers``-layer body ONCE and run it under a
``lax.fori_loop`` over the loop steps (a ``named_scope`` ``ut_loop``): the
weights are loop-invariant operands, the two cache buffers are carried and
updated in place, and the plane ``r * layers + i`` is a traced scalar —
the ``layer`` operand of ``paged_decode_attention`` and the leading index of
prefill's page write.  Unrolled it would be ``steps x layers`` bodies a
program, decode and every prefill bucket.

Parameter tree: ``embed``, ``layers/layer_<i>/{norm_attn_in, qkv,
norm_attn_out, o, norm_mlp_in, gate_up, down, norm_mlp_out}``,
``final_norm``, ``exit_gate/{kernel [hidden, 1], bias [1]}``, ``lm_head``;
every matrix a ``kernel [in, out]``, every norm a ``scale``.
"""

import math

import jax
import jax.numpy as jnp

from ..inference.kv_cache import CacheGroup
from ..ops.transformer.flash_attention import flash_attention_forward
from ..ops.transformer.paged_attention import (check_tpu_geometry,
                                               paged_decode_attention)
from ..parallel.mesh import current_platform
from .exaone_moe import rotate
from .layers import gated_silu_mlp, rms_norm


class OuroConfig:
    """The published ``config.json`` keys that shape the model."""

    def __init__(self, vocab_size=49152, hidden_size=2048,
                 num_hidden_layers=48, num_attention_heads=16,
                 num_key_value_heads=16, head_dim=128,
                 intermediate_size=5632, total_ut_steps=4,
                 early_exit_threshold=1.0, rms_norm_eps=1e-6,
                 rope_theta=1e6, max_position_embeddings=65536):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.intermediate_size = intermediate_size
        self.total_ut_steps = total_ut_steps
        self.early_exit_threshold = float(early_exit_threshold)
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = float(rope_theta)
        self.max_position_embeddings = max_position_embeddings
        assert num_key_value_heads == num_attention_heads, (
            "the published Ouro models keep a KV head a query head")
        assert self.early_exit_threshold >= 1.0, (
            "a threshold under 1 lets tokens leave the loop early: the "
            "engine has no step whose cost differs by slot")

    @property
    def kv_row(self):
        """What one token caches in one plane, once for K and once for V."""
        return self.num_key_value_heads * self.head_dim

    @property
    def cache_planes(self):
        return self.total_ut_steps * self.num_hidden_layers


def exit_masses(gates):
    """``gates [steps, ...]`` (each step's ``lambda``) -> the mass that
    leaves at each step, same shape: ``lambda_r`` of what is left, the rest
    on the last step; sums to 1 over the steps."""
    stay = jnp.cumprod(1.0 - gates, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    return jnp.concatenate([(gates * before)[:-1], before[-1:]])


class OuroForServing:
    """The served model: its configuration, the shapes of its parameter
    tree, and the serving programs (:meth:`serving`)."""

    def __init__(self, config: OuroConfig):
        self.config = config

    def param_shapes(self):
        c = self.config
        h, width = c.hidden_size, c.intermediate_size
        q_width = c.num_attention_heads * c.head_dim
        layer = {"norm_attn_in": {"scale": (h,)},
                 "qkv": {"kernel": (h, q_width + 2 * c.kv_row)},
                 "norm_attn_out": {"scale": (h,)},
                 "o": {"kernel": (q_width, h)},
                 "norm_mlp_in": {"scale": (h,)},
                 "gate_up": {"kernel": (h, 2 * width)},
                 "down": {"kernel": (width, h)},
                 "norm_mlp_out": {"scale": (h,)}}
        return {"embed": (c.vocab_size, h),
                "layers": {f"layer_{i}": dict(layer)
                           for i in range(c.num_hidden_layers)},
                "final_norm": {"scale": (h,)},
                "exit_gate": {"kernel": (h, 1), "bias": (1,)},
                "lm_head": {"kernel": (h, c.vocab_size)}}

    def serving(self):
        return OuroServing(self.config)


class OuroServing:
    """Ouro's side of :class:`~deepspeed_tpu.inference.engine.
    InferenceEngine`'s model interface (``inference/model.py``): a K and a
    V buffer of ``steps x layers`` planes in one cache group, a flash
    prefill per bucket, a paged decode, both looped over the steps."""

    # the largest flash block of prefill: a bucket of this many positions
    # or fewer is one block
    PREFILL_BLOCK = 256

    def __init__(self, config):
        self.config = config
        self.num_layers = config.num_hidden_layers
        self.interpret = current_platform() != "tpu"

    def cache_buffers(self, icfg):
        return {"k_cache": self.config.kv_row, "v_cache": self.config.kv_row}

    def cache_groups(self, icfg):
        return [CacheGroup("kv", self.config.cache_planes,
                           self.cache_buffers(icfg))]

    def check_tpu_geometry(self, icfg):
        # the row is as wide as the query: the block-diagonal kernel
        check_tpu_geometry(self.config.kv_row, icfg.kv_block_size)

    def prepare_params(self, params):
        return params

    # -- pieces shared by the two programs --------------------------------
    def _layer(self, lp, x, caches, positions, plane, attend):
        """One layer over the fp32 stream ``x [tokens, hidden]`` at cache
        plane ``plane``: ``(x, caches)``.  ``attend(plane, q, k, v, caches)
        -> (context [tokens, hidden], caches)`` caches the rotated ``k``
        and ``v [tokens, heads, d]`` and attends, the program's own way."""
        c = self.config
        eps, dtype = c.rms_norm_eps, lp["qkv"]["kernel"].dtype
        with jax.named_scope("attention"):
            u = rms_norm(lp["norm_attn_in"], x, eps).astype(dtype)
            qkv = (u @ lp["qkv"]["kernel"]).reshape(
                x.shape[0], 3, -1, c.head_dim)
            ctx, caches = attend(plane, rotate(qkv[:, 0], positions, c),
                                 rotate(qkv[:, 1], positions, c), qkv[:, 2],
                                 caches)
            a = jnp.matmul(ctx, lp["o"]["kernel"],
                           preferred_element_type=jnp.float32)
            x = x + rms_norm(lp["norm_attn_out"], a, eps)
        with jax.named_scope("mlp"):
            z = rms_norm(lp["norm_mlp_in"], x, eps).astype(dtype)
            x = x + rms_norm(lp["norm_mlp_out"],
                             gated_silu_mlp(lp, z, jnp.float32), eps)
        return x, caches

    def _ut_loop(self, params, x, caches, positions, attend):
        """The stream through every layer ``total_ut_steps`` times, the
        final norm after each walk: ``(h of the last step, caches, every
        step's gate [steps, tokens])``.  ``attend(plane, q, k, v, caches)
        -> (context, caches)``."""
        c = self.config
        layers = [params["layers"][f"layer_{i}"]
                  for i in range(self.num_layers)]
        gate = params["exit_gate"]

        def ut_step(r, carry):
            x, caches, gates = carry
            for i, lp in enumerate(layers):
                with jax.named_scope(f"layer_{i}"):
                    x, caches = self._layer(lp, x, caches, positions,
                                            r * self.num_layers + i, attend)
            with jax.named_scope("final_norm"):
                x = rms_norm(params["final_norm"], x, c.rms_norm_eps)
            with jax.named_scope("exit_gate"):
                # a sum of fp32 products, not a matmul: the MXU would
                # round the stream to bf16
                lam = jax.nn.sigmoid(
                    jnp.sum(x * gate["kernel"].astype(jnp.float32)[:, 0],
                            axis=-1) + gate["bias"].astype(jnp.float32))
            return x, caches, gates.at[r].set(lam)

        with jax.named_scope("ut_loop"):
            return jax.lax.fori_loop(
                0, c.total_ut_steps, ut_step,
                (x, tuple(caches),
                 jnp.zeros((c.total_ut_steps, x.shape[0]), jnp.float32)))

    def _next_token(self, params, h):
        head = params["lm_head"]["kernel"]
        with jax.named_scope("lm_head"):
            logits = jnp.matmul(h.astype(head.dtype), head,
                                preferred_element_type=jnp.float32)
        with jax.named_scope("sample"):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    # -- the two programs --------------------------------------------------
    def build_prefill(self, icfg, bucket_len):
        """``(params, caches, input_ids[1, S], true_len, block_tables,
        next_tokens, slot) -> (out, caches, next_tokens)``: one request
        padded to the bucket, its pages written at every plane; its first
        token is also put into lane ``slot`` of the next decode's input."""
        c = self.config
        bs, row = icfg.kv_block_size, c.kv_row
        n_pages = bucket_len // bs
        assert bucket_len % bs == 0
        block = (bucket_len if bucket_len <= self.PREFILL_BLOCK
                 else math.gcd(bucket_len, self.PREFILL_BLOCK))

        def prefill(params, caches, input_ids, true_len, block_tables,
                    next_tokens, slot):
            s = input_ids.shape[1]
            pages = block_tables[0][:n_pages]

            def attend(plane, q, k, v, caches):
                caches = tuple(
                    cache.at[plane, pages].set(
                        rows.reshape(n_pages, bs, row).astype(cache.dtype),
                        unique_indices=True)
                    for cache, rows in zip(caches, (k, v)))
                # causality alone hides the bucket's padding from the
                # positions that are tokens
                ctx = flash_attention_forward(
                    q[None], k[None], v[None], causal=True, block_q=block,
                    block_k=block, interpret=self.interpret,
                    name="loop_prefill_attention")[0]
                return ctx.reshape(s, -1), caches

            with jax.named_scope("embed"):
                x = jnp.take(params["embed"], input_ids[0], axis=0).astype(
                    jnp.float32)
            h, caches, _ = self._ut_loop(params, x, caches, jnp.arange(s),
                                         attend)
            with jax.named_scope("final_norm"):
                last = jax.lax.dynamic_slice(
                    h, (true_len - 1, 0), (1, c.hidden_size))
            token = self._next_token(params, last)[0]
            with jax.named_scope("sample"):
                next_tokens = next_tokens.at[slot].set(token)
            return {"tokens": token}, caches, next_tokens

        return prefill

    def build_decode(self, icfg):
        """``(params, caches, block_tables, ctx_lens, tokens) -> (out,
        caches)`` for the fixed ``max_batch_slots``-wide batch.  ``out``
        carries the next tokens and, in the same fetch, the loop's
        counters: ``ut_steps``, ``cache_planes``, and over the slots that
        serve a request the mean exit mass of every step and the mean step
        of exit (``sum_r (r + 1) p_r``)."""
        c = self.config
        bs, row = icfg.kv_block_size, c.kv_row
        n_slots = icfg.max_batch_slots

        def decode(params, caches, block_tables, ctx_lens, tokens):
            table = block_tables[0]
            targets = jnp.take_along_axis(
                table, (ctx_lens // bs)[:, None], axis=1)[:, 0]
            offsets = ctx_lens % bs

            def attend(plane, q, k, v, caches):
                # the append: every slot's new row in one scatter a buffer
                k_cache, v_cache = (
                    cache.at[plane, targets, offsets].set(
                        rows.reshape(n_slots, row).astype(cache.dtype))
                    for cache, rows in zip(caches, (k, v)))
                ctx = paged_decode_attention(
                    q.reshape(n_slots, -1), k_cache, v_cache, table,
                    ctx_lens, layer=plane, num_heads=c.num_attention_heads,
                    interpret=self.interpret)
                return ctx, (k_cache, v_cache)

            with jax.named_scope("embed"):
                x = jnp.take(params["embed"], tokens, axis=0).astype(
                    jnp.float32)
            h, caches, gates = self._ut_loop(params, x, caches, ctx_lens,
                                             attend)
            with jax.named_scope("sample"):
                # a slot that serves a request decodes at position >= 1
                live = (ctx_lens > 0).astype(jnp.float32)
                mass = (exit_masses(gates) * live).sum(axis=1) \
                    / jnp.maximum(live.sum(), 1.0)
            out = {"tokens": self._next_token(params, h)}
            with jax.named_scope("sample"):
                out.update(
                    ut_steps=jnp.float32(c.total_ut_steps),
                    cache_planes=jnp.float32(c.cache_planes),
                    exit_step_mean=jnp.sum(
                        mass * jnp.arange(1.0, c.total_ut_steps + 1.0)))
                for r in range(c.total_ut_steps):
                    out[f"exit_mass_step_{r + 1}"] = mass[r]
            return out, caches

        return decode
