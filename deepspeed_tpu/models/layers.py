"""Transformer building blocks (pure-JAX, MXU-first).

These are the framework's reference transformer layers — the role the fused
CUDA ``DeepSpeedTransformerLayer`` plays in the reference
(``deepspeed/ops/transformer/transformer.py:470``; kernels
``csrc/transformer/ds_transformer_cuda.cpp:145-1040``).  Design notes:

- Weights are plain pytrees; layouts keep matmuls large and bf16-friendly
  (QKV fused into one ``(hidden, 3·hidden)`` GEMM like the reference's qkv
  concat, ``module_inject/replace_module.py``).
- Tensor parallelism is declared, not coded: ``partition_specs`` returns
  Megatron-style PartitionSpecs (column-parallel QKV/FC1, row-parallel
  out/FC2) and XLA GSPMD inserts the all-reduces.
- Attention dispatches to the fused Pallas flash-attention kernel on TPU
  (``ops/transformer/attention.py``) and falls back to a jnp reference
  implementation elsewhere.
- ``pre_layer_norm``, dropout sites, and activation-checkpoint knobs mirror
  the reference config (``DeepSpeedTransformerConfig``,
  ``ops/transformer/transformer.py:39-154``).
"""

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.op_common import random_keep
from ..ops.transformer.attention import (dot_product_attention,
                                         key_padding_to_additive,
                                         self_attention,
                                         shard_kernel_over_mesh)
from ..parallel.mesh import current_platform
from ..utils.logging import logger


def _dense_init(rng, in_dim, out_dim, initializer_range=0.02):
    return {
        "kernel": jax.random.normal(rng, (in_dim, out_dim), jnp.float32)
        * initializer_range,
        "bias": jnp.zeros((out_dim,), jnp.float32),
    }


def dense(params, x):
    return x @ params["kernel"].astype(x.dtype) + params["bias"].astype(x.dtype)


def layer_norm(params, x, eps=1e-12):
    """LayerNorm in fp32 accumulations (bf16-safe), fused by XLA."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"] + params["bias"]).astype(x.dtype)


def rms_norm(params, x, eps=1e-6):
    """RMSNorm (no mean, no bias) in fp32, cast back to ``x``'s dtype."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1,
                                     keepdims=True) + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


def gated_silu_mlp(params, x, out_dtype=None):
    """``down(silu(gate x) * up x)`` with gate and up fused in one
    ``gate_up`` kernel ``[in, 2 * width]`` (gate first), no biases; the
    last product's accumulator comes back as ``out_dtype`` (``x``'s by
    default)."""
    width = params["down"]["kernel"].shape[0]
    gu = x @ params["gate_up"]["kernel"].astype(x.dtype)
    return jnp.matmul(gated_silu(gu, width),
                      params["down"]["kernel"].astype(x.dtype),
                      preferred_element_type=out_dtype or x.dtype)


def gated_silu(gate_up, width):
    """``silu(gate) * up`` of a fused ``[..., 2 * width]`` product, the
    activation in fp32."""
    gate = gate_up[..., :width].astype(jnp.float32)
    return (jax.nn.silu(gate) * gate_up[..., width:].astype(
        jnp.float32)).astype(gate_up.dtype)


def gelu(x):
    # tanh approximation: matches the reference kernel (gelu_kernels.cu) and
    # keeps everything elementwise-fusable.
    x32 = x.astype(jnp.float32)
    y = 0.5 * x32 * (1.0 + jnp.tanh(0.7978845608028654 * (x32 + 0.044715 * x32 ** 3)))
    return y.astype(x.dtype)


def dropout(rng, x, rate, deterministic):
    if deterministic or rate < 1.0 / 512.0 or rng is None:
        return x
    keep, scale = random_keep(rng, x.shape, rate)
    return jnp.where(keep, x * jnp.asarray(scale, x.dtype), jnp.zeros_like(x))


class TransformerLayer:
    """One encoder/decoder layer.

    Config mirrors ``DeepSpeedTransformerConfig`` (reference
    ``ops/transformer/transformer.py:39-154``): ``pre_layer_norm``,
    ``attn_dropout_ratio``, ``hidden_dropout_ratio``, ``initializer_range``.
    ``causal`` turns it into a GPT block.
    """

    def __init__(self, hidden_size, heads, intermediate_size=None, causal=False,
                 attn_dropout_ratio=0.1, hidden_dropout_ratio=0.1,
                 pre_layer_norm=False, initializer_range=0.02, layer_norm_eps=1e-12,
                 attn_impl="auto", sparsity_config=None,
                 gelu_checkpoint=False, attn_dropout_checkpoint=False,
                 normalize_invertible=False, stochastic_mode=False):
        assert hidden_size % heads == 0
        self.hidden_size = hidden_size
        self.heads = heads
        self.head_dim = hidden_size // heads
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.causal = causal
        self.attn_dropout_ratio = attn_dropout_ratio
        self.hidden_dropout_ratio = hidden_dropout_ratio
        self.pre_layer_norm = pre_layer_norm
        self.initializer_range = initializer_range
        self.layer_norm_eps = layer_norm_eps
        # memory knobs mirroring DeepSpeedTransformerConfig (reference
        # ops/transformer/transformer.py:109-137): each drops a class of
        # saved activations and recomputes it in backward — here expressed
        # as jax.checkpoint around the corresponding sub-block (the
        # reference frees the buffer and replays the kernel)
        self.gelu_checkpoint = gelu_checkpoint
        self.attn_dropout_checkpoint = attn_dropout_checkpoint
        self.normalize_invertible = normalize_invertible
        # Reference knob parity: stochastic_mode trades run-to-run
        # determinism for ~2% speed via non-deterministic CUDA atomics
        # (ops/transformer/transformer.py:93-107,
        # op_builder/stochastic_transformer.py).  XLA:TPU execution is
        # deterministic by construction — there is no atomics-ordering
        # speed to buy back — so the knob is accepted for config
        # compatibility and logged as a no-op.
        self.stochastic_mode = stochastic_mode
        if stochastic_mode:
            from ..utils.logging import logger

            logger.warning(
                "stochastic_mode=True accepted for reference config parity "
                "but is a no-op on TPU: XLA execution is deterministic and "
                "there is no non-deterministic-atomics fast path to enable")
        # attention core selection:
        #   'auto'   — flash kernel on TPU / jnp reference elsewhere
        #   'ring'   — sequence-parallel ring attention over the 'seq' mesh
        #              axis (long-context; SURVEY §5.7 upgrade)
        #   'sparse' — block-sparse attention driven by sparsity_config
        #              (reference ops/sparse_attention)
        assert attn_impl in ("auto", "ring", "sparse")
        self.attn_impl = attn_impl
        self.sparsity_config = sparsity_config
        self._layout_cache = {}  # seq_len -> layout (stable across traces)
        if attn_impl == "sparse":
            assert sparsity_config is not None, (
                "attn_impl='sparse' requires a SparsityConfig")

    def _sparse_layout(self, seq_len):
        """Layout cached per sequence length: randomized configs (BigBird,
        Variable) must yield the SAME pattern in every traced program
        (train/eval/retrace), not a fresh sample per trace."""
        if seq_len not in self._layout_cache:
            self._layout_cache[seq_len] = self.sparsity_config.make_layout(seq_len)
        return self._layout_cache[seq_len]

    def init(self, rng) -> Dict[str, Any]:
        ks = jax.random.split(rng, 4)
        h, i = self.hidden_size, self.intermediate_size
        return {
            "qkv": _dense_init(ks[0], h, 3 * h, self.initializer_range),
            "attn_out": _dense_init(ks[1], h, h, self.initializer_range),
            "fc1": _dense_init(ks[2], h, i, self.initializer_range),
            "fc2": _dense_init(ks[3], i, h, self.initializer_range),
            "ln_attn": {"scale": jnp.ones((h,), jnp.float32),
                        "bias": jnp.zeros((h,), jnp.float32)},
            "ln_mlp": {"scale": jnp.ones((h,), jnp.float32),
                       "bias": jnp.zeros((h,), jnp.float32)},
        }

    @staticmethod
    def partition_specs() -> Dict[str, Any]:
        """Megatron TP layout over the ``model`` axis: QKV/FC1 column-
        parallel, out/FC2 row-parallel (SURVEY §2.3 'slice' groups)."""
        col = {"kernel": P(None, "model"), "bias": P("model")}
        row = {"kernel": P("model", None), "bias": P()}
        ln = {"scale": P(), "bias": P()}
        return {"qkv": col, "attn_out": row, "fc1": col, "fc2": row,
                "ln_attn": ln, "ln_mlp": ln}

    def attention_core(self, params, y, mask=None, key_padding_mask=None,
                       attn_rng=None, deterministic=True, positions=None):
        """Fused-QKV attention → [b, s, h] context, honoring the configured
        ``attn_impl`` (auto/ring/sparse) and attention dropout.  Shared by
        the dense block and :class:`~deepspeed_tpu.models.moe.MoETransformerLayer`,
        so every attention variant behaves identically in both.

        ``positions`` [b, K]: compute QUERIES (and hence output rows) only
        at these positions while keys/values cover the full sequence — the
        final-layer optimization for heads that consume a few positions
        (MLM gather).  Identical math for the computed rows."""
        b, s, h = y.shape
        r1 = attn_rng
        if positions is not None:
            assert self.attn_impl == "auto" and not self.causal, (
                "query-gathered attention supports the dense bidirectional "
                "core only")
            K = positions.shape[1]
            w = params["qkv"]["kernel"].astype(y.dtype)
            bias = params["qkv"]["bias"].astype(y.dtype)
            y_sel = jnp.take_along_axis(y, positions[..., None], axis=1)
            q = (y_sel @ w[:, :h] + bias[:h]).reshape(b, K, self.heads,
                                                      self.head_dim)
            kv = (y @ w[:, h:] + bias[h:]).reshape(b, s, 2, self.heads,
                                                   self.head_dim)
            ctx = dot_product_attention(
                q, kv[:, :, 0], kv[:, :, 1], mask=mask,
                key_padding_mask=key_padding_mask,
                causal=False, dropout_rate=self.attn_dropout_ratio,
                dropout_rng=r1, deterministic=deterministic)
            return ctx.reshape(b, K, h)
        qkv = dense(params["qkv"], y)  # [b, s, 3h] one fused GEMM
        qkv = qkv.reshape(b, s, 3, self.heads, self.head_dim)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        kpm_add = None  # additive [b, s] form for ring/sparse cores
        if self.attn_impl in ("ring", "sparse"):
            if key_padding_mask is not None:
                kpm_add = key_padding_to_additive(key_padding_mask)
            elif mask is not None:
                # the general additive [b, 1, 1, s] broadcast collapses
                assert mask.size == b * s, (
                    f"attn_impl={self.attn_impl!r} supports key-padding "
                    f"masks ([b,1,1,s]), got mask shape {mask.shape}")
                kpm_add = mask.reshape(b, s)
        if self.attn_impl == "ring":
            from ..ops.transformer.ring_attention import ring_attention

            ctx = ring_attention(q, k, v, causal=self.causal,
                                 key_padding_mask=kpm_add)
        elif self.attn_impl == "sparse":
            layout = self._sparse_layout(s)
            causal_sp = self.causal or getattr(
                self.sparsity_config, "attention",
                "bidirectional") == "unidirectional"
            # Pallas LUT-driven kernel on TPU when the layout blocks are
            # MXU-shaped and no key-padding mask is needed; the gather
            # implementation stays as the general/CPU path.
            blk = s // layout.shape[1]
            use_kernel = (kpm_add is None
                          and current_platform() == "tpu"
                          and blk % 128 == 0 and q.shape[-1] % 64 == 0)
            if use_kernel:
                from ..ops.sparse_attention.flash_block_sparse import (
                    flash_block_sparse_attention)

                # heads stay whole: the layout is per head and static
                ctx = shard_kernel_over_mesh(
                    lambda q, k, v, _mask, _seed: (
                        flash_block_sparse_attention(q, k, v, layout,
                                                     causal=causal_sp)),
                    q, k, v, shard_heads=False)
            else:
                from ..ops.sparse_attention import block_sparse_attention

                ctx = block_sparse_attention(
                    q, k, v, layout, causal=causal_sp,
                    key_padding_mask=kpm_add, attn_mask=None)
        else:
            ctx = self_attention(
                qkv, mask=mask, key_padding_mask=key_padding_mask,
                causal=self.causal,
                dropout_rate=self.attn_dropout_ratio, dropout_rng=r1,
                deterministic=deterministic)
        if self.attn_impl in ("ring", "sparse") and r1 is not None \
                and self.attn_dropout_ratio > 0.0:
            # ring/sparse cores have no in-core dropout; apply it to the
            # attention output so attn_dropout_ratio is honored rather
            # than silently ignored.
            ctx = dropout(r1, ctx, self.attn_dropout_ratio, deterministic)
        return ctx.reshape(b, s, h)

    def apply(self, params, x, mask=None, key_padding_mask=None, rng=None,
              deterministic=True, positions=None):
        """x: [batch, seq, hidden]; mask: [batch, 1, 1, seq] additive or None;
        key_padding_mask: [batch, seq] with 1 at visible tokens (routed to the
        fused flash kernel's mask operand on TPU).

        ``positions`` [b, K]: produce outputs only at these positions
        (attention queries gathered; K/V over the full sequence; FFN and
        layernorms on the K gathered rows).  For the FINAL layer of models
        whose heads consume few positions — identical math for those rows,
        ~(s−K)/s of the layer's FLOPs saved.  Returns [b, K, hidden]."""
        b, s, h = x.shape
        assert mask is None or key_padding_mask is None, (
            "pass either an additive mask or a key_padding_mask, not both")
        r1 = r2 = r3 = None
        if rng is not None and not deterministic:
            r1, r2, r3 = jax.random.split(rng, 3)

        @jax.named_scope("attention")
        def attention_block(params, y):
            ctx = self.attention_core(params, y, mask=mask,
                                      key_padding_mask=key_padding_mask,
                                      attn_rng=r1, deterministic=deterministic,
                                      positions=positions)
            out = dense(params["attn_out"], ctx)
            return dropout(r2, out, self.hidden_dropout_ratio, deterministic)

        @jax.named_scope("mlp")
        def mlp_block(params, y):
            z = gelu(dense(params["fc1"], y))
            z = dense(params["fc2"], z)
            return dropout(r3, z, self.hidden_dropout_ratio, deterministic)

        if self.attn_dropout_checkpoint:
            # don't save attention internals (probs/dropout mask);
            # recompute in backward (reference attn_dropout_checkpoint)
            attention_block = jax.checkpoint(attention_block)
        if self.gelu_checkpoint:
            # recompute gelu/fc1 intermediates (reference gelu_checkpoint)
            mlp_block = jax.checkpoint(mlp_block)

        def ln(p, y):
            return layer_norm(p, y, self.layer_norm_eps)

        if self.normalize_invertible:
            # don't save layernorm inputs (reference normalize_invertible
            # re-derives them; recompute is the XLA-friendly equivalent)
            ln = jax.checkpoint(ln)

        if positions is not None:
            # residuals use the gathered input rows; attention_block already
            # returns [b, K, h]
            def sel(t):
                return jnp.take_along_axis(t, positions[..., None], axis=1)
        else:
            sel = lambda t: t

        if self.pre_layer_norm:
            x = sel(x) + attention_block(params, ln(params["ln_attn"], x))
            x = x + mlp_block(params, ln(params["ln_mlp"], x))
        else:
            x = ln(params["ln_attn"], sel(x) + attention_block(params, x))
            x = ln(params["ln_mlp"], x + mlp_block(params, x))
        return x


def embedding_init(rng, vocab_size, hidden, initializer_range=0.02):
    return jax.random.normal(rng, (vocab_size, hidden), jnp.float32) * initializer_range


def cross_entropy_with_logits(logits, labels, ignore_index=-100):
    """Mean token cross entropy with masking; fp32 logsumexp for stability.

    ``labels == ignore_index`` positions contribute nothing (the reference
    relies on torch's CrossEntropyLoss ignore_index semantics).
    """
    logits = logits.astype(jnp.float32)
    mask = labels != ignore_index
    safe_labels = jnp.where(mask, labels, 0)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe_labels[..., None], axis=-1)[..., 0]
    nll = (lse - gold) * mask
    denom = jnp.maximum(jnp.sum(mask), 1)
    return jnp.sum(nll) / denom


def _chunks_of(x, labels, chunk):
    """``x`` and ``labels`` as ``[chunks, rows, chunk, ...]``."""
    b, s, h = x.shape
    n = s // chunk
    return (x.reshape(b, n, chunk, h).swapaxes(0, 1),
            labels.reshape(b, n, chunk).swapaxes(0, 1))


def _chunk_nll(logits, labels):
    """One chunk's summed cross-entropy and what its gradient needs:
    ``(sum, logsumexp, labelled, labels with 0 where unlabelled)``."""
    mask = labels != -100
    safe = jnp.where(mask, labels, 0)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    return jnp.sum((lse - gold) * mask), lse, mask, safe


def _log_chunked_geometry(head, head_params, x, chunk, products, what):
    """One line a traced geometry, as the kernels': which form of the loop
    a program holds."""
    b, s, h = x.shape
    vocab = jax.eval_shape(head, head_params, jax.ShapeDtypeStruct(
        (b, chunk, h), x.dtype)).shape[-1]
    logger.info(
        "chunked_lm_loss geometry: rows=%d seq=%d chunk=%d chunks=%d "
        "vocab=%d head_products_per_chunk=%d (%s)", b, s, chunk, s // chunk,
        vocab, products, what)


def _plain_chunked_loss(head, head_params, x, labels, chunk, recompute=False):
    """The loop as autodiff sees it: a chunk a trip of a ``lax.map``, its
    logits recomputed on the way back under ``recompute``."""

    def one(args):
        xc, lc = args
        total, _, mask, _ = _chunk_nll(head(head_params, xc), lc)
        return total, jnp.sum(mask)

    with jax.named_scope("loss"):
        sums, counts = jax.lax.map(jax.checkpoint(one) if recompute else one,
                                   _chunks_of(x, labels, chunk))
        return jnp.sum(sums) / jnp.maximum(jnp.sum(counts), 1)


def chunked_lm_loss(head, head_params, x, labels, chunk):
    """Mean cross-entropy of ``head(head_params, x)`` over the positions
    labelled other than ``-100``, the head and the loss taken over chunks
    of ``chunk`` positions a row (``chunk`` divides the sequence), so no
    ``[tokens, vocab]`` array exists: a chunk's ``[rows, chunk, vocab]``
    logits live inside one step of a loop.

    ``head`` is a static callable ``(head_params, [rows, chunk, hidden])
    -> logits`` whose products keep their own dtypes; logsumexp and
    softmax run on the logits as it returns them (float32 wherever the
    head accumulates so).

    Differentiated, the gradient is made in the forward, while a chunk's
    logits are live: ``d logits = (softmax - one-hot) * labelled / count``
    goes through the head's own transposes in the same step of ONE loop,
    the head's gradient adds up over the chunks in float32 and is rounded
    once, and what is kept for the way back is ``d x`` and the head's
    gradient — not one logit — which the backward rule only multiplies by
    the loss's cotangent.  So a chunk costs three products where a loop
    recomputed on the way back costs four.  Undifferentiated (an eval
    loss) it is the plain loop and no gradient work is done.

    A float16 head keeps the recomputed loop: its gradients leave their
    products as float16 and need the loss scale — which arrives only with
    the cotangent — inside ``d logits`` to stay out of the subnormals.
    """
    if x.dtype == jnp.float16:
        _log_chunked_geometry(
            head, head_params, x, chunk, 1,
            "float16: 4 where differentiated, recomputed on the way back")
        return _plain_chunked_loss(head, head_params, x, labels, chunk,
                                   recompute=True)
    return _one_pass_lm_loss(head, head_params, x, labels, chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 4))
def _one_pass_lm_loss(head, head_params, x, labels, chunk):
    _log_chunked_geometry(head, head_params, x, chunk, 1, "primal")
    return _plain_chunked_loss(head, head_params, x, labels, chunk)


def _one_pass_lm_loss_fwd(head, head_params, x, labels, chunk):
    _log_chunked_geometry(head, head_params, x, chunk, 3,
                          "gradient in the forward")
    with jax.named_scope("loss"):
        count = jnp.maximum(jnp.sum(labels != -100), 1)

        def step(d_head, args):
            xc, lc = args
            logits, pull = jax.vjp(head, head_params, xc)
            total, lse, mask, safe = _chunk_nll(logits, lc)
            d_logits = (jnp.exp(logits - lse[..., None]) - jax.nn.one_hot(
                safe, logits.shape[-1], dtype=logits.dtype)) * (
                    mask / count)[..., None].astype(logits.dtype)
            d_head_c, d_xc = pull(d_logits)
            return jax.tree_util.tree_map(
                lambda acc, g: acc + g.astype(jnp.float32), d_head,
                d_head_c), (total, d_xc)

        d_head, (sums, d_xs) = jax.lax.scan(
            step, jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), head_params),
            _chunks_of(x, labels, chunk))
        d_head = jax.tree_util.tree_map(
            lambda g, p: g.astype(p.dtype), d_head, head_params)
        return jnp.sum(sums) / count, (d_head, d_xs.swapaxes(0, 1).reshape(
            x.shape))


def _one_pass_lm_loss_bwd(head, chunk, kept, g):
    with jax.named_scope("loss"):
        d_head, d_x = jax.tree_util.tree_map(
            lambda d: (d * g).astype(d.dtype), kept)
    return d_head, d_x, None


_one_pass_lm_loss.defvjp(_one_pass_lm_loss_fwd, _one_pass_lm_loss_bwd)
