"""GPT-2 model family (flagship decoder model).

Fills the role of the reference's Megatron-GPT2 integration tests and perf
configs (``tests/model/Megatron_GPT2``; BASELINE configs #3/#4).  Decoder-
only transformer with pre-layernorm blocks (GPT-2 convention), causal flash
attention, weight-tied LM head, optional per-layer remat, and Megatron-style
tensor-parallel partition specs.

Batch contract: ``batch = {"input_ids"[, "labels"]}``; labels default to
shifted input_ids; ``-100`` positions are ignored.
"""

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import (TransformerLayer, cross_entropy_with_logits, dropout,
                     embedding_init, layer_norm)


class GPT2Config:
    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12,
                 num_heads=12, max_position_embeddings=1024,
                 embd_dropout=0.1, attn_dropout=0.1, resid_dropout=0.1,
                 initializer_range=0.02, layer_norm_eps=1e-5, remat=False,
                 attn_impl="auto", sparsity_config=None,
                 gelu_checkpoint=False, attn_dropout_checkpoint=False,
                 normalize_invertible=False,
                 moe_experts=0, moe_every=2, moe_k=2,
                 moe_capacity_factor=1.25, moe_aux_coef=0.01,
                 loss_chunk=0):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.max_position_embeddings = max_position_embeddings
        self.embd_dropout = embd_dropout
        self.attn_dropout = attn_dropout
        self.resid_dropout = resid_dropout
        self.initializer_range = initializer_range
        self.layer_norm_eps = layer_norm_eps
        self.gelu_checkpoint = gelu_checkpoint
        self.attn_dropout_checkpoint = attn_dropout_checkpoint
        self.normalize_invertible = normalize_invertible
        # loss_chunk > 0: fused LM-head + CE over sequence chunks of this
        # size (never materializes the full [b, s, vocab] logits; backward
        # recomputes per chunk).  Loss is exactly the full-logits value.
        self.loss_chunk = loss_chunk
        # MoE (beyond-reference; expert parallelism over the 'expert' axis):
        # moe_experts > 0 swaps the dense FFN for a routed-expert FFN on
        # every moe_every-th block (GShard-style alternation)
        self.moe_experts = moe_experts
        self.moe_every = moe_every
        self.moe_k = moe_k
        self.moe_capacity_factor = moe_capacity_factor
        self.moe_aux_coef = moe_aux_coef
        self.remat = remat
        self.attn_impl = attn_impl
        self.sparsity_config = sparsity_config

    @staticmethod
    def gpt2_small(**kw):
        return GPT2Config(hidden_size=768, num_layers=12, num_heads=12, **kw)

    @staticmethod
    def gpt2_medium(**kw):
        """GPT-2 345M (BASELINE config #3)."""
        return GPT2Config(hidden_size=1024, num_layers=24, num_heads=16, **kw)

    @staticmethod
    def gpt2_large(**kw):
        return GPT2Config(hidden_size=1280, num_layers=36, num_heads=20, **kw)

    @staticmethod
    def gpt2_xl(**kw):
        """GPT-2 1.5B (BASELINE config #4)."""
        return GPT2Config(hidden_size=1600, num_layers=48, num_heads=25, **kw)


class GPT2LMHeadTPU:
    def __init__(self, config: GPT2Config, compute_dtype=None):
        self.config = config
        self.compute_dtype = compute_dtype
        self.layer = TransformerLayer(
            hidden_size=config.hidden_size, heads=config.num_heads,
            causal=True, attn_dropout_ratio=config.attn_dropout,
            hidden_dropout_ratio=config.resid_dropout, pre_layer_norm=True,
            initializer_range=config.initializer_range,
            layer_norm_eps=config.layer_norm_eps,
            attn_impl=config.attn_impl,
            sparsity_config=config.sparsity_config,
            gelu_checkpoint=config.gelu_checkpoint,
            attn_dropout_checkpoint=config.attn_dropout_checkpoint,
            normalize_invertible=config.normalize_invertible)
        self.moe_layer = None
        if config.moe_experts:
            from .moe import MoETransformerLayer

            self.moe_layer = MoETransformerLayer(
                hidden_size=config.hidden_size, heads=config.num_heads,
                num_experts=config.moe_experts, causal=True,
                k=config.moe_k, capacity_factor=config.moe_capacity_factor,
                attn_dropout_ratio=config.attn_dropout,
                hidden_dropout_ratio=config.resid_dropout,
                initializer_range=config.initializer_range,
                layer_norm_eps=config.layer_norm_eps,
                attn_impl=config.attn_impl,
                sparsity_config=config.sparsity_config,
                gelu_checkpoint=config.gelu_checkpoint,
                attn_dropout_checkpoint=config.attn_dropout_checkpoint,
                normalize_invertible=config.normalize_invertible)

    def serving(self):
        """This model's side of ``InferenceEngine``'s model interface."""
        from ..inference.model import GPT2Serving

        return GPT2Serving(self.config)

    def _is_moe_layer(self, i):
        c = self.config
        return bool(c.moe_experts) and i % c.moe_every == c.moe_every - 1

    def init(self, rng):
        c = self.config
        keys = jax.random.split(rng, c.num_layers + 3)
        return {
            "wte": embedding_init(keys[0], c.vocab_size, c.hidden_size,
                                  c.initializer_range),
            "wpe": embedding_init(keys[1], c.max_position_embeddings,
                                  c.hidden_size, c.initializer_range),
            "blocks": {f"layer_{i}": (self.moe_layer.init(keys[2 + i])
                                      if self._is_moe_layer(i)
                                      else self.layer.init(keys[2 + i]))
                       for i in range(c.num_layers)},
            "ln_f": {"scale": jnp.ones((c.hidden_size,), jnp.float32),
                     "bias": jnp.zeros((c.hidden_size,), jnp.float32)},
        }

    def sparse_gradient_paths(self):
        """Embedding leaves with genuinely row-sparse gradients (the
        reference's nn.Embedding auto-detect, ``engine.py:180-185``).
        ``wte`` does NOT qualify: the LM head ties to it, and the vocab
        projection's backward puts gradient mass on EVERY vocab row, so a
        row-sparse exchange would drop most of it (the engine would poison
        the step with NaN).  ``wpe`` rows are all touched every step, so
        there is nothing to compress either."""
        return ()

    def partition_specs(self, mesh):
        c = self.config
        has_model = "model" in mesh.axis_names
        layer_spec = TransformerLayer.partition_specs()
        moe_spec = None
        if c.moe_experts:
            from .moe import MoETransformerLayer

            moe_spec = MoETransformerLayer.partition_specs()
        return {
            "wte": P("model", None) if has_model else P(),
            "wpe": P(),
            "blocks": {f"layer_{i}": (moe_spec if self._is_moe_layer(i)
                                      else layer_spec)
                       for i in range(c.num_layers)},
            "ln_f": {"scale": P(), "bias": P()},
        }

    def hidden(self, params, input_ids, rng=None, deterministic=True):
        """Trunk + final layernorm → [b, s, h] (pre-LM-head hidden states)."""
        c = self.config
        b, s = input_ids.shape
        with jax.named_scope("embed"):
            x = jnp.take(params["wte"], input_ids, axis=0) \
                + params["wpe"][None, :s]
            if self.compute_dtype is not None:
                x = x.astype(self.compute_dtype)
            if rng is not None and not deterministic:
                rng_e, rng = jax.random.split(rng)
                x = dropout(rng_e, x, c.embd_dropout, deterministic)

        aux_losses = []

        def run_layer(layer_params, x, layer_rng):
            return self.layer.apply(layer_params, x, rng=layer_rng,
                                    deterministic=deterministic)

        def run_moe_layer(layer_params, x, layer_rng):
            return self.moe_layer.apply(layer_params, x, rng=layer_rng,
                                        deterministic=deterministic)

        ck_layer = ck_moe_layer = None
        if c.remat:
            from ..runtime.activation_checkpointing import checkpointing as ds_ckpt

            ck_layer = ds_ckpt.checkpoint_wrapper(run_layer)
            if self.moe_layer is not None:
                ck_moe_layer = ds_ckpt.checkpoint_wrapper(run_moe_layer)

        for i in range(c.num_layers):
            layer_rng = None
            if rng is not None and not deterministic:
                rng, layer_rng = jax.random.split(rng)
            if self._is_moe_layer(i):
                fn = run_moe_layer
                if ck_moe_layer is not None:
                    from ..runtime.activation_checkpointing import checkpointing as ds_ckpt

                    if ds_ckpt.should_checkpoint_layer(i, c.num_layers):
                        fn = ck_moe_layer
                with jax.named_scope(f"layer_{i}"):
                    x, aux = fn(params["blocks"][f"layer_{i}"], x, layer_rng)
                    aux_losses.append(aux)
                continue
            fn = run_layer
            if ck_layer is not None:
                from ..runtime.activation_checkpointing import checkpointing as ds_ckpt

                if ds_ckpt.should_checkpoint_layer(i, c.num_layers):
                    fn = ck_layer
            with jax.named_scope(f"layer_{i}"):
                x = fn(params["blocks"][f"layer_{i}"], x, layer_rng)

        with jax.named_scope("final_norm"):
            x = layer_norm(params["ln_f"], x, c.layer_norm_eps)
        self._last_moe_aux = (sum(aux_losses) / len(aux_losses)
                              if aux_losses else None)
        return x

    @staticmethod
    @jax.named_scope("lm_head")
    def _lm_head(params, x):
        """Tied LM head (wte shared with the input embedding; the
        reference ties them through TiedLayerSpec under pipelining)."""
        return x @ params["wte"].T.astype(x.dtype)

    def logits(self, params, input_ids, rng=None, deterministic=True):
        x = self.hidden(params, input_ids, rng=rng, deterministic=deterministic)
        return self._lm_head(params, x)

    def _chunked_lm_loss(self, params, x, labels, chunk):
        """Fused LM-head + cross entropy over sequence chunks.

        The full-logits path materializes [b, s, V] (824 MB bf16 at
        GPT-2-medium bench shape) and upcasts it to fp32 for the
        logsumexp (3.3 GB) — the single biggest tensor in the step.  Here
        each chunk's logits [b, chunk, V] live only inside one
        ``lax.map`` iteration and the backward recomputes them
        (``jax.checkpoint``), the reference's fused-kernel philosophy
        (``csrc/transformer/gelu_kernels.cu``-class fusion) applied to
        the head: HBM high-water drops by ~the logits tensor.
        """
        b, s, h = x.shape
        n = s // chunk
        assert s % chunk == 0, f"seq {s} not divisible by loss_chunk {chunk}"
        w = params["wte"]
        xs = x.reshape(b, n, chunk, h).swapaxes(0, 1)        # [n,b,chunk,h]
        ls = labels.reshape(b, n, chunk).swapaxes(0, 1)

        @jax.checkpoint
        def one(args):
            xc, lc = args
            with jax.named_scope("lm_head"):
                logits = (xc @ w.T.astype(xc.dtype)).astype(jnp.float32)
            mask = lc != -100
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(
                logits, jnp.where(mask, lc, 0)[..., None], axis=-1)[..., 0]
            return jnp.sum((lse - gold) * mask), jnp.sum(mask)

        with jax.named_scope("loss"):
            sums, counts = jax.lax.map(one, (xs, ls))
            return jnp.sum(sums) / jnp.maximum(jnp.sum(counts), 1)

    def apply(self, params, batch, rng=None, train=True, **kw):
        c = self.config
        input_ids = batch["input_ids"] if isinstance(batch, dict) else batch
        want_logits = not train and not (isinstance(batch, dict)
                                         and "labels" in batch)
        chunk = getattr(c, "loss_chunk", 0)
        use_chunked = (not want_logits and chunk
                       and input_ids.shape[1] % chunk == 0)
        if chunk and not want_logits and not use_chunked:
            from ..utils.logging import logger

            logger.warning(
                "loss_chunk=%s does not divide seq %s — falling back to the "
                "FULL-logits loss (the [b, s, vocab] tensor this knob exists "
                "to avoid WILL be materialized); pick a divisor",
                chunk, input_ids.shape[1])
        x = self.hidden(params, input_ids, rng=rng, deterministic=not train)
        if want_logits:
            return self._lm_head(params, x)
        if isinstance(batch, dict) and "labels" in batch:
            labels = batch["labels"]
        else:
            labels = jnp.concatenate(
                [input_ids[:, 1:],
                 jnp.full((input_ids.shape[0], 1), -100, input_ids.dtype)], axis=1)
        if use_chunked:
            loss = self._chunked_lm_loss(params, x, labels, int(chunk))
        else:
            logits = self._lm_head(params, x)
            with jax.named_scope("loss"):
                loss = cross_entropy_with_logits(logits, labels,
                                                 ignore_index=-100)
        if train and getattr(self, "_last_moe_aux", None) is not None:
            # Switch load-balancing aux loss (training-only regularizer),
            # averaged over MoE blocks; eval loss stays comparable to dense
            loss = loss + self.config.moe_aux_coef * self._last_moe_aux
        return loss
