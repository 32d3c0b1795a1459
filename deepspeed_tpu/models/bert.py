"""BERT model family (flagship encoder model).

Fills the role of the reference's BERT usage: the DeepSpeedExamples
``bing_bert`` pretraining flow and the fused-kernel test models
(``tests/unit/modeling.py``, ``modelingpreln.py``).  Implemented TPU-first:
one fused QKV GEMM per layer, flash attention, bf16-friendly fp32
layernorms, optional pre-layernorm (the reference's ``pre_layer_norm``
kernel knob), ``jax.checkpoint`` rematerialization per layer (the
reference's activation checkpointing, SURVEY §5.7), and Progressive Layer
Drop support (``pld_theta`` kwarg; reference
``runtime/progressive_layer_drop.py``).

Batch contract for pretraining (``BertForPreTrainingTPU``):
``batch = {"input_ids", "attention_mask", "token_type_ids", "masked_lm_labels",
"next_sentence_labels"}`` → scalar loss (MLM + NSP), mirroring the bing_bert
batch layout.
"""


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import (TransformerLayer, cross_entropy_with_logits, dense,
                     dropout, embedding_init, gelu, layer_norm, _dense_init)


class BertConfig:
    def __init__(self, vocab_size=30528, hidden_size=768, num_hidden_layers=12,
                 num_attention_heads=12, intermediate_size=None,
                 max_position_embeddings=512, type_vocab_size=2,
                 hidden_dropout_prob=0.1, attention_probs_dropout_prob=0.1,
                 initializer_range=0.02, pre_layer_norm=False,
                 layer_norm_eps=1e-12, remat=False,
                 attn_impl="auto", sparsity_config=None,
                 gelu_checkpoint=False, attn_dropout_checkpoint=False,
                 normalize_invertible=False, max_predictions_per_seq=None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.max_position_embeddings = max_position_embeddings
        self.type_vocab_size = type_vocab_size
        self.hidden_dropout_prob = hidden_dropout_prob
        self.attention_probs_dropout_prob = attention_probs_dropout_prob
        self.initializer_range = initializer_range
        self.pre_layer_norm = pre_layer_norm
        self.layer_norm_eps = layer_norm_eps
        self.remat = remat
        self.attn_impl = attn_impl
        self.sparsity_config = sparsity_config
        # kernel memory knobs (reference DeepSpeedTransformerConfig,
        # ops/transformer/transformer.py:109-137)
        self.gelu_checkpoint = gelu_checkpoint
        self.attn_dropout_checkpoint = attn_dropout_checkpoint
        self.normalize_invertible = normalize_invertible
        # MLM head masked-position gather: when set, the transform + vocab
        # projection run over only this many gathered positions per row
        # instead of all of them (~15% of positions carry labels — the
        # projection over the other 85% is wasted FLOPs, ~8% of the step at
        # seq 128).  Must be >= the per-row masked count the data pipeline
        # produces (bing_bert's max_predictions_per_seq contract); rows
        # with more labels than this have the excess silently ignored.
        self.max_predictions_per_seq = max_predictions_per_seq

    @staticmethod
    def bert_base(**kw):
        return BertConfig(hidden_size=768, num_hidden_layers=12,
                          num_attention_heads=12, **kw)

    @staticmethod
    def bert_large(**kw):
        return BertConfig(hidden_size=1024, num_hidden_layers=24,
                          num_attention_heads=16, **kw)


class BertModel:
    """Encoder trunk: embeddings + N transformer layers (+pooler)."""

    def __init__(self, config: BertConfig):
        self.config = config
        self.layer = TransformerLayer(
            hidden_size=config.hidden_size, heads=config.num_attention_heads,
            intermediate_size=config.intermediate_size, causal=False,
            attn_dropout_ratio=config.attention_probs_dropout_prob,
            hidden_dropout_ratio=config.hidden_dropout_prob,
            pre_layer_norm=config.pre_layer_norm,
            initializer_range=config.initializer_range,
            layer_norm_eps=config.layer_norm_eps,
            attn_impl=config.attn_impl,
            sparsity_config=config.sparsity_config,
            gelu_checkpoint=config.gelu_checkpoint,
            attn_dropout_checkpoint=config.attn_dropout_checkpoint,
            normalize_invertible=config.normalize_invertible)

    def init(self, rng):
        c = self.config
        keys = jax.random.split(rng, c.num_hidden_layers + 5)
        params = {
            "embeddings": {
                "word": embedding_init(keys[0], c.vocab_size, c.hidden_size,
                                       c.initializer_range),
                "position": embedding_init(keys[1], c.max_position_embeddings,
                                           c.hidden_size, c.initializer_range),
                "token_type": embedding_init(keys[2], c.type_vocab_size,
                                             c.hidden_size, c.initializer_range),
                "ln": {"scale": jnp.ones((c.hidden_size,), jnp.float32),
                       "bias": jnp.zeros((c.hidden_size,), jnp.float32)},
            },
            "encoder": {f"layer_{i}": self.layer.init(keys[3 + i])
                        for i in range(c.num_hidden_layers)},
            "pooler": _dense_init(keys[-2], c.hidden_size, c.hidden_size,
                                  c.initializer_range),
        }
        return params

    def partition_specs(self, mesh):
        c = self.config
        layer_spec = TransformerLayer.partition_specs()
        emb = P("model", None) if "model" in mesh.axis_names else P()
        return {
            "embeddings": {"word": emb, "position": P(), "token_type": P(),
                           "ln": {"scale": P(), "bias": P()}},
            "encoder": {f"layer_{i}": layer_spec for i in range(c.num_hidden_layers)},
            "pooler": {"kernel": P(), "bias": P()},
        }

    def encode(self, params, input_ids, attention_mask=None, token_type_ids=None,
               rng=None, deterministic=True, pld_theta=None, dtype=None,
               final_positions=None):
        """``final_positions`` [b, K]: compute the LAST encoder layer only
        at these positions (queries gathered, K/V full — see
        ``TransformerLayer.apply``); the returned sequence output is
        [b, K, hidden] and the pooler reads row 0, so callers must put
        position 0 first.  Ignored under Progressive Layer Drop (the
        keep/passthrough select needs uniform shapes)."""
        c = self.config
        b, s = input_ids.shape
        emb = params["embeddings"]
        with jax.named_scope("embed"):
            x = (jnp.take(emb["word"], input_ids, axis=0)
                 + emb["position"][None, :s]
                 + (jnp.take(emb["token_type"], token_type_ids, axis=0)
                    if token_type_ids is not None else 0.0))
            if dtype is not None:
                x = x.astype(dtype)
            x = layer_norm(emb["ln"], x, c.layer_norm_eps)
            if rng is not None and not deterministic:
                rng_e, rng = jax.random.split(rng)
                x = dropout(rng_e, x, c.hidden_dropout_prob, deterministic)

        # Key-padding form (1 = visible), so the flash kernel can fuse the
        # mask into its softmax instead of falling back to O(s²) attention.
        kpm = attention_mask

        def run_layer(layer_params, x, layer_rng):
            return self.layer.apply(layer_params, x, key_padding_mask=kpm,
                                    rng=layer_rng, deterministic=deterministic)

        ck_layer = None
        if c.remat:
            from ..runtime.activation_checkpointing import checkpointing as ds_ckpt

            ck_layer = ds_ckpt.checkpoint_wrapper(run_layer)

        if pld_theta is not None:
            final_positions = None  # PLD's select needs uniform shapes

        def run_last_layer(layer_params, x, layer_rng):
            return self.layer.apply(layer_params, x, key_padding_mask=kpm,
                                    rng=layer_rng, deterministic=deterministic,
                                    positions=final_positions)

        for i in range(c.num_hidden_layers):
            layer_rng = None
            if rng is not None and not deterministic:
                rng, layer_rng = jax.random.split(rng)
            last = (i == c.num_hidden_layers - 1)
            fn = run_last_layer if (last and final_positions is not None) \
                else run_layer
            if ck_layer is not None:
                from ..runtime.activation_checkpointing import checkpointing as ds_ckpt

                if ds_ckpt.should_checkpoint_layer(i, c.num_hidden_layers):
                    fn = (ds_ckpt.checkpoint_wrapper(run_last_layer)
                          if (last and final_positions is not None)
                          else ck_layer)
            with jax.named_scope(f"layer_{i}"):
                y = fn(params["encoder"][f"layer_{i}"], x, layer_rng)
            if pld_theta is not None and not deterministic and layer_rng is not None:
                # Progressive Layer Drop: keep layer with prob θ; residual
                # pass-through otherwise (reference PLD wiring
                # engine.py:809-810 + bing_bert modeling).  Expressed as a
                # select so the program stays static-shape for XLA.
                keep = jax.random.bernoulli(jax.random.fold_in(layer_rng, 17),
                                            jnp.clip(pld_theta, 0.0, 1.0))
                x = jnp.where(keep, y, x)
            else:
                x = y
        with jax.named_scope("pooler"):
            pooled = jnp.tanh(dense(params["pooler"], x[:, 0]))
        return x, pooled


class BertForPreTrainingTPU:
    """MLM + NSP pretraining objective (bing_bert parity)."""

    def __init__(self, config: BertConfig, compute_dtype=None):
        self.config = config
        self.bert = BertModel(config)
        self.compute_dtype = compute_dtype

    def init(self, rng):
        c = self.config
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        params = {"bert": self.bert.init(k1)}
        params["cls"] = {
            "transform": _dense_init(k2, c.hidden_size, c.hidden_size,
                                     c.initializer_range),
            "transform_ln": {"scale": jnp.ones((c.hidden_size,), jnp.float32),
                             "bias": jnp.zeros((c.hidden_size,), jnp.float32)},
            "decoder_bias": jnp.zeros((c.vocab_size,), jnp.float32),
            "seq_relationship": _dense_init(k3, c.hidden_size, 2,
                                            c.initializer_range),
        }
        return params

    def sparse_gradient_paths(self):
        """Embedding leaves with genuinely row-sparse gradients (the
        reference's nn.Embedding auto-detect, ``engine.py:180-185``).  The
        word embedding does NOT qualify here: the MLM decoder ties to it
        (``apply``), and the vocab projection's backward puts gradient on
        EVERY vocab row — a row-sparse exchange would drop most of it (the
        engine poisons such a step with NaN rather than train silently
        wrong).  The 2-row token_type table can never beat its own exchange
        overhead either, so the pretraining model declares NOTHING — the
        engine then keeps the plain GSPMD path.  The untied heads (QA,
        classification) do declare the word embedding."""
        return ()

    def partition_specs(self, mesh):
        has_model = "model" in mesh.axis_names
        return {
            "bert": self.bert.partition_specs(mesh),
            "cls": {
                "transform": {"kernel": P(), "bias": P()},
                "transform_ln": {"scale": P(), "bias": P()},
                "decoder_bias": P("model") if has_model else P(),
                "seq_relationship": {"kernel": P(), "bias": P()},
            },
        }

    def apply(self, params, batch, rng=None, train=True, pld_theta=None, **kw):
        c = self.config
        input_ids = batch["input_ids"]
        attention_mask = batch.get("attention_mask")
        token_type_ids = batch.get("token_type_ids")
        mlm_labels = batch.get("masked_lm_labels")
        n_pred = c.max_predictions_per_seq
        # Gather the labeled positions before the head — and, when PLD is
        # off, before the FINAL encoder layer too (its outputs at other
        # positions feed nothing): only ~15% of positions carry MLM
        # labels, so the last layer + vocab projection over the rest is
        # pure waste (the reference pays it; this is the fused-kernel
        # philosophy applied at the model level).  top_k of the label mask
        # is stable, so it selects the FIRST n_pred labeled positions;
        # unlabeled fill positions gather a -100 label and are ignored by
        # the loss.  Position 0 rides along for the pooler/NSP head.
        gather = (mlm_labels is not None and n_pred
                  and n_pred < input_ids.shape[1])
        final_positions = None
        if gather:
            with jax.named_scope("mlm_head"):
                is_masked = (mlm_labels != -100).astype(jnp.int32)
                _, pos = jax.lax.top_k(is_masked, n_pred)  # [b, n_pred]
                mlm_labels = jnp.take_along_axis(mlm_labels, pos, axis=1)
                # final-layer query gather needs the dense bidirectional
                # attention core and uniform shapes (no PLD select); other
                # configs keep the full final layer + post-encode head
                # gather
                if pld_theta is None and c.attn_impl == "auto":
                    final_positions = jnp.concatenate(
                        [jnp.zeros((pos.shape[0], 1), pos.dtype), pos],
                        axis=1)
        seq_out, pooled = self.bert.encode(
            params["bert"], input_ids, attention_mask, token_type_ids,
            rng=rng, deterministic=not train, pld_theta=pld_theta,
            dtype=self.compute_dtype, final_positions=final_positions)

        cls = params["cls"]
        with jax.named_scope("mlm_head"):
            head_in = seq_out
            if gather:
                if final_positions is not None:
                    # encode returned [b, 1 + n_pred, h]: CLS row + label
                    # rows
                    head_in = seq_out[:, 1:]
                else:  # PLD active — encode ran full-length; gather here
                    head_in = jnp.take_along_axis(seq_out, pos[..., None],
                                                  axis=1)
            h = gelu(dense(cls["transform"], head_in))
            h = layer_norm(cls["transform_ln"], h, c.layer_norm_eps)
            # decoder tied to word embeddings (standard BERT; the reference
            # ties them through TiedLayerSpec under pipelining,
            # module.py:71)
            logits = h @ params["bert"]["embeddings"]["word"].T.astype(
                h.dtype) + cls["decoder_bias"].astype(h.dtype)

        if not train and mlm_labels is None:
            return logits

        with jax.named_scope("loss"):
            loss = cross_entropy_with_logits(logits, mlm_labels,
                                             ignore_index=-100)
            if "next_sentence_labels" in batch:
                nsp_logits = dense(cls["seq_relationship"], pooled)
                loss = loss + cross_entropy_with_logits(
                    nsp_logits, batch["next_sentence_labels"])
        return loss


class BertForQuestionAnsweringTPU:
    """Extractive QA (SQuAD) head: per-token start/end logits.

    Parity target: the reference's BingBertSquad fine-tuning flow
    (``tests/model/BingBertSquad/test_e2e_squad.py``) whose model is BERT +
    a 2-output span classifier.  Batch: ``{"input_ids", "attention_mask",
    "token_type_ids", "start_positions", "end_positions"}`` → scalar loss;
    without positions, returns ``(start_logits, end_logits)``.
    """

    def __init__(self, config: BertConfig, compute_dtype=None):
        self.config = config
        self.bert = BertModel(config)
        self.compute_dtype = compute_dtype

    def sparse_gradient_paths(self):
        # no tied LM head here, so the word embedding's grad really is
        # row-sparse (only token rows touched)
        return ("bert/embeddings/word", "bert/embeddings/token_type")

    def init(self, rng):
        k1, k2 = jax.random.split(rng)
        return {"bert": self.bert.init(k1),
                "qa_outputs": _dense_init(k2, self.config.hidden_size, 2,
                                          self.config.initializer_range)}

    def partition_specs(self, mesh):
        return {"bert": self.bert.partition_specs(mesh),
                "qa_outputs": {"kernel": P(), "bias": P()}}

    def apply(self, params, batch, rng=None, train=True, **kw):
        seq_out, _ = self.bert.encode(
            params["bert"], batch["input_ids"], batch.get("attention_mask"),
            batch.get("token_type_ids"), rng=rng, deterministic=not train,
            dtype=self.compute_dtype)
        logits = dense(params["qa_outputs"], seq_out)  # [b, s, 2]
        start_logits = logits[..., 0]
        end_logits = logits[..., 1]
        if "start_positions" not in batch and "end_positions" not in batch:
            return start_logits, end_logits
        assert "start_positions" in batch and "end_positions" in batch, (
            "QA batches must carry both start_positions and end_positions")
        # out-of-range positions (truncated/unanswerable spans in SQuAD
        # preprocessing) contribute nothing — torch CrossEntropyLoss
        # ignored_index semantics, via this codebase's ignore_index path
        s_len = start_logits.shape[1]

        def ignore_oob(pos):
            return jnp.where((pos < 0) | (pos >= s_len), -100, pos)

        loss = 0.5 * (
            cross_entropy_with_logits(start_logits,
                                      ignore_oob(batch["start_positions"]))
            + cross_entropy_with_logits(end_logits,
                                        ignore_oob(batch["end_positions"])))
        return loss


class BertForSequenceClassificationTPU:
    """[CLS]-pooled classification/regression head (GLUE-style).

    Batch: ``{"input_ids", "attention_mask", "token_type_ids", "labels"}``
    → scalar loss; without labels, returns [b, num_labels] logits.
    Integer labels → cross entropy; float labels → mean-squared error on
    the squeezed logits (STS-B-style regression).
    """

    def __init__(self, config: BertConfig, num_labels=2, compute_dtype=None):
        self.config = config
        self.num_labels = num_labels
        self.bert = BertModel(config)
        self.compute_dtype = compute_dtype

    def sparse_gradient_paths(self):
        # untied trunk (see BertForQuestionAnsweringTPU)
        return ("bert/embeddings/word", "bert/embeddings/token_type")

    def init(self, rng):
        k1, k2 = jax.random.split(rng)
        return {"bert": self.bert.init(k1),
                "classifier": _dense_init(k2, self.config.hidden_size,
                                          self.num_labels,
                                          self.config.initializer_range)}

    def partition_specs(self, mesh):
        return {"bert": self.bert.partition_specs(mesh),
                "classifier": {"kernel": P(), "bias": P()}}

    def apply(self, params, batch, rng=None, train=True, **kw):
        _, pooled = self.bert.encode(
            params["bert"], batch["input_ids"], batch.get("attention_mask"),
            batch.get("token_type_ids"), rng=rng, deterministic=not train,
            dtype=self.compute_dtype)
        if rng is not None and train:
            pooled = dropout(jax.random.fold_in(rng, 99), pooled,
                             self.config.hidden_dropout_prob, False)
        logits = dense(params["classifier"], pooled)
        if "labels" not in batch:
            return logits
        labels = batch["labels"]
        if jnp.issubdtype(jnp.asarray(labels).dtype, jnp.floating):
            preds = jnp.squeeze(logits, -1) if logits.shape[-1] == 1 else logits
            return jnp.mean((preds.astype(jnp.float32)
                             - labels.astype(jnp.float32)) ** 2)
        return cross_entropy_with_logits(logits, labels)
