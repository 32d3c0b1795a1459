"""Mellum 2 (JetBrains ``Mellum2-12B-A2.5B``, ``model_type`` ``mellum``) for
training: a decoder whose every layer is a mixture of experts, with sliding
window attention on three layers of four and full attention on the fourth.

Layer ``l``, residual stream ``x`` in fp32, products in the compute dtype
with fp32 accumulation::

    h = RMSNorm(x)                      q, k, v = h Wqkv   (no bias)
    q, k = RMSNorm over each head (learned scale), then rotary on the whole
           head, halves convention, by the layer's type: window layers
           theta^(-2i/d); full layers YaRN (frequencies blended between
           interpolated and extrapolated, cos and sin times attention_factor)
    x = x + softmax(q k^T / sqrt(d), causal[, last `sliding_window` keys]) v Wo
           query head n reads KV head n // (heads / kv heads)
    g = RMSNorm(x);  p = softmax(g Wr) over ALL the experts, in fp32
    x = x + sum over the 8 best experts c HELD HERE of
            p_c / (sum of the 8 best p) * Wdown_c (silu(Wgate_c g) * Wup_c g)

then ``RMSNorm``, the untied head over the vocabulary rows held here, and
the mean next-token cross-entropy plus ``router_aux_loss_coef`` times the
mean over layers and sequences of the load-balancing loss
(``expert_shard.aux_load_balance``, a sequence at a time: what a
data-parallel chip computes on its own tokens).

The chip's share is the expert layer's (``models/expert_shard.py``):
``experts_held`` experts from ``first_expert`` on, the router scoring all
``num_experts``; pairs that fall to experts held elsewhere add nothing, and
no code stands in for the other chips.  Attention runs the flash kernels'
grouped and windowed forms, forward and backward
(``ops/transformer/flash_attention.py``); off the TPU the same kernels run
through Pallas' interpreter.  Each layer is recomputed on the way back
(``remat``) but for its attention kernel's output and logsumexp (0.27 GB a
layer at 4 rows of 8192) and the expert layer's routing — the chosen scores
and ids, the sorted pairs' order and weights, each pair's place and the
group sizes, 5 MB a layer — which are kept (``SAVED_NAMES``: the second
forward holds no ``top_k`` and no sort), and the head and the loss are
taken over chunks of positions (``loss_chunk``,
``layers.chunked_lm_loss``), so neither an ``[s, s]`` nor a ``[tokens,
vocab]`` array exists.  Nothing of the head is recomputed: each chunk's
gradient is made in the forward while its logits are live, and what is kept
for the way back is ``d x`` into the final norm and the head's gradient
(0.19 GB at 4 rows of 8192 and 24,576 vocabulary rows) — not one logit.

Batch contract: ``{"input_ids"[, "labels"]}`` as ``GPT2LMHeadTPU``'s;
``eval_batch`` on ids alone returns the logits at every position.
"""

import math
import types

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.transformer.flash_attention import SAVED_NAMES as flash_saved_names
from ..ops.transformer.flash_attention import flash_attention
from ..parallel.mesh import current_platform
from . import expert_shard
from .deepseek_v2 import yarn_inv_freq
from .layers import chunked_lm_loss, rms_norm

WINDOW, FULL = "sliding_attention", "full_attention"

# what a recomputed layer keeps: the attention kernels' outputs and the
# expert layer's routing (who was chosen, who sorted where)
SAVED_NAMES = flash_saved_names + expert_shard.SAVED_NAMES

# the published ``rope_parameters``
ROPE_PARAMETERS = {
    FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
           "original_max_position_embeddings": 8192, "beta_fast": 32,
           "beta_slow": 1, "attention_factor": 1.2772588722239782},
    WINDOW: {"rope_type": "default", "rope_theta": 500000},
}


class MellumConfig:
    """The published ``config.json`` keys that shape the model, the share
    this chip holds (``experts_held`` routed experts from ``first_expert``
    on; the router still scores all ``num_experts``) and how the step is
    cut to fit (``remat``, ``loss_chunk``; ``attn_block``: the flash
    kernels' square blocks on a window layer and on a full one).
    ``layer_types`` may be the published list whole: the first
    ``num_hidden_layers`` entries are the layers run."""

    def __init__(self, vocab_size=98304, hidden_size=2304,
                 num_hidden_layers=28, num_attention_heads=32,
                 num_key_value_heads=4, head_dim=128,
                 moe_intermediate_size=896, num_experts=64,
                 num_experts_per_tok=8, norm_topk_prob=True,
                 layer_types=None, sliding_window=1024,
                 rope_parameters=None, rms_norm_eps=1e-6,
                 max_position_embeddings=131072, initializer_range=0.02,
                 router_aux_loss_coef=0.001, experts_held=None,
                 first_expert=0, remat=True, loss_chunk=0,
                 attn_block=(512, 1024), expert_tiling=(512, 1024, 1024)):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = n = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.moe_intermediate_size = moe_intermediate_size
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.norm_topk_prob = bool(norm_topk_prob)
        self.layer_types = list(layer_types or (
            [WINDOW, WINDOW, WINDOW, FULL] * n))[:n]
        self.sliding_window = sliding_window
        self.rope_parameters = rope_parameters or ROPE_PARAMETERS
        self.rms_norm_eps = rms_norm_eps
        self.max_position_embeddings = max_position_embeddings
        self.initializer_range = initializer_range
        self.router_aux_loss_coef = router_aux_loss_coef
        self.experts_held = (num_experts if experts_held is None
                             else experts_held)
        self.first_expert = first_expert
        self.remat = remat
        self.loss_chunk = loss_chunk
        self.attn_block = tuple(attn_block)
        self.expert_tiling = tuple(expert_tiling)
        assert len(self.layer_types) == n
        assert set(self.layer_types) <= {WINDOW, FULL}
        assert num_attention_heads % num_key_value_heads == 0
        assert 0 <= first_expert \
            and first_expert + self.experts_held <= num_experts


def rotary_inv_freq(config, kind):
    """The rotary frequencies ``[head_dim / 2]`` of a layer of ``kind``
    and the factor on its cos and sin: the plain ones, or YaRN's blend
    (``deepseek_v2.yarn_inv_freq``, the same published function) with
    ``attention_factor``.  Static: the same at every length."""
    rp = config.rope_parameters[kind]
    d, theta = config.head_dim, float(rp["rope_theta"])
    if rp["rope_type"] == "default":
        return (1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d),
                1.0)
    assert rp["rope_type"] == "yarn", rp["rope_type"]
    return (jnp.asarray(yarn_inv_freq(types.SimpleNamespace(
        rope_scaling=rp, qk_rope_head_dim=d, rope_theta=theta))),
        float(rp["attention_factor"]))


def rotary_tables(config, kind, seq):
    """``(cos, sin) [seq, 1, head_dim / 2]`` of positions ``0 .. seq - 1``."""
    inv_freq, factor = rotary_inv_freq(config, kind)
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq
    return (jnp.cos(angles) * factor)[:, None], \
        (jnp.sin(angles) * factor)[:, None]


def rotate(x, cos, sin):
    """Rotary embedding of ``x [rows, seq, heads, head_dim]`` (fp32) over
    the whole head: value ``c`` pairs with ``c + d/2`` (``rotate_half``)."""
    half = x.shape[-1] // 2
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin],
                           axis=-1)


class MellumForCausalLMTPU:
    """The trainable model: the interface ``GPT2LMHeadTPU`` gives the
    engine (``init``, ``apply``, ``logits``, ``partition_specs``) plus
    ``apply_reporting``, whose scalars the engine reads at its print
    cadence."""

    def __init__(self, config: MellumConfig):
        self.config = config

    @property
    def interpret(self):
        """Off the TPU the kernels run through Pallas' interpreter; asked
        while a program is traced, of the mesh it is traced for."""
        return current_platform() != "tpu"

    # -- parameters --------------------------------------------------------
    def param_shapes(self):
        c = self.config
        h, d = c.hidden_size, c.head_dim
        q_width, kv_width = c.num_attention_heads * d, \
            c.num_key_value_heads * d
        layer = {
            "input_norm": {"scale": (h,)},
            "qkv": {"kernel": (h, q_width + 2 * kv_width)},
            "q_norm": {"scale": (d,)}, "k_norm": {"scale": (d,)},
            "attn_out": {"kernel": (q_width, h)},
            "post_norm": {"scale": (h,)},
            "moe": {"router": {"kernel": (h, c.num_experts)},
                    "experts": {
                        "gate_up": (c.experts_held, h,
                                    2 * c.moe_intermediate_size),
                        "down": (c.experts_held, c.moe_intermediate_size,
                                 h)}}}
        return {"embed": (c.vocab_size, h),
                "layers": {f"layer_{i}": layer
                           for i in range(c.num_hidden_layers)},
                "final_norm": {"scale": (h,)},
                "lm_head": {"kernel": (h, c.vocab_size)}}

    def init(self, rng):
        """N(0, ``initializer_range``) matrices and tables, unit scales."""
        leaves, treedef = jax.tree_util.tree_flatten_with_path(
            self.param_shapes(), is_leaf=lambda x: isinstance(x, tuple))
        std = self.config.initializer_range
        out = [jnp.ones(shape, jnp.float32) if path[-1].key == "scale"
               else std * jax.random.normal(jax.random.fold_in(rng, i),
                                            shape, jnp.float32)
               for i, (path, shape) in enumerate(leaves)]
        return jax.tree_util.tree_unflatten(treedef, out)

    def partition_specs(self, mesh):
        """Replicated: the share is the chip's own (its experts, its rows
        of the vocabulary); ZeRO shards the optimizer's flat state."""
        return jax.tree_util.tree_map(
            lambda _: P(), self.param_shapes(),
            is_leaf=lambda x: isinstance(x, tuple))

    # -- one layer ---------------------------------------------------------
    def _attention(self, lp, x, kind, tables):
        c = self.config
        b, s, _ = x.shape
        d, dtype = c.head_dim, lp["qkv"]["kernel"].dtype
        q_width = c.num_attention_heads * d
        kv_width = c.num_key_value_heads * d
        h = rms_norm(lp["input_norm"], x, c.rms_norm_eps).astype(dtype)
        qkv = h @ lp["qkv"]["kernel"]
        cos, sin = tables[kind]
        q = rms_norm(lp["q_norm"], qkv[..., :q_width].reshape(
            b, s, -1, d).astype(jnp.float32), c.rms_norm_eps)
        k = rms_norm(lp["k_norm"], qkv[..., q_width:q_width + kv_width]
                     .reshape(b, s, -1, d).astype(jnp.float32),
                     c.rms_norm_eps)
        q = rotate(q, cos, sin).astype(dtype)
        k = rotate(k, cos, sin).astype(dtype)
        v = qkv[..., q_width + kv_width:].reshape(b, s, -1, d)
        window = c.sliding_window if kind == WINDOW else None
        if window is not None and window >= s:
            window = None   # every causal key is inside it
        block = math.gcd(c.attn_block[kind == FULL], s)
        ctx = flash_attention(q, k, v, None, None, True, block, block,
                              self.interpret, 0.0, window)
        return jnp.matmul(ctx.reshape(b, s, q_width),
                          lp["attn_out"]["kernel"],
                          preferred_element_type=jnp.float32)

    def _moe(self, lp, x):
        """The expert layer's part of the stream (fp32) and what it
        reports: ``(aux loss, pairs a held expert and elsewhere, passes,
        share of the tokens with no expert here, share of the rows
        moved)``."""
        c, moe = self.config, lp["moe"]
        b, s, hidden = x.shape
        g32 = rms_norm(lp["post_norm"], x, c.rms_norm_eps).reshape(
            b * s, hidden)
        valid = jnp.ones((b * s,), bool)
        with jax.named_scope("router"):
            scores = expert_shard.router_scores(
                g32, moe["router"]["kernel"], "softmax")
            weights, ids = expert_shard.choose_experts(
                scores, n_group=1, topk_group=1,
                top_k=c.num_experts_per_tok, scaling=1.0,
                renormalise=c.norm_topk_prob)
        with jax.named_scope("aux_loss"):
            # a sequence at a time, as a data-parallel chip sees its own
            aux = jnp.mean(jax.vmap(
                lambda p, i: expert_shard.aux_load_balance(
                    p, i, c.num_experts))(
                scores.reshape(b, s, -1), ids.reshape(b, s, -1)))
        with jax.named_scope("experts"):
            y, counts = expert_shard.held_experts_ffn(
                g32.astype(moe["experts"]["down"].dtype), weights, ids,
                valid, moe["experts"], first_expert=c.first_expert,
                interpret=self.interpret, tiling=c.expert_tiling,
                routed=c.num_experts, reverse=True)
            passes = expert_shard.pair_passes(
                counts, ids.size, c.num_experts, c.expert_tiling[0])
            nowhere = expert_shard.tokens_without_held_expert(
                ids, valid, c.first_expert, c.experts_held)
            moved = expert_shard.rows_moved_share(
                counts, ids.size, c.num_experts, c.expert_tiling[0])
        return y.reshape(b, s, hidden), (aux, counts, passes, nowhere, moved)

    def _layer(self, lp, x, kind, tables):
        with jax.named_scope("attention"):
            x = x + self._attention(lp, x, kind, tables)
        with jax.named_scope("moe"):
            y, reports = self._moe(lp, x)
        return x + y, jax.lax.stop_gradient(reports[1:]), reports[0]

    # -- the trunk ---------------------------------------------------------
    def hidden(self, params, input_ids):
        """``(x [rows, seq, hidden] after the final norm, in the compute
        dtype; the layers' mean auxiliary loss; their load reports)``."""
        c = self.config
        s = input_ids.shape[1]
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], input_ids, axis=0).astype(
                jnp.float32)
        tables = {kind: rotary_tables(c, kind, s)
                  for kind in set(c.layer_types)}
        auxes, loads = [], []
        for i, kind in enumerate(c.layer_types):
            def run(lp, x, kind=kind):
                return self._layer(lp, x, kind, tables)

            if c.remat:
                from ..runtime.activation_checkpointing import (
                    checkpointing as ds_ckpt)

                if ds_ckpt.should_checkpoint_layer(i, c.num_hidden_layers):
                    run = ds_ckpt.checkpoint_wrapper(
                        run, save_names=SAVED_NAMES)
            with jax.named_scope(f"layer_{i}"):
                x, load, aux = run(params["layers"][f"layer_{i}"], x)
            auxes.append(aux)
            loads.append(load)
        with jax.named_scope("final_norm"):
            x = rms_norm(params["final_norm"], x, c.rms_norm_eps).astype(
                params["lm_head"]["kernel"].dtype)
        return x, sum(auxes) / len(auxes), loads

    def _reports(self, aux, loads):
        """The expert layers' counters as the step's device scalars."""
        counts = jnp.stack([load[0] for load in loads]).astype(jnp.float32)
        held = counts[:, :-1]
        return {
            "training/moe_expert_load_max_over_mean": jnp.max(
                held.max(axis=1) / jnp.maximum(held.mean(axis=1), 1e-9)),
            "training/moe_local_assignment_share":
                held.sum() / jnp.maximum(counts.sum(), 1.0),
            "training/moe_pair_passes": jnp.max(jnp.stack(
                [load[1] for load in loads])).astype(jnp.float32),
            "training/moe_tokens_without_local_expert": jnp.mean(jnp.stack(
                [load[2] for load in loads])),
            "training/moe_rows_moved_share": jnp.mean(jnp.stack(
                [load[3] for load in loads])),
            "training/moe_aux_loss": aux,
        }

    @staticmethod
    def _lm_head(params, x):
        with jax.named_scope("lm_head"):
            return jnp.matmul(x, params["lm_head"]["kernel"],
                              preferred_element_type=jnp.float32)

    def logits(self, params, input_ids, rng=None, deterministic=True):
        return self._lm_head(params, self.hidden(params, input_ids)[0])

    def _lm_loss(self, params, x, labels):
        """Mean cross-entropy over the labelled positions; with
        ``loss_chunk`` over chunks of that many positions a row, each
        chunk's ``[rows, chunk, vocab]`` logits living inside one step of
        ``layers.chunked_lm_loss``'s loop, which makes the chunk's
        gradient in that same step."""
        chunk = self.config.loss_chunk
        if not chunk or x.shape[1] % chunk:
            chunk = x.shape[1]
        return chunked_lm_loss(self._lm_head, {"lm_head": params["lm_head"]},
                               x, labels, chunk)

    def apply_reporting(self, params, batch, rng=None, train=True, **kw):
        """``(loss, {name: device scalar})``: what :meth:`apply` returns
        for a batch with a loss, and the expert layers' counters."""
        input_ids = batch["input_ids"] if isinstance(batch, dict) else batch
        x, aux, loads = self.hidden(params, input_ids)
        if isinstance(batch, dict) and "labels" in batch:
            labels = batch["labels"]
        else:
            labels = jnp.concatenate(
                [input_ids[:, 1:], jnp.full((input_ids.shape[0], 1), -100,
                                            input_ids.dtype)], axis=1)
        loss = self._lm_loss(params, x, labels)
        if train:   # the regulariser trains; an eval loss is the model's
            loss = loss + self.config.router_aux_loss_coef * aux
        return loss, self._reports(aux, loads)

    def apply(self, params, batch, rng=None, train=True, **kw):
        if not train and not (isinstance(batch, dict) and "labels" in batch):
            ids = batch["input_ids"] if isinstance(batch, dict) else batch
            return self.logits(params, ids)
        return self.apply_reporting(params, batch, rng, train)[0]
