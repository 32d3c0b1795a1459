"""MiniCPM-SALA (``model_type`` ``minicpm_sala``), for serving: block-selected
sparse attention layers (``minicpm4``) beside lightning linear-attention
layers (``lightning-attn``) in the published 1 : 3 mix, gated-SiLU MLPs, an
untied head, muP scalings.

Written from the published ``config.json`` of openbmb/MiniCPM-SALA (every
width, head count and switch, ``mixer_types``, ``scale_emb`` / ``scale_depth``
/ ``dim_model_base`` / ``mup_denominator``) and two conventions of the families
it names, ASSUMED and said so in the benchmark's configuration file: the
sparse mixer's ``sparse_config`` is MiniCPM4's InfLLM-V2 (arXiv:2506.07900,
arXiv:2509.24663: kernel 32, stride 16, block 64, top-k 64, 1 initial block,
window 2048, ``dense_len`` 8192), the linear mixer's decay Lightning
Attention's ALiBi slopes (arXiv:2401.04658).  The stream:

    x_0 = scale_emb * E[token]
    x <- x + a * Mixer(RMSNorm(x)),  x <- x + a * MLP(RMSNorm(x)),  a = scale_depth / sqrt(mup_denominator)
    logits = W_head RMSNorm(x_L) / (hidden_size / dim_model_base)

``a`` divides by the PUBLISHED depth (``mup_denominator`` 32) whatever number
of layers a chip holds.  Per layer ``n`` of type ``mixer_types[n]``, ``u =
RMSNorm(x)``:

- ``lightning-attn``: ``[q ; k ; v ; gate] = u W_QKVG`` (32 heads of 128
  each), q and k through RMSNorm over a head's 128 values (one ``[128]``
  scale each) and rotated by position (theta 1e4, the whole head); the
  recurrence ``S_t = lambda_h S_{t-1} + k_t^T v_t``, ``o_t = q_t S_t /
  sqrt(128)`` (``ops/transformer/lightning_attention.py``); ``y = W_o(norm_h(o)
  * sigmoid(gate))``.  What a request keeps is ``S`` of every head, float32:
  ONE block of the ``state`` cache group (``inference/kv_cache.py``), written
  whole by prefill, read and rewritten in place by every decode step.
- ``minicpm4``: 32 query heads over 2 KV heads of 128, no rotary, no QK-norm;
  a context longer than ``dense_len`` attends to 64 chosen blocks of 64 keys
  (``ops/transformer/sparse_attention.py`` has the rule), a shorter one to
  everything; ``y = W_o(o * sigmoid(gate))``.  What a token keeps is its K and
  V row (the ``kv`` group's pages, the whole context) and, every 16 tokens,
  one compressed key (the ``ckeys`` group: the mean of the last 32 cached
  keys, row ``j % block`` of the request's page ``j // block``).  The
  compressed keys have a table of their own — a fixed grant of
  ``ceil(kernels(max_seq_len) / block)`` pages a request — and not the K/V
  table's: four keys a K/V page would be a 2 KB row fetched alone, 304
  fetches a slot and step where 19 pages of 32 KB hold the same keys dense.
- precision as the other served models: the residual stream, the norms, the
  softmaxes, the block scores and the state in float32, every other product
  in the weights' dtype with a float32 accumulator.

Parameter tree: ``embed``, ``layers/layer_<i>/{input_norm, qkvg, o, post_norm,
mlp/{gate_up, down}}`` and, on lightning layers, ``q_norm``, ``k_norm``,
``o_norm``; ``final_norm``, ``lm_head``; every matrix a ``kernel [in, out]``
with no bias.  ``qkvg`` is q's heads, then k's, then v's, then the gate.
"""

import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp

from ..inference.kv_cache import NULL_BLOCK, CacheGroup
from ..ops.transformer import lightning_attention as lightning
from ..ops.transformer import sparse_attention as sparse
from ..ops.transformer.paged_attention import check_gqa_tpu_geometry
from ..parallel.mesh import current_platform
from .exaone_moe import rotate
from .layers import gated_silu_mlp, rms_norm

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"

# MiniCPM4's published ``sparse_config`` (InfLLM-V2)
SPARSE_CONFIG = dict(kernel_size=32, kernel_stride=16, block_size=64,
                     topk=64, init_blocks=1, window_size=2048,
                     dense_len=8192)


class MiniCPMSALAConfig:
    """The published ``config.json`` keys that shape the model.
    ``mixer_types`` lists the layers run (a chip's slice of the published
    list); ``mup_denominator`` stays the published depth."""

    def __init__(self, vocab_size=73448, hidden_size=4096,
                 num_hidden_layers=32, num_attention_heads=32,
                 num_key_value_heads=2, head_dim=128,
                 intermediate_size=16384, lightning_nh=32, lightning_nkv=32,
                 lightning_head_dim=128, mixer_types=None,
                 sparse_config=None, rms_norm_eps=1e-6, rope_theta=1e4,
                 scale_emb=12, scale_depth=1.4, dim_model_base=256,
                 mup_denominator=32, max_position_embeddings=524288,
                 initializer_range=0.02):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.intermediate_size = intermediate_size
        self.lightning_nh = lightning_nh
        self.lightning_head_dim = lightning_head_dim
        self.mixer_types = list(mixer_types or (
            [SPARSE] + [LIGHTNING] * 3) * num_hidden_layers)[
                :num_hidden_layers]
        self.sparse_config = dict(sparse_config or SPARSE_CONFIG)
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = float(rope_theta)
        self.scale_emb = float(scale_emb)
        self.scale_depth = float(scale_depth)
        self.dim_model_base = dim_model_base
        self.mup_denominator = mup_denominator
        self.max_position_embeddings = max_position_embeddings
        self.initializer_range = initializer_range
        assert len(self.mixer_types) == num_hidden_layers
        assert set(self.mixer_types) <= {SPARSE, LIGHTNING}
        assert lightning_nkv == lightning_nh, "lightning heads are not grouped"
        assert num_attention_heads % num_key_value_heads == 0

    @property
    def residual_scale(self):
        return self.scale_depth / math.sqrt(self.mup_denominator)

    @property
    def logit_divisor(self):
        return self.hidden_size / self.dim_model_base

    @property
    def kv_row(self):
        """What one token caches in a sparse layer, once for K, once for V."""
        return self.num_key_value_heads * self.head_dim

    def layers_of(self, kind):
        return [i for i, t in enumerate(self.mixer_types) if t == kind]


class MiniCPMSALAForServing:
    """The served model: its configuration, the shapes of its parameter
    tree, and the serving programs (:meth:`serving`)."""

    def __init__(self, config: MiniCPMSALAConfig):
        self.config = config

    def param_shapes(self):
        c = self.config
        h = c.hidden_size

        def layer(kind):
            out = {"input_norm": {"scale": (h,)},
                   "post_norm": {"scale": (h,)},
                   "mlp": {"gate_up": {"kernel": (h, 2 * c.intermediate_size)},
                           "down": {"kernel": (c.intermediate_size, h)}}}
            if kind == SPARSE:
                q_width = c.num_attention_heads * c.head_dim
                out["qkvg"] = {"kernel": (h, 2 * q_width + 2 * c.kv_row)}
                out["o"] = {"kernel": (q_width, h)}
            else:
                width, d = c.lightning_nh * c.lightning_head_dim, \
                    c.lightning_head_dim
                out["qkvg"] = {"kernel": (h, 4 * width)}
                out["o"] = {"kernel": (width, h)}
                for name in ("q_norm", "k_norm", "o_norm"):
                    out[name] = {"scale": (d,)}
            return out

        return {"embed": (c.vocab_size, h),
                "layers": {f"layer_{i}": layer(kind)
                           for i, kind in enumerate(c.mixer_types)},
                "final_norm": {"scale": (h,)},
                "lm_head": {"kernel": (h, c.vocab_size)}}

    def serving(self):
        return MiniCPMSALAServing(self.config)


def greedy(logits):
    """The served token: the largest logit."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


class MiniCPMSALAServing:
    """MiniCPM-SALA's side of :class:`~deepspeed_tpu.inference.engine.
    InferenceEngine`'s model interface (``inference/model.py``): the sparse
    layers' K/V pages (``kv``) and compressed keys (``ckeys``), the lightning
    layers' float32 state (``state``: one block a request), a prefill per
    bucket, a decode."""

    # rows the MLP takes at once in prefill (its [rows, 2 x 16384]
    # intermediate is 268 MB at 4096 rows, 1 GB at a 16,384 bucket), the
    # lightning prefill's chunk, the sparse prefill's flash tiles and the
    # pages the sparse decode kernel multiplies at once
    MLP_ROWS = 4096
    LIGHTNING_CHUNK = 256
    PREFILL_BLOCKS = (256, 512)
    DECODE_PAGES = 8

    def __init__(self, config):
        self.config = config
        self.num_layers = config.num_hidden_layers
        self.interpret = current_platform() != "tpu"
        self.geometry = sparse.SparseGeometry(**config.sparse_config)
        self._rope = SimpleNamespace(head_dim=config.lightning_head_dim,
                                     rope_theta=config.rope_theta)

    def _ck_pages(self, icfg):
        """Pages of compressed keys a request is granted: what a context
        of ``max_seq_len`` can close."""
        return max(-(-self.geometry.kernels(icfg.max_seq_len)
                     // icfg.kv_block_size), 1)

    def cache_groups(self, icfg):
        """``kv`` and ``ckeys`` (the sparse layers), ``state`` (the
        lightning layers); a kind with no layer has no group."""
        c = self.config
        assert icfg.kv_block_size == self.geometry.block_size, (
            "a page is a block of the sparse layers' choice: kv_block_size "
            f"{icfg.kv_block_size} != {self.geometry.block_size}")
        groups = []
        n_sparse = len(c.layers_of(SPARSE))
        if n_sparse:
            groups.append(CacheGroup(
                "kv", n_sparse, {"k_cache": c.kv_row, "v_cache": c.kv_row}))
            groups.append(CacheGroup(
                "ckeys", n_sparse, {"ck_cache": c.kv_row},
                pages=self._ck_pages(icfg)))
        n_lightning = len(c.layers_of(LIGHTNING))
        if n_lightning:
            groups.append(CacheGroup(
                "state", n_lightning,
                {"state": lightning.state_row_width(
                    c.lightning_nh, c.lightning_head_dim,
                    icfg.kv_block_size)},
                pages=1, dtype="float32"))
        return groups

    def cache_buffers(self, icfg):
        return {name: row for group in self.cache_groups(icfg)
                for name, row in group.buffers.items()}

    def check_tpu_geometry(self, icfg):
        c = self.config
        # a KV head's keys are a lane slice of a page, as in the grouped
        # paged kernel
        check_gqa_tpu_geometry(c.num_key_value_heads, c.head_dim,
                               icfg.kv_block_size)
        lightning.check_tpu_geometry(c.lightning_head_dim,
                                     icfg.kv_block_size)

    def prepare_params(self, params):
        return params

    def _places(self, icfg):
        """name of a buffer or group -> its position in ``caches`` /
        ``block_tables``, and layer -> its plane in its group's buffers."""
        groups = self.cache_groups(icfg)
        buffers = {name: i for i, name in enumerate(self.cache_buffers(icfg))}
        tables = {group.name: i for i, group in enumerate(groups)}
        planes = {}
        for kind in (SPARSE, LIGHTNING):
            for n, layer in enumerate(self.config.layers_of(kind)):
                planes[layer] = n
        return buffers, tables, planes

    # -- pieces shared by the two programs --------------------------------
    def _sparse_qkvg(self, lp, u):
        c = self.config
        q_width = c.num_attention_heads * c.head_dim
        qkvg = u @ lp["qkvg"]["kernel"]
        return (qkvg[:, :q_width], qkvg[:, q_width:q_width + c.kv_row],
                qkvg[:, q_width + c.kv_row:q_width + 2 * c.kv_row],
                qkvg[:, q_width + 2 * c.kv_row:])

    def _lightning_qkvg(self, lp, u, positions):
        """``(q, k [tokens, heads, d] float32, normed and rotated, v, the
        gate [tokens, heads * d])``."""
        c = self.config
        tokens, d = u.shape[0], c.lightning_head_dim
        width = c.lightning_nh * d
        qkvg = u @ lp["qkvg"]["kernel"]

        def head(x, norm):
            x = rms_norm(lp[norm], x.reshape(tokens, -1, d).astype(
                jnp.float32), c.rms_norm_eps)
            return rotate(x, positions, self._rope)

        return (head(qkvg[:, :width], "q_norm"),
                head(qkvg[:, width:2 * width], "k_norm"),
                qkvg[:, 2 * width:3 * width].reshape(tokens, -1, d),
                qkvg[:, 3 * width:])

    def _gated_out(self, lp, ctx, gate, dtype):
        """``W_o(ctx * sigmoid(gate))``, the accumulator in float32."""
        y = ctx.astype(jnp.float32) * jax.nn.sigmoid(
            gate.astype(jnp.float32))
        return jnp.matmul(y.astype(dtype), lp["o"]["kernel"],
                          preferred_element_type=jnp.float32)

    def _lightning_out(self, lp, o, gate, dtype):
        tokens = o.shape[0]
        o = rms_norm(lp["o_norm"], o, self.config.rms_norm_eps)
        return self._gated_out(lp, o.reshape(tokens, -1), gate, dtype)

    def _mlp(self, lp, x, dtype):
        """The layer's MLP of the stream ``x`` (float32), the result in
        float32; rows in blocks where a bucket is long."""
        z = rms_norm(lp["post_norm"], x, self.config.rms_norm_eps).astype(
            dtype)
        rows = z.shape[0]
        block = math.gcd(rows, self.MLP_ROWS)
        if block == rows:
            return gated_silu_mlp(lp["mlp"], z, jnp.float32)
        return jax.lax.map(
            lambda zb: gated_silu_mlp(lp["mlp"], zb, jnp.float32),
            z.reshape(rows // block, block, -1)).reshape(rows, -1)

    def logits(self, params, x):
        head = params["lm_head"]["kernel"]
        with jax.named_scope("final_norm"):
            x = rms_norm(params["final_norm"], x, self.config.rms_norm_eps)
        with jax.named_scope("lm_head"):
            return jnp.matmul(x.astype(head.dtype), head,
                              preferred_element_type=jnp.float32) \
                / self.config.logit_divisor

    def _next_token(self, params, x):
        logits = self.logits(params, x)
        with jax.named_scope("sample"):
            return greedy(logits)

    # -- the two programs --------------------------------------------------
    def build_prefill(self, icfg, bucket_len):
        """``(params, caches, input_ids[1, S], true_len, block_tables,
        next_tokens, slot) -> (out, caches, next_tokens)``: one request
        padded to the bucket; its first token is also put into lane
        ``slot`` of the next decode's input."""
        c, g = self.config, self.geometry
        bs = icfg.kv_block_size
        n_pages = bucket_len // bs
        assert bucket_len % bs == 0 and bucket_len % g.kernel_stride == 0
        buffers, tables, planes = self._places(icfg)
        a = c.residual_scale

        def sparse_layer(lp, u, caches, plane, block_tables, true_len):
            s = u.shape[0]
            q, k, v, gate = self._sparse_qkvg(lp, u)
            at_k, at_v = buffers["k_cache"], buffers["v_cache"]
            # what is cached is what is attended to and compressed
            k, v = (rows.astype(caches[at].dtype)
                    for rows, at in ((k, at_k), (v, at_v)))
            ids = block_tables[tables["kv"]][:n_pages]
            for at, rows in ((at_k, k), (at_v, v)):
                caches[at] = caches[at].at[plane, ids].set(
                    rows.reshape(n_pages, bs, -1), unique_indices=True)
            # ``sparse_select`` is the choice alone: compress, score, count
            # out the best blocks (the projection and the cache writes
            # stand under ``attention`` itself)
            with jax.named_scope("sparse_select"):
                ck = sparse.compress_keys(k, g).astype(k.dtype)
            # every compressed key of the bucket, the grant's pages whole:
            # those the padding reaches are written again by the decode
            # step that closes them
            at_ck = buffers["ck_cache"]
            ck_rows = self._ck_pages(icfg) * bs
            caches[at_ck] = caches[at_ck].at[
                plane, block_tables[tables["ckeys"]]].set(
                    jnp.pad(ck, ((0, ck_rows - ck.shape[0]), (0, 0)))
                    .reshape(-1, bs, ck.shape[1]), unique_indices=True)
            with jax.named_scope("sparse_select"):
                mask = sparse.prefill_block_mask(
                    q.reshape(s, c.num_attention_heads, c.head_dim),
                    ck.reshape(-1, c.num_key_value_heads, c.head_dim),
                    true_len, g, kv_heads=c.num_key_value_heads)
            with jax.named_scope("sparse_attention"):
                ctx = sparse.sparse_prefill_attention(
                    q, k, v, mask, kv_heads=c.num_key_value_heads,
                    block_size=bs, block_q=self.PREFILL_BLOCKS[0],
                    block_k=self.PREFILL_BLOCKS[1], interpret=self.interpret)
                return self._gated_out(lp, ctx, gate, u.dtype)

        def lightning_layer(lp, u, caches, plane, block_tables, true_len,
                            positions):
            s = u.shape[0]
            with jax.named_scope("lightning"):
                q, k, v, gate = self._lightning_qkvg(lp, u, positions)
                with jax.named_scope("state_update"):
                    o, state = lightning.lightning_prefill_scan(
                        q.reshape(s, -1).astype(u.dtype),
                        k.reshape(s, -1).astype(u.dtype), v.reshape(s, -1),
                        true_len,
                        heads=c.lightning_nh, chunk=self.LIGHTNING_CHUNK,
                        interpret=self.interpret)
                    at = buffers["state"]
                    caches[at] = caches[at].at[
                        plane, block_tables[tables["state"]][0]].set(
                            lightning.state_to_block(state, bs))
                return self._lightning_out(
                    lp, o.reshape(s, c.lightning_nh, -1), gate, u.dtype)

        def prefill(params, caches, input_ids, true_len, block_tables,
                    next_tokens, slot):
            caches = list(caches)
            s = input_ids.shape[1]
            positions = jnp.arange(s)
            dtype = params["embed"].dtype
            with jax.named_scope("embed"):
                x = c.scale_emb * jnp.take(
                    params["embed"], input_ids[0], axis=0).astype(jnp.float32)
            for i, kind in enumerate(c.mixer_types):
                lp = params["layers"][f"layer_{i}"]
                with jax.named_scope(f"layer_{i}"):
                    with jax.named_scope("attention"):
                        u = rms_norm(lp["input_norm"], x,
                                     c.rms_norm_eps).astype(dtype)
                        if kind == SPARSE:
                            y = sparse_layer(lp, u, caches, planes[i],
                                             block_tables, true_len)
                        else:
                            y = lightning_layer(lp, u, caches, planes[i],
                                                block_tables, true_len,
                                                positions)
                        x = x + a * y
                    with jax.named_scope("mlp"):
                        x = x + a * self._mlp(lp, x, dtype)
            last = jax.lax.dynamic_slice(x, (true_len - 1, 0),
                                         (1, c.hidden_size))
            token = self._next_token(params, last)[0]
            with jax.named_scope("sample"):
                next_tokens = next_tokens.at[slot].set(token)
            return {"tokens": token}, tuple(caches), next_tokens

        return prefill

    def build_decode(self, icfg):
        """``(params, caches, block_tables, ctx_lens, tokens) -> (out,
        caches)`` for the fixed ``max_batch_slots``-wide batch.  ``out``
        carries the next tokens and, in the same fetch, the pages a sparse
        layer read against the pages the contexts hold."""
        c, g = self.config, self.geometry
        bs = icfg.kv_block_size
        n_slots = icfg.max_batch_slots
        buffers, tables, planes = self._places(icfg)
        a = c.residual_scale

        def sparse_layer(lp, u, caches, plane, block_tables, ctx_lens,
                         target):
            kv_table = block_tables[tables["kv"]]
            ck_table = block_tables[tables["ckeys"]]
            at_k, at_v, at_ck = (buffers[n] for n in (
                "k_cache", "v_cache", "ck_cache"))
            q, k, v, gate = self._sparse_qkvg(lp, u)
            # the append: every slot's new row in one scatter a buffer
            for at, rows in ((at_k, k), (at_v, v)):
                caches[at] = caches[at].at[plane, target[0], target[1]].set(
                    rows.astype(caches[at].dtype))
            # the choice alone: the compressed key this step closes, the
            # scores, the best blocks counted out and listed
            with jax.named_scope("sparse_select"):
                # the compressed key this token closes, if it closes one:
                # the mean of the last kernel_size cached keys, its own
                # among them; a slot that closes none writes the null block
                closes = (ctx_lens + 1 >= g.kernel_size) & (
                    (ctx_lens + 1 - g.kernel_size) % g.kernel_stride == 0)
                last = jnp.maximum(
                    ctx_lens[:, None] - jnp.arange(g.kernel_size)[None, :], 0)
                keys = caches[at_k][
                    plane,
                    jnp.take_along_axis(kv_table, last // bs, axis=1),
                    last % bs]
                ck = keys.astype(jnp.float32).mean(axis=1)
                j = jnp.maximum(ctx_lens + 1 - g.kernel_size,
                                0) // g.kernel_stride
                page = jnp.take_along_axis(
                    ck_table, (j // bs)[:, None], axis=1)[:, 0]
                caches[at_ck] = caches[at_ck].at[
                    plane, jnp.where(closes, page, NULL_BLOCK),
                    jnp.where(closes, j % bs, 0)].set(
                        ck.astype(caches[at_ck].dtype))
                r = sparse.sparse_block_select(
                    q.reshape(n_slots, c.num_attention_heads, c.head_dim),
                    caches[at_ck], ck_table, ctx_lens, layer=plane,
                    kv_heads=c.num_key_value_heads, geometry=g,
                    interpret=self.interpret)
                chosen, counts = sparse.choose_decode_blocks(
                    r, ctx_lens, g, kv_table.shape[1])
            with jax.named_scope("sparse_attention"):
                ctx = sparse.sparse_paged_decode_attention(
                    q, caches[at_k], caches[at_v], kv_table, ctx_lens,
                    chosen, counts, layer=plane,
                    num_heads=c.num_attention_heads,
                    pages_per_step=self.DECODE_PAGES,
                    interpret=self.interpret)
                return self._gated_out(lp, ctx, gate, u.dtype), counts

        def lightning_layer(lp, u, caches, plane, block_tables, ctx_lens):
            with jax.named_scope("lightning"):
                q, k, v, gate = self._lightning_qkvg(lp, u, ctx_lens)
                with jax.named_scope("state_update"):
                    at = buffers["state"]
                    o, caches[at] = lightning.lightning_decode_update(
                        q, k, v, caches[at],
                        block_tables[tables["state"]][:, 0], layer=plane,
                        interpret=self.interpret)
                return self._lightning_out(lp, o, gate, u.dtype)

        def decode(params, caches, block_tables, ctx_lens, tokens):
            caches = list(caches)
            dtype = params["embed"].dtype
            with jax.named_scope("embed"):
                x = c.scale_emb * jnp.take(
                    params["embed"], tokens, axis=0).astype(jnp.float32)
                target = None
                if "kv" in tables:
                    # where the new token's K and V rows go
                    target = (jnp.take_along_axis(
                        block_tables[tables["kv"]],
                        (ctx_lens // bs)[:, None], axis=1)[:, 0],
                        ctx_lens % bs)
            read = []
            for i, kind in enumerate(c.mixer_types):
                lp = params["layers"][f"layer_{i}"]
                with jax.named_scope(f"layer_{i}"):
                    with jax.named_scope("attention"):
                        u = rms_norm(lp["input_norm"], x,
                                     c.rms_norm_eps).astype(dtype)
                        if kind == SPARSE:
                            y, counts = sparse_layer(
                                lp, u, caches, planes[i], block_tables,
                                ctx_lens, target)
                            read.append(counts)
                        else:
                            y = lightning_layer(lp, u, caches, planes[i],
                                                block_tables, ctx_lens)
                        x = x + a * y
                    with jax.named_scope("mlp"):
                        x = x + a * self._mlp(lp, x, dtype)
            out = {"tokens": self._next_token(params, x)}
            if read:
                with jax.named_scope("sample"):
                    # over the slots that serve a request (a dead one is
                    # parked at position 0), the sparse layers and the KV
                    # heads: pages read, pages the context holds
                    live = (ctx_lens > 0).astype(jnp.float32)
                    slots = jnp.maximum(live.sum(), 1.0)
                    pages_read = sum(
                        (n.astype(jnp.float32).mean(axis=1) * live).sum()
                        for n in read) / (len(read) * slots)
                    pages_live = ((ctx_lens // bs + 1) * live).sum() / slots
                out["sparse_pages_read_mean"] = pages_read
                out["sparse_pages_live_mean"] = pages_live
                out["sparse_read_share"] = pages_read / pages_live
            return out, tuple(caches)

        return decode
