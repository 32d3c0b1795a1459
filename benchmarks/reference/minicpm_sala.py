"""MiniCPM-SALA (``model_type`` ``minicpm_sala``), plain: the full forward over
whole sequences in float32 at ``highest``, no cache, no chunks, no kernels,
nothing from the program.  Written from the published ``config.json`` of
openbmb/MiniCPM-SALA (every width, head count and switch) and two family
conventions the config does not spell out, ASSUMED and listed in the
configuration file: the sparse mixer's block sizes are MiniCPM4's InfLLM-V2
``sparse_config`` (arXiv:2506.07900 section 2.2, arXiv:2509.24663), the
linear mixer's decay Lightning Attention's ALiBi slopes (arXiv:2401.04658).

Stream (muP):  x_0 = scale_emb * E[token];  per layer, with a = scale_depth /
sqrt(mup_denominator) (the PUBLISHED depth 32, not the layers held):

    x <- x + a * Mixer(RMSNorm(x; g_in))        x <- x + a * MLP(RMSNorm(x; g_post))
    MLP(u) = W_down(silu(W_gate u) * W_up u)
    logits = W_head RMSNorm(x_L; g_f) / (hidden_size / dim_model_base)

``lightning-attn`` (32 heads h of d = 128):  q = rope(norm_h(W_q u)),  k =
rope(norm_h(W_k u)),  v = W_v u  (norm_h: RMSNorm over a head's 128 values with
one [128] scale; rope theta 1e4, pairs (c, c + d/2), the whole head);

    S_t = lambda_h S_{t-1} + k_t^T v_t  in R^{d x d},  S_{-1} = 0,   lambda_h = exp(-2^{-8(h+1)/32})
    o_t = q_t S_t / sqrt(d)            y = W_o(norm_h(o_t) * sigmoid(W_g u))

computed as the literal recurrence, one position after another (``lax.scan``).

``minicpm4`` (32 query heads over 2 KV heads g of 128, 16 query heads a KV
head; no rotary, no QK-norm), for query position t of a request whose context
is n tokens (n = the prompt's length while t is a prompt position, t + 1 after
it).  n <= dense_len: plain causal attention.  n > dense_len, per KV head g:

    c_j = mean(k_{16j} .. k_{16j+31})   for every j with 16j + 31 <= t      (kernel 32, stride 16)
    p^h_{t,j} = softmax_j(q^h_t . c_j / sqrt(d)),   r_{t,j} = sum_{h in g} p^h_{t,j}
    R_{t,b} = max{ r_{t,j} : kernel j overlaps block b = tokens [64b, 64b + 64) }   (4b-1 <= j <= 4b+3)
    B_t = block 0, every block that meets [t - 2047, t], then the highest R_{t,b} of the rest
          until |B_t| = 64 (every visible block while there are at most 64)
    o^h_t = sum_i softmax_i(q^h_t . k_i / sqrt(d)) v_i   over i <= t with floor(i / 64) in B_t
    y = W_o(o_t * sigmoid(W_g u))

Departures:
- q, k, v and the output gate are read out of one fused ``qkvg`` kernel (q's
  heads first, then k, v, then the gate); gate and up out of one ``gate_up``.
- ``position_logits`` is not told a row's prompt length; it takes the first
  judged column of a row as the prompt's last position (what
  ``benchmarks/serve.py::reference_gaps`` hands it: a request's columns start
  at ``len(prompt) - 1``; its padding entries are (row 0, column 0) after the
  first entry).
- the choice's margin.  The choice of blocks is discrete, like a router's:
  where the last block chosen and the first left out stand within a hair of
  each other a sound program and this reference may choose differently.
  The reference works out, per position and sparse layer, the margin by
  which its own choice stands — ``(R_last_in - R_first_out) / R_last_in``
  over the blocks that are not forced, the least over the KV heads,
  infinite where nothing visible is left out — for the tests that hold the
  program's chosen sets to ``chosen_blocks`` (they leave the near-ties
  out).  ``position_logits`` takes no notice of it: every position is
  judged.
- the control's return.  18,432 positions x 73,448 float32 logits are
  5.4 GB, and ``benchmarks/serve.py::reference_gaps`` holds the float32
  pass's while the float8 pass runs: two such sets do not fit a chip beside
  the weights and a row's forward.  Of the lower precision's logits the
  harness takes ``argmax(axis=-1)`` and nothing else, so under any ``mm``
  but float32's ``position_logits`` returns that argmax as int8 one-hot rows
  (1.35 GB), its head taken 2,048 positions at a time.
- sequences are walked one row at a time, query rows in blocks, so that
  19,456 positions of float32 fit beside the weights; weights are read in the
  dtype they are stored in and widened to float32 where they are used.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import ops

ROW_BLOCK = 2048        # rows a projection or an MLP takes at once
QUERY_BLOCK = 64        # query rows the sparse layer scores at once
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def rms_norm(p, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) \
        * p["scale"].astype(jnp.float32)


def project(p, u, mm, first, last):
    """Columns ``first:last`` of the fused kernel ``p`` applied to ``u``,
    rows in blocks: one part of a fused projection at a time (the whole
    ``[19456, 16384]`` float32 product would be 1.3 GB)."""
    part = {"kernel": p["kernel"][:, first:last]}
    return by_rows(lambda rows: mm(rows, _w(part, rows)), u)


def _w(p, rows):
    """The kernel widened to float32 where it meets the block ``rows``.  The
    barrier ties the widening to the block, so that it stays inside the
    loop over blocks: widened once for all blocks and rows, the four layers
    and the head would be 6.8 GB held from the first row to the last."""
    kernel, _ = jax.lax.optimization_barrier((p["kernel"], rows))
    return kernel.astype(jnp.float32)


def by_rows(fn, x, block=ROW_BLOCK):
    """``fn`` over ``x [seq, .]`` in blocks of rows, one after another."""
    seq = x.shape[0]
    block = math.gcd(seq, block)
    out = jax.lax.map(fn, x.reshape(seq // block, block, -1))
    return out.reshape(seq, -1)


def residual_scale(cfg):
    return cfg["scale_depth"] / math.sqrt(cfg["mup_denominator"])


def decays(cfg):
    """lambda_h of every lightning head, the same in every layer."""
    heads = cfg["lightning_nh"]
    slopes = 2.0 ** (-8.0 * (np.arange(heads) + 1) / heads)
    return np.exp(-slopes).astype(np.float32)


def rotate(x, cfg):
    """``x [seq, heads, d]`` rotated by its position: value ``c`` and value
    ``c + d/2`` turn together by ``p * theta^(-2c/d)``."""
    d = x.shape[-1]
    inv_freq = 1.0 / float(cfg["rope_theta"]) ** (
        np.arange(0, d, 2, dtype=np.float64) / d)
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


# -- the lightning layer ------------------------------------------------------

def lightning_qkvg(p, u, cfg, mm):
    heads, d = cfg["lightning_nh"], cfg["lightning_head_dim"]
    s, eps = u.shape[0], cfg["rms_norm_eps"]
    width = heads * d
    q, k, v, gate = (project(p["qkvg"], u, mm, n * width, (n + 1) * width)
                     for n in range(4))
    q = rotate(rms_norm(p["q_norm"], q.reshape(s, heads, d), eps), cfg)
    k = rotate(rms_norm(p["k_norm"], k.reshape(s, heads, d), eps), cfg)
    return q, k, v.reshape(s, heads, d), gate


def lightning_scan(q, k, v, cfg, state=None):
    """The recurrence over ``[seq, heads, d]``: ``(o [seq, heads, d], S
    [seq, heads, d, d]``'s last).  ``state`` is S before the first
    position (zeros)."""
    heads, d = q.shape[1:]
    lam = jnp.asarray(decays(cfg))[:, None, None]
    if state is None:
        state = jnp.zeros((heads, d, d), jnp.float32)

    def step(state, qkv):
        q_t, k_t, v_t = qkv
        state = lam * state + k_t[:, :, None] * v_t[:, None, :]
        return state, jnp.einsum("hi,hij->hj", q_t, state) / math.sqrt(d)

    state, o = jax.lax.scan(step, state, (q, k, v))
    return o, state


def lightning(p, u, cfg, mm):
    """The mixer's output ``[seq, hidden]`` and the state after the last
    position."""
    s = u.shape[0]
    q, k, v, gate = lightning_qkvg(p, u, cfg, mm)
    o, state = lightning_scan(q, k, v, cfg)
    y = rms_norm(p["o_norm"], o, cfg["rms_norm_eps"]).reshape(s, -1) \
        * jax.nn.sigmoid(gate)
    return by_rows(lambda rows: mm(rows, _w(p["o"], rows)), y), state


# -- the sparse layer ---------------------------------------------------------

def geometry(cfg, seq):
    """(compressed keys a sequence of ``seq`` has, its blocks)."""
    sc = cfg["sparse_config"]
    n_ck = max((seq - sc["kernel_size"]) // sc["kernel_stride"] + 1, 0)
    return n_ck, -(-seq // sc["block_size"])


def compressed_keys(k, cfg):
    """``c_j [n_ck, kv_heads, d]``: the means of the windows of
    ``kernel_size`` keys every ``kernel_stride``."""
    sc = cfg["sparse_config"]
    n_ck, _ = geometry(cfg, k.shape[0])
    at = (jnp.arange(n_ck) * sc["kernel_stride"])[:, None] \
        + jnp.arange(sc["kernel_size"])[None, :]
    return k[at].mean(axis=1)


def choose_blocks(q, ck, positions, sparse_mode, cfg, seq):
    """For query rows ``q [rows, heads, d]`` at ``positions [rows]``: the
    chosen blocks ``[kv_heads, rows, blocks]`` (bool) and the choice's
    margin ``[rows]``.  ``sparse_mode [rows]`` False: every visible
    block."""
    sc = cfg["sparse_config"]
    heads, d = q.shape[1:]
    kv_heads = cfg["num_key_value_heads"]
    bs, stride, ksize = (sc["block_size"], sc["kernel_stride"],
                         sc["kernel_size"])
    n_ck, nb = geometry(cfg, seq)
    rows = q.shape[0]
    t = positions
    block = jnp.arange(nb)
    visible = block[None, :] <= (t // bs)[:, None]               # [rows, nb]
    if n_ck == 0:
        return jnp.broadcast_to(visible, (kv_heads, rows, nb)), \
            jnp.full((rows,), jnp.inf)
    j = jnp.arange(n_ck)
    closed = (stride * j + ksize - 1)[None, :] <= t[:, None]     # [rows, n_ck]
    qg = q.reshape(rows, kv_heads, heads // kv_heads, d)
    scores = jnp.einsum("tghd,jgd->gthj", qg, ck) / math.sqrt(d)
    scores = jnp.where(closed[None, :, None, :], scores, -jnp.inf)
    top = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.where(closed[None, :, None, :],
                  jnp.exp(scores - jnp.where(jnp.isfinite(top), top, 0.0)),
                  0.0)
    total = e.sum(axis=-1, keepdims=True)
    p = e / jnp.where(total > 0, total, 1.0)
    r = p.sum(axis=2)                                            # [g, rows, n_ck]
    overlap = ((stride * j[:, None] < bs * (block[None, :] + 1))
               & (stride * j[:, None] + ksize > bs * block[None, :]))
    big_r = jnp.max(jnp.where(overlap[None, None], r[..., None], 0.0),
                    axis=2)                                      # [g, rows, nb]
    first_window = jnp.maximum(t - (sc["window_size"] - 1), 0) // bs
    forced = visible & ((block[None, :] < sc["init_blocks"])
                        | (block[None, :] >= first_window[:, None]))
    adjusted = jnp.where(forced[None], jnp.inf,
                         jnp.where(visible[None], big_r, -jnp.inf))
    k_top = min(sc["topk"], nb)
    values, ids = jax.lax.top_k(adjusted, min(k_top + 1, nb))
    taken = values[..., :k_top] > -jnp.inf
    chosen = jnp.any(jax.nn.one_hot(ids[..., :k_top], nb, dtype=jnp.bool_)
                     & taken[..., None], axis=-2)
    if nb > k_top:
        last_in, first_out = values[..., k_top - 1], values[..., k_top]
        decided = jnp.isfinite(last_in) & jnp.isfinite(first_out)
        margin = jnp.where(decided, (last_in - first_out)
                           / jnp.where(decided, last_in, 1.0), jnp.inf)
        margin = margin.min(axis=0)
    else:
        margin = jnp.full((rows,), jnp.inf)
    mode = sparse_mode[None, :, None]
    return (jnp.where(mode, chosen, visible[None]),
            jnp.where(sparse_mode, margin, jnp.inf))


def sparse_attention(p, u, prompt_len, cfg, mm):
    """The mixer's output ``[seq, hidden]``, the chosen blocks ``[kv_heads,
    seq, blocks]`` and the choice's margin ``[seq]``."""
    sc = cfg["sparse_config"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, s, bs = cfg["head_dim"], u.shape[0], sc["block_size"]
    group = heads // kv_heads
    ends = [0, heads * d, (heads + kv_heads) * d, (heads + 2 * kv_heads) * d,
            (2 * heads + 2 * kv_heads) * d]
    q, k, v, gate = (project(p["qkvg"], u, mm, first, last)
                     for first, last in zip(ends[:-1], ends[1:]))
    q = q.reshape(s, heads, d)
    k, v = k.reshape(s, kv_heads, d), v.reshape(s, kv_heads, d)
    ck = compressed_keys(k, cfg)
    key_pos = jnp.arange(s)
    block_of_key = key_pos // bs

    def query_block(args):
        positions, qb = args
        context = jnp.maximum(prompt_len, positions + 1)
        chosen, margin = choose_blocks(qb, ck, positions,
                                       context > sc["dense_len"], cfg, s)
        allowed = chosen[:, :, block_of_key] \
            & (key_pos[None, None, :] <= positions[None, :, None])
        qg = qb.reshape(-1, kv_heads, group, d)
        scores = jnp.einsum("tghd,sgd->gths", qg, k) / math.sqrt(d)
        probs = jax.nn.softmax(
            jnp.where(allowed[:, :, None, :], scores, -jnp.inf), axis=-1)
        ctx = jnp.einsum("gths,sgd->tghd", probs, v)
        return ctx.reshape(-1, heads * d), chosen, margin

    block = math.gcd(s, QUERY_BLOCK)
    ctx, chosen, margin = jax.lax.map(query_block, (
        key_pos.reshape(-1, block), q.reshape(-1, block, heads, d)))
    y = ctx.reshape(s, heads * d) * jax.nn.sigmoid(gate)
    chosen = chosen.transpose(1, 0, 2, 3).reshape(kv_heads, s, -1)
    return (by_rows(lambda rows: mm(rows, _w(p["o"], rows)), y), chosen,
            margin.reshape(s))


# -- the model ----------------------------------------------------------------

def gated_mlp(p, z, mm):
    width = p["down"]["kernel"].shape[0]

    def rows(zb):
        gu = mm(zb, _w(p["gate_up"], zb))
        return mm(jax.nn.silu(gu[:, :width]) * gu[:, width:],
                  _w(p["down"], zb))

    return by_rows(rows, z)


def forward_row(params, row_ids, prompt_len, cfg, mm):
    """One sequence: ``(x after the final norm [seq, hidden], the least
    choice margin [seq], {layer: chosen blocks}, {layer: state})``."""
    eps, a = cfg["rms_norm_eps"], residual_scale(cfg)
    x = cfg["scale_emb"] * params["embed"][row_ids].astype(jnp.float32)
    margin = jnp.full(x.shape[:1], jnp.inf)
    chosen, states = {}, {}
    for n in range(cfg["num_hidden_layers"]):
        p = params["layers"][f"layer_{n}"]
        u = rms_norm(p["input_norm"], x, eps)
        if cfg["mixer_types"][n] == SPARSE:
            y, chosen[n], m = sparse_attention(p, u, prompt_len, cfg, mm)
            margin = jnp.minimum(margin, m)
        else:
            y, states[n] = lightning(p, u, cfg, mm)
        x = x + a * y
        x = x + a * gated_mlp(p["mlp"], rms_norm(p["post_norm"], x, eps), mm)
    return rms_norm(params["final_norm"], x, eps), margin, chosen, states


def head_scale(cfg):
    return cfg["hidden_size"] / cfg["dim_model_base"]


def logits(params, ids, prompt_lens, cfg, mm=ops.matmul):
    """``[rows, seq, vocab]`` of whole sequences ``ids [rows, seq]`` whose
    first ``prompt_lens [rows]`` tokens were the prompt (the tests' sizes
    only: the benchmark picks positions)."""
    with jax.default_matmul_precision("highest"):
        x = jax.lax.map(lambda a: forward_row(params, a[0], a[1], cfg,
                                              mm)[0],
                        (ids, jnp.asarray(prompt_lens)))
        return mm(x, _w(params["lm_head"], x)) / head_scale(cfg)


def states(params, prompt_ids, cfg):
    """``{layer: S [heads, d, d]}`` of every lightning layer after the last
    position of one prompt (the model is causal: a longer sequence's states
    after that position are these)."""
    with jax.default_matmul_precision("highest"):
        return forward_row(params, prompt_ids, prompt_ids.shape[0], cfg,
                           ops.matmul)[3]


def chosen_blocks(params, row_ids, prompt_len, cfg):
    """``({layer: chosen [kv_heads, seq, blocks]}, margin [seq])`` of one
    sequence's sparse layers."""
    with jax.default_matmul_precision("highest"):
        _, margin, chosen, _ = forward_row(params, row_ids, prompt_len, cfg,
                                           ops.matmul)
        return chosen, margin


def real_entries(rows, cols):
    """The harness pads its list of positions with (row 0, column 0)
    entries, which only the first entry can really be."""
    return (jnp.arange(rows.shape[0]) == 0) | (rows != 0) | (cols != 0)


def prompt_lengths(rows, cols, n_rows):
    """A row's prompt length from the judged positions: its first judged
    column is the prompt's last position."""
    real = real_entries(rows, cols)
    first = jnp.full((n_rows,), jnp.iinfo(jnp.int32).max, jnp.int32).at[
        jnp.where(real, rows, n_rows)].min(cols.astype(jnp.int32),
                                           mode="drop")
    return jnp.where(first == jnp.iinfo(jnp.int32).max, 1, first + 1)


def stream_at(params, ids, rows, cols, cfg, mm):
    """The stream after the final norm at the (row, column) positions
    ``[positions, hidden]``: one full forward over every row of ``ids``."""
    prompt_lens = prompt_lengths(rows, cols, ids.shape[0])

    def one_row(x_at, a):
        """The row's stream at ITS judged positions, put beside the earlier
        rows' (every row's whole stream side by side would be 1.9 GB)."""
        r, row_ids, prompt_len = a
        x = forward_row(params, row_ids, prompt_len, cfg, mm)[0]
        mine = rows == r
        return jnp.where(mine[:, None], x[jnp.where(mine, cols, 0)],
                         x_at), None

    return jax.lax.scan(
        one_row, jnp.zeros((rows.shape[0], cfg["hidden_size"]), jnp.float32),
        (jnp.arange(ids.shape[0]), ids, prompt_lens))[0]


def position_logits(params, ids, rows, cols, cfg, mm):
    """Logits at the (row, column) positions of ``ids`` [n, L]: one full
    forward over every row, no cache; every position is judged.  A pass in
    a lower precision (the control, of which the harness reads the argmax
    alone) returns its argmax as int8 one-hot rows, the head taken in blocks
    of positions (module docstring)."""
    with jax.default_matmul_precision("highest"):
        # the divisor goes into the head with the stream (the head is
        # linear): its product is then the result itself
        x = stream_at(params, ids, rows, cols, cfg, mm) / head_scale(cfg)
        if mm is ops.MATMULS["float32"]:
            return mm(x, params["lm_head"]["kernel"].astype(jnp.float32))
        vocab = params["lm_head"]["kernel"].shape[1]
        return by_rows(lambda xb: jax.nn.one_hot(
            jnp.argmax(mm(xb, _w(params["lm_head"], xb)), axis=-1),
            vocab, dtype=jnp.int8), x)
