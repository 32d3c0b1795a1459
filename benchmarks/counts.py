"""Operations and bytes the algorithms need, computed from shapes.

Exact model FLOPs (a multiply-add is 2) and *lower bounds* of bytes, so
that no share built on them can pass 100%.  Forward + backward is three
times the forward's matrix multiplications; recomputation is not counted.
"""


def _layer_matmul_flops_per_token(hidden, intermediate):
    # QKV (h x 3h), attention output (h x h), FFN (h x i, i x h)
    return 2 * (3 * hidden * hidden + hidden * hidden
                + 2 * hidden * intermediate)


def _attention_flops(batch, heads, q_len, k_len, head_dim, causal):
    """QK^T and PV of one attention call, forward: 4 b h q k d; a causal
    mask halves the work that has to be done."""
    flops = 4 * batch * heads * q_len * k_len * head_dim
    return flops // 2 if causal else flops


def bert_train_flops_per_step(cfg, batch, seq, n_pred):
    """BERT pretraining step (fwd + bwd).  The MLM head runs on the
    ``n_pred`` labelled positions of a row only; the last encoder layer's
    queries may be gathered too, which this count does NOT credit (it
    counts the full last layer: what the model needs by its definition)."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    layers, heads = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    tokens = batch * seq
    fwd = layers * (tokens * _layer_matmul_flops_per_token(h, i)
                    + _attention_flops(batch, heads, seq, seq, h // heads,
                                       causal=False))
    head_rows = batch * n_pred
    fwd += head_rows * 2 * h * h                      # MLM transform
    fwd += head_rows * 2 * h * cfg["vocab_size"]      # tied decoder
    fwd += batch * 2 * h * h                          # pooler
    return 3 * fwd


def gpt2_train_flops_per_step(cfg, batch, seq):
    """GPT-2 LM step (fwd + bwd), LM head on every position."""
    h = cfg["hidden_size"]
    layers, heads = cfg["num_layers"], cfg["num_heads"]
    tokens = batch * seq
    fwd = layers * (tokens * _layer_matmul_flops_per_token(h, 4 * h)
                    + _attention_flops(batch, heads, seq, seq, h // heads,
                                       causal=True))
    fwd += tokens * 2 * h * cfg["vocab_size"]
    return 3 * fwd


def attention_kernel_flops_per_layer(batch, heads, seq, head_dim, causal):
    """The flash kernel's matrix work for one layer of one training step:
    forward is QK^T and PV (2 matmuls), backward recomputes QK^T and forms
    dV, dP, dQ, dK (5 matmuls); each matmul is 2 b h s s d, halved when
    causal."""
    one = 2 * batch * heads * seq * seq * head_dim
    if causal:
        one //= 2
    return 7 * one


def attention_kernel_bytes_per_layer(batch, heads, seq, head_dim,
                                     dtype_bytes=2):
    """Lower bound of the kernel's HBM traffic for one layer of one
    training step: forward reads Q, K, V and writes O; backward reads Q, K,
    V, O, dO and writes dQ, dK, dV — 12 tensors of b h s d, each touched
    once."""
    return 12 * batch * heads * seq * head_dim * dtype_bytes


def gpt2_param_count(cfg, with_positions=True):
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    per_layer = (h * 3 * h + 3 * h) + (h * h + h) + (h * 4 * h + 4 * h) \
        + (4 * h * h + h) + 4 * h
    n = cfg["num_layers"] * per_layer + v * h + 2 * h
    if with_positions:
        n += cfg["max_position_embeddings"] * h
    return n


def gpt2_decode_bytes_per_step(cfg, live_context_tokens, dtype_bytes=2):
    """Lower bound of the bytes one decode iteration must read: every
    weight once (the position table is read by row, not counted) plus the
    keys and values of the batch's live context."""
    weights = gpt2_param_count(cfg, with_positions=False) * dtype_bytes
    kv = (2 * cfg["num_layers"] * live_context_tokens * cfg["hidden_size"]
          * dtype_bytes)
    return weights + kv
