"""The few operations both references share, and the lower-precision
matrix multiplication of the control."""

import jax
import jax.numpy as jnp


def matmul(x, w):
    return jnp.matmul(x, w)


def _fake_fp8(x, axis=None):
    """Round to float8 (e4m3: three bits of mantissa) after scaling the
    tensor's (or, along ``axis``, each row's) largest magnitude to the
    format's largest, with a straight-through gradient."""
    top = float(jnp.finfo(jnp.float8_e4m3fn).max)
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None) / top
    scale = jnp.where(scale > 0, scale, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(q - x)


def matmul_fp8(x, w):
    """The control: both operands of every weight matrix multiplication in
    scaled float8 (weights per tensor, activations per row) — the precision
    below the bfloat16 the configurations state.  int8 would also do by the
    rule, but its 7 bits are too near bfloat16's 8 to tell the two apart;
    float8's 3 are not."""
    return jnp.matmul(_fake_fp8(x, axis=-1), _fake_fp8(w))


MATMULS = {"float32": matmul, "fp8": matmul_fp8}


def dense(p, x, mm):
    return mm(x, p["kernel"]) + p["bias"]


def layer_norm(p, x, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def dropout(key, x, rate):
    """Inverted dropout with the reference's own mask; ``key`` None or a
    zero rate leaves ``x`` alone."""
    if key is None or not rate:
        return x
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0)


def attention(q, k, v, bias, key, rate):
    """softmax(q k^T / sqrt(d) + bias) v over [rows, seq, heads, d]."""
    d = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(d))
    if bias is not None:
        scores = scores + bias
    probs = dropout(key, jax.nn.softmax(scores, axis=-1), rate)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def split_heads(qkv, heads):
    b, s, three_h = qkv.shape
    qkv = qkv.reshape(b, s, 3, heads, three_h // (3 * heads))
    return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]


def through_layers(layer, layers, x, keys):
    """``x`` through ``layers`` (a list of equal parameter trees) one after
    another, as one scanned body (a 24- or 48-layer program otherwise takes
    minutes to compile); each layer is recomputed on the way back so that
    the float32 activations fit."""
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)
    body = jax.checkpoint(layer)
    if keys[0] is None:
        out, _ = jax.lax.scan(lambda x, p: (body(p, x, None), None), x,
                              stacked)
    else:
        out, _ = jax.lax.scan(lambda x, pk: (body(pk[0], x, pk[1]), None),
                              x, (stacked, jnp.stack(keys)))
    return out


def keys_for(key, n):
    return [None] * n if key is None else list(jax.random.split(key, n))


def nll(logits, labels):
    """Per-position negative log-likelihood; ``labels`` < 0 give 0."""
    mask = labels >= 0
    safe = jnp.where(mask, labels, 0)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    return (lse - gold) * mask, mask
