"""Micro-profile of ONE GPT-2-medium layer's components at the bench shape.

Attributes the trunk's wall time (the step profile's dominant scope) to
QKV/attention/FFN/layernorm/dropout at [b=8, s=1024, h=1024, heads=16].
Every probe runs inside one jitted lax.scan (per-dispatch cost would
otherwise swamp sub-ms ops) with operands passed as arguments (NOT
closures — large closure constants stall XLA compiles).

Usage: python examples/profile_gpt2_layer.py
"""

import os

import numpy as np

B, S, H, HEADS = 8, 1024, 1024, 16
D = H // HEADS
STEPS = int(os.environ.get("PROF_STEPS", "20"))


def main():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.layers import TransformerLayer
    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention
    from deepspeed_tpu.profiling.step_profiler import grad_fold, timed_scan

    rng = jax.random.PRNGKey(0)
    layer = TransformerLayer(hidden_size=H, heads=HEADS, causal=True,
                             attn_dropout_ratio=0.1, hidden_dropout_ratio=0.1,
                             pre_layer_norm=True)
    params = layer.init(rng)
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a,
        params)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, H), jnp.bfloat16)
    qkvh = jax.random.normal(jax.random.PRNGKey(2), (B, S, HEADS, D),
                             jnp.bfloat16)

    def t(name, fn, ops, bwd=True):
        fwd_ms = timed_scan(fn, ops, steps=STEPS) * 1e3
        line = f"  {name:>28}: fwd {fwd_ms:7.3f} ms"
        if bwd:
            def fb(o, i):
                val, grads = jax.value_and_grad(
                    lambda oo: fn(oo, i))(o)
                return val + 1e-30 * grad_fold(grads)

            fb_ms = timed_scan(fb, ops, steps=STEPS) * 1e3
            line += f"   fwd+bwd {fb_ms:8.3f} ms"
        print(line, flush=True)

    # full layer, dropout on/off
    def layer_drop(ops, i):
        p, xx = ops
        r = jax.random.fold_in(jax.random.PRNGKey(7), i)
        return jnp.sum(layer.apply(p, xx, rng=r, deterministic=False)
                       .astype(jnp.float32)) * 1e-9

    def layer_nodrop(ops, i):
        p, xx = ops
        return jnp.sum(layer.apply(p, xx, deterministic=True)
                       .astype(jnp.float32)) * 1e-9

    t("layer (dropout 0.1)", layer_drop, (params, x))
    t("layer (no dropout)", layer_nodrop, (params, x))

    # attention core alone (flash kernel, causal)
    def attn_only(ops, i):
        q, k, v = ops
        o = flash_attention(q, k, v, causal=True)
        return jnp.sum(o.astype(jnp.float32)) * 1e-9

    t("flash attention (causal)", attn_only, (qkvh, qkvh, qkvh))

    # the GEMMs at layer shapes
    def gemm(shape_b):
        w = jax.random.normal(jax.random.PRNGKey(3), (H, shape_b),
                              jnp.bfloat16)

        def f(ops, i):
            xx, ww = ops
            y = jax.lax.dot_general(
                xx.reshape(-1, H), ww, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return jnp.sum(y) * 1e-9

        return f, (x, w)

    for name, nout in (("QKV GEMM [1024->3072]", 3 * H),
                       ("attn-out GEMM [1024->1024]", H),
                       ("FC1 GEMM [1024->4096]", 4 * H)):
        f, ops = gemm(nout)
        t(name, f, ops)

    # FC2 [4096 -> 1024]
    xi = jax.random.normal(jax.random.PRNGKey(4), (B, S, 4 * H), jnp.bfloat16)
    w2 = jax.random.normal(jax.random.PRNGKey(5), (4 * H, H), jnp.bfloat16)

    def fc2(ops, i):
        xx, ww = ops
        y = jax.lax.dot_general(xx.reshape(-1, 4 * H), ww,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        return jnp.sum(y) * 1e-9

    t("FC2 GEMM [4096->1024]", fc2, (xi, w2))

    # layernorm at [8, 1024, 1024]
    from deepspeed_tpu.models.layers import layer_norm
    ln_p = {"scale": jnp.ones((H,), jnp.float32),
            "bias": jnp.zeros((H,), jnp.float32)}

    def ln(ops, i):
        p, xx = ops
        return jnp.sum(layer_norm(p, xx, 1e-5).astype(jnp.float32)) * 1e-9

    t("layernorm", ln, (ln_p, x))

    # one dropout site at [8, 1024, 1024]
    from deepspeed_tpu.models.layers import dropout as ds_dropout

    def drop(ops, i):
        xx, = ops
        r = jax.random.fold_in(jax.random.PRNGKey(9), i)
        return jnp.sum(ds_dropout(r, xx, 0.1, False)
                       .astype(jnp.float32)) * 1e-9

    t("dropout site [8,1024,1024]", drop, (x,))


if __name__ == "__main__":
    main()
