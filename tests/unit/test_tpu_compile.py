"""The main path's kernels compiled for a described TPU v5e, without a chip.

The TPU's compiler is installed beside jax and compiles for a topology
that is described, not attached (``on-chip-measurement`` guide, section 2).
Interpret-mode tests cannot see what it refuses — a slice off the tiling,
too much VMEM, a kernel that will not lower — so the kernels of
``chip_smoke.py``'s shapes are compiled here at their real widths, about
two seconds each.  Nothing runs: this says nothing of results or times.

The persistent compile cache is off around them: an executable for a
described device is written to it but cannot be read back without a chip.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax.experimental.compilation_cache import compilation_cache  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from deepspeed_tpu.ops.sparse_attention import (  # noqa: E402
    BigBirdSparsityConfig, flash_block_sparse_attention)
from deepspeed_tpu.ops.transformer import (  # noqa: E402
    flash_attention as flash_kernels)
from deepspeed_tpu.ops.transformer.attention import (  # noqa: E402
    dot_product_attention)
from deepspeed_tpu.ops.transformer.flash_attention import (  # noqa: E402
    flash_attention, flash_self_attention)
from deepspeed_tpu.ops.transformer.paged_attention import (  # noqa: E402
    check_tpu_geometry, paged_decode_attention)
from deepspeed_tpu.runtime.compilation import CompileStats  # noqa: E402


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e!r:.200}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _qkv(shape, sharding, dtype=jnp.bfloat16):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)] * 3


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


@pytest.mark.parametrize("shape,causal,masked,dropout", [
    ((8, 512, 16, 64), False, True, 0.1),    # BERT-large seq 512
    ((4, 1024, 16, 64), True, False, 0.0),   # GPT-2-medium seq 1024
    ((4, 1024, 16, 64), True, False, 0.1),
    ((1, 512, 20, 64), True, True, 0.0),     # GPT-2-large prefill bucket
], ids=["bert-s512-mask-dropout", "gpt2-s1024-causal",
        "gpt2-s1024-causal-dropout", "prefill-s512-causal-mask"])
def test_flash_kernel_grad_compiles(v5e, shape, causal, masked, dropout):
    b, s = shape[:2]
    extra = []
    if masked:
        extra.append(jax.ShapeDtypeStruct((b, s), jnp.float32, sharding=v5e))
    if dropout:
        extra.append(jax.ShapeDtypeStruct((2,), jnp.int32, sharding=v5e))

    def loss(q, k, v, *rest):
        rest = list(rest)
        kv_mask = rest.pop(0) if masked else None
        seed = rest.pop(0) if dropout else None
        out = flash_attention(q, k, v, kv_mask=kv_mask, dropout_seed=seed,
                              causal=causal, dropout_rate=dropout)
        return jnp.sum(out.astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), *_qkv(shape, v5e),
                    *extra)
    # forward + the single-tile fused backward
    assert text.count("tpu_custom_call") >= 2


def _relayouts(text, *shapes):
    """Lines of an optimized program that copy or transpose a tensor of
    one of ``shapes`` ("[32,16,512,64]"), inside fusions too."""
    return [line.strip()[:160] for line in text.splitlines()
            if (" copy(" in line or " transpose(" in line)
            and any(shape in line.split("(")[0] for shape in shapes)]


def _layer_grad_text(v5e, b, s, attend, seed_dtype, h=16, d=64):
    """Optimized program of one layer's attention sandwich and its
    gradient: QKV GEMM, ``attend(qkv [b, s, 3, h, d], kv_mask [b, s],
    seed [2])``, output GEMM."""
    hidden = h * d

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=v5e)

    def loss(x, w_qkv, b_qkv, w_out, kv_mask, seed):
        qkv = (x @ w_qkv + b_qkv).reshape(b, s, 3, h, d)
        out = attend(qkv, kv_mask, seed).reshape(b, s, hidden) @ w_out
        return jnp.sum(out.astype(jnp.float32) ** 2)

    return _compile(jax.grad(loss, argnums=(0, 1, 2, 3)),
                    shape((b, s, hidden)), shape((hidden, 3 * hidden)),
                    shape((3 * hidden,)), shape((hidden, hidden)),
                    shape((b, s), jnp.float32), shape((2,), seed_dtype))


@pytest.mark.parametrize("entry", ["qkv", "fused"])
def test_no_head_transposes_around_the_seq512_kernels(v5e, entry):
    """One layer's attention as ``bert_large.seq512`` runs it (b 32, s 512,
    16 heads of 64, key mask, dropout 0.1): QKV GEMM, the kernels, output
    GEMM, and the gradient.  The kernels index the projection's layout, so
    the program holds no relayout of a [b, h, s, d] tensor and exactly the
    forward and the fused backward call; through the fused entry, which
    ``TransformerLayer`` takes, no slice of the projection going in and no
    concatenate coming back is materialised either."""
    def attend(qkv, kv_mask, seed):
        kw = dict(kv_mask=kv_mask, dropout_seed=seed, dropout_rate=0.1)
        if entry == "fused":
            return flash_self_attention(qkv, **kw)
        return flash_attention(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], **kw)

    text = _layer_grad_text(v5e, 32, 512, attend, jnp.int32)
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert _relayouts(text, "[32,16,512,64]") == []
    if entry == "fused":
        assert _relayouts(text, "[32,512,1024]", "[32,512,3072]",
                          "[32,512,3,1024]", "[32,512,3,16,64]") == []


@pytest.mark.parametrize("b,s,causal,rows", [
    (112, 128, False, 8),   # bert_large.seq128's step: mask + dropout
    (2, 128, False, 2),     # ...and its eval_batch's rows
    (8, 256, True, 4),      # a causal decoder trained at seq 256
], ids=["bert-b112-s128", "bert-b2-s128", "causal-b8-s256"])
def test_short_sequences_take_the_kernels_several_rows_a_step(
        v5e, monkeypatch, b, s, causal, rows):
    """Under 512 the dispatch hands a self-attention to the flash kernels
    where a grid step can hold several batch rows (``_Operands.tile``): one
    layer's QKV GEMM, attention, output GEMM and the gradient, 16 heads of
    64, dropout 0.1 — the forward and the fused backward call compile for
    the chip at ``rows`` rows a step, with no relayout of a [b, h, s, d]
    tensor or of the projection around them."""
    from deepspeed_tpu.ops.transformer import attention
    from deepspeed_tpu.ops.transformer import flash_attention as fa
    from deepspeed_tpu.parallel import mesh

    h, d = 16, 64
    hidden = h * d
    logged = []
    monkeypatch.setattr(mesh, "current_platform", lambda: "tpu")
    # one described chip: no mesh an earlier test of the worker left current
    monkeypatch.setattr(mesh, "get_current_mesh", lambda: None)
    monkeypatch.setattr(fa, "_log_geometry", lambda *a: logged.append(a[-3]))

    def attend(qkv, kv_mask, key):
        return attention.self_attention(
            qkv, key_padding_mask=None if causal else kv_mask, causal=causal,
            dropout_rate=0.1, dropout_rng=key, deterministic=False)

    text = _layer_grad_text(v5e, b, s, attend, jnp.uint32)
    assert set(logged) == {rows}
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert _relayouts(text, f"[{b},{h},{s},{d}]", f"[{b},{s},{hidden}]",
                      f"[{b},{s},{3 * hidden}]", f"[{b},{s},3,{hidden}]",
                      f"[{b},{s},3,{h},{d}]") == []


def _lowered(monkeypatch, fn, *args):
    """(StableHLO text of ``fn`` lowered for the described chip with each
    kernel call's ``backend_config`` taken out and the helper functions
    named after their bodies, the distinct Mosaic bodies that were in the
    calls as text without locations): what a change to the kernels' source
    lines or to the order in which helpers were traced leaves as it is."""
    from jax._src import tpu_custom_call

    bodies = set()
    serialize = tpu_custom_call._lower_mosaic_module_to_asm

    def keep(module, **kw):
        bodies.add(module.operation.get_asm(enable_debug_info=False))
        return serialize(module, **kw)

    monkeypatch.setattr(tpu_custom_call, "_lower_mosaic_module_to_asm", keep)
    text = jax.jit(fn).lower(*args).as_text()
    return (_names_normalised(re.sub(r'backend_config = "[^"]*"', "", text)),
            bodies)


def _names_normalised(text):
    """A StableHLO module's text with every function but ``main`` named
    after its own body (its callees' names replaced first), the functions
    sorted and equal bodies kept once: two modules that differ in the
    numbering, the order or the sharing of their helper functions alone
    (``_where_3`` here, ``_where_7`` there) give the same text."""
    funcs, head, name = {}, [], None
    for line in text.splitlines():
        m = re.match(r"  func\.func (?:\w+ )?@([\w.$\-]+)\(", line)
        if m:
            name = m.group(1)
            funcs[name] = []
        (head if name is None else funcs[name]).append(line)
        if line == "  }":
            name = None
    called = re.compile(r"@(" + "|".join(
        map(re.escape, sorted(funcs, key=len, reverse=True))) + r")\b")
    bodies = {}

    def body(fn):
        if fn not in bodies:
            bodies[fn] = "\n".join(
                called.sub(lambda m: "@SELF" if m.group(1) == fn
                           else "@f_" + _digest(body(m.group(1))), line)
                for line in funcs[fn])
        return bodies[fn]

    return "\n".join(head + [body("main")] + sorted(
        {body(fn) for fn in funcs if fn != "main"}))


def _digest(*texts):
    import hashlib

    return hashlib.sha256("\n".join(texts).encode()).hexdigest()[:16]


def _seq512_layers(shape):
    """Two layers of ``bert_large.seq512``'s attention (b 32, mask,
    dropout 0.1) and their gradient."""
    from deepspeed_tpu.ops.transformer import attention

    def two_layers(qkv, kv_mask, key):
        for layer in range(2):
            out = attention.self_attention(
                qkv, key_padding_mask=kv_mask, dropout_rate=0.1,
                dropout_rng=jax.random.fold_in(key, layer),
                deterministic=False)
            qkv = qkv + out[:, :, None]
        return jnp.sum(qkv.astype(jnp.float32))

    return jax.grad(two_layers), (shape((32, 512, 3, 16, 64)),
                                  shape((32, 512), jnp.float32),
                                  shape((2,), jnp.uint32))


def _prefill_layers(s):
    """Two layers of GPT-2-large's bucket-``s`` prefill attention (one row,
    20 heads of 64, causal, key mask)."""
    def build(shape):
        from deepspeed_tpu.ops.transformer import attention

        def two_layers(q, k, v, visible):
            for _ in range(2):
                q = q + attention.dot_product_attention(
                    q, k, v, key_padding_mask=visible, causal=True)
            return q

        return two_layers, (*[shape((1, s, 20, 64))] * 3,
                            shape((1, s), jnp.float32))

    return build


@pytest.mark.parametrize("program,text_digest,body_digests", [
    (_seq512_layers, "093a0768c2ce9cb7", ["169ef6412f20687f", "888a5e6e32a4cf3d"]),
    (_prefill_layers(512), "bd37f3a5f4150dc7", ["4c1432cca2ba5260"]),
    (_prefill_layers(128), "08f5eaebab9f6b8a", []),
], ids=["seq512-step", "prefill-512", "prefill-128"])
def test_one_row_programs_are_the_parents(v5e, monkeypatch, program,
                                          text_digest, body_digests):
    """Several rows a step changed the kernels' source and the dispatch
    under 512, and every kernel call is traced once a geometry (PR 37);
    what the cells ran at one row a step is what they run now.  Two layers
    of ``bert_large.seq512``'s attention with their gradient, and of
    GPT-2-large's bucket-512 prefill: the lowered text, its helper
    functions named after their bodies, and every Mosaic body are PR 35's
    (the digests were taken from its tree with this file's helpers).  The
    bucket-128 prefill — one row, nothing for a step to hold — stays XLA's
    attention, the same text.  A PR that means to change these re-pins
    the digests (they are printed) and says so."""
    from deepspeed_tpu.parallel import mesh

    monkeypatch.setattr(mesh, "current_platform", lambda: "tpu")
    monkeypatch.setattr(mesh, "get_current_mesh", lambda: None)

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=v5e)

    fn, args = program(shape)
    text, bodies = _lowered(monkeypatch, fn, *args)
    got = (_digest(text), sorted(map(_digest, bodies)))
    assert got == (text_digest, body_digests), got
    assert ("tpu_custom_call" in text) == bool(body_digests)


def test_no_head_transposes_around_the_prefill_kernel(v5e):
    """GPT-2-large's bucket-512 prefill (b 1, 20 heads of 64, causal, key
    mask), forward: one kernel call and no [1, 20, 512, 64] relayout."""
    s, h, d = 512, 20, 64

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=v5e)

    def prefill(x, w_qkv, w_out, visible):
        qkv = (x @ w_qkv).reshape(1, s, 3, h * d)
        q, k, v = (qkv[:, :, j].reshape(1, s, h, d) for j in range(3))
        ctx = flash_attention(q, k, v, kv_mask=visible, causal=True)
        return ctx.reshape(1, s, h * d) @ w_out

    text = _compile(prefill, shape((1, s, h * d)), shape((h * d, 3 * h * d)),
                    shape((h * d, h * d)), shape((1, s), jnp.float32))
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert _relayouts(text, "[1,20,512,64]") == []


def test_block_sparse_flash_kernel_grad_compiles(v5e):
    s, heads = 4096, 16
    layout = BigBirdSparsityConfig(
        num_heads=heads, block=128, num_random_blocks=1,
        num_sliding_window_blocks=3, num_global_blocks=1).make_layout(s)

    def loss(q, k, v):
        out = flash_block_sparse_attention(q, k, v, layout)
        return jnp.sum(out.astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)),
                    *_qkv((1, s, heads, 64), v5e))
    assert "tpu_custom_call" in text


def test_serving_decode_attention_compiles(v5e):
    """One decode position per slot against the paged context, GPT-2-large
    geometry (20 heads of 64, 4 slots, 1024 positions): the dispatch sends
    a one-row query to XLA attention, not to the kernel."""
    slots, max_seq, heads, d = 4, 1024, 20, 64
    q = jax.ShapeDtypeStruct((slots, 1, heads, d), jnp.bfloat16, sharding=v5e)
    kv = jax.ShapeDtypeStruct((slots, max_seq, heads, d), jnp.bfloat16,
                              sharding=v5e)
    visible = jax.ShapeDtypeStruct((slots, max_seq), jnp.float32,
                                   sharding=v5e)
    text = _compile(
        lambda q, k, v, m: dot_product_attention(q, k, v, key_padding_mask=m),
        q, kv, kv, visible)
    assert "tpu_custom_call" not in text


@pytest.mark.parametrize("hidden,heads,block_size,dtype,slots,ambient", [
    (1280, 20, 64, jnp.bfloat16, 16, None),  # gpt2_large.backlog, chip_smoke
    (1280, 20, 64, jnp.float32, 16, None),   # weights_dtype unset
    (1280, 20, 64, jnp.bfloat16, 16, "float32"),  # as test_tpu_kernels sets
    (1280, 20, 8, jnp.bfloat16, 2, None),    # half a bf16 tile of rows a page
    (768, 12, 16, jnp.bfloat16, 1, None),    # GPT-2-small, one slot
    (1600, 25, 64, jnp.bfloat16, 16, None),  # GPT-2-xl: refused, and said so
], ids=["large-bf16", "large-fp32", "large-bf16-ambient-fp32", "large-bs8",
        "small-1slot", "xl"])
def test_paged_decode_kernel_compiles(v5e, hidden, heads, block_size, dtype,
                                      slots, ambient):
    """The decode kernel over the whole cache in place: scalar-prefetched
    layer and tables, page DMAs out of an ``ANY``-space operand, the
    block-diagonal matmuls — also under an ambient fp32 matmul precision,
    which Mosaic refuses for bf16 operands unless the kernel pins its
    own.  ``check_tpu_geometry`` agrees with the compiler on what cannot
    be tiled."""
    layers, blocks, per_seq = 4, 40, 16

    def shape(dims, dt):
        return jax.ShapeDtypeStruct(dims, dt, sharding=v5e)

    args = (shape((slots, hidden), dtype),
            shape((layers, blocks, block_size, hidden), dtype),
            shape((layers, blocks, block_size, hidden), dtype),
            shape((slots, per_seq), jnp.int32), shape((slots,), jnp.int32))

    def attend(q, k_cache, v_cache, tables, ctx_lens):
        return paged_decode_attention(q, k_cache, v_cache, tables, ctx_lens,
                                      layer=3, num_heads=heads)

    try:
        check_tpu_geometry(hidden, block_size)
    except ValueError:
        with pytest.raises(Exception, match="aligned to tiling"):
            _compile(attend, *args)
        return
    with jax.default_matmul_precision(ambient or "default"):
        text = _compile(attend, *args)
    assert "tpu_custom_call" in text


# -- the latent-attention serving path at its published widths ---------------

@pytest.mark.parametrize("pages", [1, 16])
def test_latent_paged_decode_kernel_compiles(v5e, pages):
    """128 query heads over one 576-wide latent row stored 640 wide, 64
    slots of 192 pages: what the decode program calls once a layer."""
    from deepspeed_tpu.ops.transformer.mla_paged_attention import (
        mla_paged_decode_attention, padded_row_width)

    row = padded_row_width(512 + 64)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    text = _compile(
        lambda q, c, t, l: mla_paged_decode_attention(
            q, c, t, l, layer=3, value_width=512, scale=0.1,
            pages_per_step=pages),
        s((64, 128, row), jnp.bfloat16), s((5, 1025, 64, row), jnp.bfloat16),
        s((64, 192), jnp.int32), s((64,), jnp.int32))
    assert "tpu_custom_call" in text
    assert "mla_paged_decode_attention" in text


def test_flash_forward_compiles_at_key_width_192_value_width_128(v5e):
    from deepspeed_tpu.ops.transformer.flash_attention import (
        flash_attention_forward)

    def s(d):
        return jax.ShapeDtypeStruct((1, 4096, 128, d), jnp.bfloat16,
                                    sharding=v5e)

    text = _compile(
        lambda q, k, v: flash_attention_forward(
            q, k, v, causal=True, block_q=1024, block_k=1024,
            name="mla_prefill_attention"), s(192), s(192), s(128))
    assert "tpu_custom_call" in text and "mla_prefill_attention" in text


@pytest.mark.parametrize("rows,tiling", [(384, (128, 5120, 512)),
                                         (49152, (256, 2560, 1024))])
def test_grouped_matmul_compiles_for_decode_and_prefill(v5e, rows, tiling):
    """20 held experts of 5120 x 3072 (gate and up fused) and 1536 x 5120,
    21 groups: the last is the pairs held elsewhere."""
    from deepspeed_tpu.ops.transformer.grouped_matmul import (
        moe_grouped_matmul)

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    for k, n in ((5120, 3072), (1536, 5120)):
        text = _compile(
            lambda lhs, rhs, sizes: moe_grouped_matmul(lhs, rhs, sizes,
                                                       tiling=tiling),
            s((rows, k)), s((20, k, n)), s((21,), jnp.int32))
        assert "moe_grouped_matmul" in text


# -- grouped KV heads and windows at K-EXAONE's published widths -------------

@pytest.mark.parametrize("window,pages,name", [
    (None, 4, "gqa_paged_decode_attention"),
    (128, 3, "window_paged_decode_attention")])
def test_grouped_paged_decode_kernel_compiles(v5e, window, pages, name):
    """64 query heads over 8 KV heads of 128 (a cache row of 1024), 64
    slots: the full layer's whole-context table of 192 pages, and a window
    layer's ring of 3."""
    from deepspeed_tpu.ops.transformer.paged_attention import (
        check_gqa_tpu_geometry, ring_pages)

    check_gqa_tpu_geometry(8, 128, 64)
    layers, blocks, per_seq = ((1, 1025, 192) if window is None
                               else (4, 193, ring_pages(128, 64)))

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    text = _compile(
        lambda q, k, v, t, l: paged_decode_attention(
            q, k, v, t, l, layer=layers - 1, num_heads=64, window=window,
            pages_per_step=pages),
        s((64, 8192)), s((layers, blocks, 64, 1024)),
        s((layers, blocks, 64, 1024)), s((64, per_seq), jnp.int32),
        s((64,), jnp.int32))
    assert "tpu_custom_call" in text and name in text


@pytest.mark.parametrize("seq,block,window,name", [
    (6144, 1024, None, "gqa_prefill_attention"),
    (6144, 512, 128, "window_prefill_attention"),
    (3072, 512, 128, "window_prefill_attention")])
def test_flash_forward_compiles_with_grouped_heads_and_a_window(
        v5e, seq, block, window, name):
    from deepspeed_tpu.ops.transformer.flash_attention import (
        flash_attention_forward)

    def s(heads):
        return jax.ShapeDtypeStruct((1, seq, heads, 128), jnp.bfloat16,
                                    sharding=v5e)

    text = _compile(
        lambda q, k, v: flash_attention_forward(
            q, k, v, causal=True, block_q=block, block_k=block,
            window=window, name=name), s(64), s(8), s(8))
    assert "tpu_custom_call" in text and name in text


@pytest.mark.parametrize("rows,tiling", [(512, (128, 6144, 256)),
                                         (49152, (256, 2048, 1024))])
def test_grouped_matmul_compiles_for_the_sigmoid_expert_layer(v5e, rows,
                                                               tiling):
    """16 held experts of 6144 x 4096 (gate and up fused) and 2048 x 6144,
    17 groups: the last is the pairs held elsewhere."""
    from deepspeed_tpu.ops.transformer.grouped_matmul import (
        moe_grouped_matmul)

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    for k, n in ((6144, 4096), (2048, 6144)):
        text = _compile(
            lambda l, r, g: moe_grouped_matmul(l, r, g, tiling=tiling),
            s((rows, k)), s((16, k, n)), s((17,), jnp.int32))
        assert "tpu_custom_call" in text


@pytest.mark.parametrize(
    "tokens,hidden,width,top_k,held,routed,tiling,compact,gone", [
        # DeepSeek-V2's 8,192-token prefill: 12,288 of 49,152 pairs a pass
        (8192, 5120, 1536, 6, 20, 160, (256, 2560, 1024),
         ("bf16[12288,5120]", "bf16[12288,3072]"),
         ("[49152,5120]", "[49152,3072]", "[49152,1536]",
          "f32[8192,6,5120]")),
        # K-EXAONE's 4,096-token prefill: 8,192 of 32,768
        (4096, 6144, 2048, 8, 16, 128, (256, 2048, 1024),
         ("bf16[8192,6144]", "bf16[8192,4096]"),
         ("[32768,6144]", "[32768,4096]", "[32768,2048]",
          "f32[4096,8,6144]"))])
def test_an_expert_layer_forms_no_array_of_every_pair(
        v5e, tokens, hidden, width, top_k, held, routed, tiling, compact,
        gone):
    """One chip of eight holds an eighth of the pairs on average: the
    compiled layer gathers, multiplies and combines a capacity's worth of
    rows, and holds no ``[tokens * top_k, hidden]``, ``[tokens * top_k,
    2 * width]`` or float32 ``[tokens, top_k, hidden]`` array (the last
    was 1.0 GB an 8,192-token layer, six choices in eight-row tiles)."""
    from deepspeed_tpu.models import expert_shard

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    text = _compile(
        lambda x, w, ids, valid, experts: expert_shard.held_experts_ffn(
            x, w, ids, valid, experts, first_expert=0, interpret=False,
            tiling=tiling, routed=routed),
        s((tokens, hidden)), s((tokens, top_k), jnp.float32),
        s((tokens, top_k), jnp.int32), s((tokens,), jnp.bool_),
        {"gate_up": s((held, hidden, 2 * width)),
         "down": s((held, width, hidden))})
    assert text.count("tpu_custom_call") >= 2
    for shape in compact:
        assert shape in text
    for shape in gone:
        assert shape not in text, shape


# -- the serving cells' decode programs, whole, at the benchmark's widths ------

_ITEM_BYTES = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1}


def _top_level(text, ops):
    """``(op, output bytes, minor-to-major of the output and of the operand
    where the line gives one, line)`` of the ENTRY computation's
    instructions named in ``ops``; of a tuple output (``copy-start``) the
    first element, the destination."""
    entry = text[text.index("\nENTRY "):]
    found = []
    for line in entry[:entry.index("\n}")].splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = \(?(\w+)\[([\d,]*)\]"
                     r".*? ([\w\-]+)\(", line)
        if m and m.group(3) in ops:
            size = _ITEM_BYTES[m.group(1)]
            for dim in filter(None, m.group(2).split(",")):
                size *= int(dim)
            layouts = re.findall(r"\]\{([\d,]+)", line.split(" = ", 1)[1])
            found.append((m.group(3), size, layouts[:2], line.strip()))
    return found


def _serve_decode(cell, sharding, monkeypatch):
    return _compiled_serve_decode(cell, sharding, monkeypatch).as_text()


_COMPILED = {}      # cell -> its compiled decode: two tests read Ouro's


def _compiled_serve_decode(cell, sharding, monkeypatch):
    if cell not in _COMPILED:
        _COMPILED[cell] = _compile_serve_decode(cell, sharding, monkeypatch)
    return _COMPILED[cell]


def _compile_serve_decode(cell, sharding, monkeypatch):
    """The optimized ``serve_decode`` of a serving cell of BENCHMARK.json:
    the model object and the engine config the harness builds, the tree
    ``InferenceEngine`` would hold (``prepare_params``) as shapes, the TPU
    path steered from here."""
    serving, icfg, s, params, caches = _serving_shapes(cell, sharding,
                                                       monkeypatch)
    slots = icfg.max_batch_slots
    tables = tuple(s((slots, g.table_width(icfg)), jnp.int32)
                   for g in serving.cache_groups(icfg))
    return jax.jit(serving.build_decode(icfg), donate_argnums=(1,)).lower(
        params, caches, tables, s((slots,), jnp.int32),
        s((slots,), jnp.int32)).compile()


def _serving_shapes(cell, sharding, monkeypatch):
    """``(serving object, inference config, shape maker, parameter shapes,
    cache shapes)`` of a serving cell, the TPU path steered from here; a
    cache group's buffers in its own dtype where it names one."""
    from benchmarks import common, models
    from deepspeed_tpu.inference import model as gpt2_serving
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.models import (deepseek_v2, exaone_moe, minicpm_sala,
                                      ouro, xing)

    for module in (gpt2_serving, deepseek_v2, exaone_moe, ouro,
                   minicpm_sala, xing):
        monkeypatch.setattr(module, "current_platform", lambda: "tpu")
    spec = common.load_cell(cell)
    config = spec["config"]
    shapes_of = models.load(config["model"])
    serving = shapes_of.build_program_model(
        config["model_config"], spec["traffic"]).serving()
    icfg = DeepSpeedInferenceConfig(config["engine"])
    dtype = (jnp.bfloat16 if icfg.weights_dtype == "bfloat16"
             else jnp.float32)

    def s(shape, dt=dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dt, sharding=sharding)

    params = jax.tree_util.tree_map(
        lambda x: s(x.shape, x.dtype),
        jax.eval_shape(serving.prepare_params, jax.tree_util.tree_map(
            s, shapes_of.param_shapes(config["model_config"]),
            is_leaf=lambda x: isinstance(x, tuple))))
    caches = tuple(
        s((g.layers, g.num_blocks(icfg), icfg.kv_block_size, row),
          jnp.dtype(g.dtype) if g.dtype else dtype)
        for g in serving.cache_groups(icfg) for row in g.buffers.values())
    return serving, icfg, s, params, caches


@pytest.mark.parametrize("cell", ["deepseek_v2_ep8.repo_backlog",
                                  "gpt2_large.backlog",
                                  "k_exaone_ep8.reason_backlog",
                                  "ouro_2_6b.think_backlog",
                                  "minicpm_sala_pp8.longdoc_backlog",
                                  "xing4_29b_6l.docqa_backlog"])
def test_decode_makes_no_copy_of_a_weight(v5e, monkeypatch, cell):
    """A weight does not change between decode steps, so a step re-lays
    none out: no top-level ``copy`` or ``transpose`` of 1 MB or more whose
    ``op_name`` or operand names a parameter, and no ``copy-start`` of one
    into ANOTHER layout (XLA's prefetches of a weight into faster memory
    keep its layout and stay).  DeepSeek-V2's ``kv_b`` split inside the
    program was nine such copies of 33.5 MB a step, 328.6 MB of copy
    outputs in all, before ``prepare_params`` made the split once."""
    text = _serve_decode(cell, v5e, monkeypatch)
    assert "tpu_custom_call" in text
    moved = _top_level(text, ("copy", "transpose", "copy-start"))
    assert moved
    relaid = [line[:240] for op, size, layouts, line in moved
              if size >= 2 ** 20
              and ("params[" in line or "(%params__" in line)
              and not (op == "copy-start" and len(set(layouts)) == 1)]
    assert relaid == []
    assert sum(size for op, size, _, _ in moved if op == "copy") <= 40e6


def test_the_looped_decode_carries_its_caches_in_place(v5e, monkeypatch):
    """Ouro-2.6B's decode at the published size: the 48-layer body once,
    under ONE ``while`` over the four loop steps (48 kernel calls, not
    192), both 4.08 GB cache buffers aliased onto their inputs and carried
    through the loop without a copy — the program's temporaries are tens
    of megabytes, where one copied buffer would be 4,077 MB (and the two
    do not fit the chip twice)."""
    compiled = _compiled_serve_decode("ouro_2_6b.think_backlog", v5e,
                                      monkeypatch)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 48
    assert len(re.findall(r" while\(", text)) == 1
    header = text.split("\n", 1)[0]
    assert header.count("may-alias") + header.count("must-alias") == 2
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == 2 * 192 * 81 * 64 * 2048 * 2
    assert memory.temp_size_in_bytes < 256 * 2 ** 20
    assert not [line for op, size, _, line in _top_level(
        text, ("copy", "transpose", "copy-start")) if size >= 2 ** 30]


def test_the_sparse_and_lightning_decode_rewrites_every_cache_in_place(
        v5e, monkeypatch):
    """MiniCPM-SALA's decode at the published size: five kernel calls (the
    scores over the compressed keys, the attention over the chosen pages,
    three state updates), all four cache buffers — K, V, the compressed
    keys and the 409 MB of float32 states — aliased onto their inputs, and
    temporaries of a few megabytes: nothing of ``max_seq_len`` but the
    scores, no copy of a state."""
    compiled = _compiled_serve_decode("minicpm_sala_pp8.longdoc_backlog",
                                      v5e, monkeypatch)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 5
    for name in ("sparse_block_select", "sparse_paged_decode_attention",
                 "lightning_decode_update"):
        assert name in text
    assert " sort(" not in text          # the choice counts (PR 41)
    header = text.split("\n", 1)[0]
    assert header.count("may-alias") + header.count("must-alias") == 4
    memory = compiled.memory_analysis()
    pages = 19457 * 64 * 256 * 2
    assert memory.alias_size_in_bytes == (
        2 * pages + (64 * 19 + 1) * 64 * 256 * 2 + 3 * 65 * 64 * 8192 * 4)
    assert memory.temp_size_in_bytes < 64 * 2 ** 20
    assert not [line for op, size, _, line in _top_level(
        text, ("copy", "transpose", "copy-start")) if size >= 2 ** 21]


def test_the_largest_sparse_and_lightning_prefill_fits_beside_the_caches(
        v5e, monkeypatch):
    """The 16,384-token bucket at the published size: four kernel calls
    (the masked flash attention, three chunked scans), the caches written
    in place, and under 2.5 GB of temporaries (the MLP's rows in blocks of
    4,096: whole, its intermediate alone is 1 GB)."""
    serving, icfg, s, params, caches = _serving_shapes(
        "minicpm_sala_pp8.longdoc_backlog", v5e, monkeypatch)
    bucket = max(icfg.prefill_buckets)
    tables = tuple(s((g.table_width(icfg),), jnp.int32)
                   for g in serving.cache_groups(icfg))
    compiled = jax.jit(serving.build_prefill(icfg, bucket),
                       donate_argnums=(1,)).lower(
        params, caches, s((1, bucket), jnp.int32), s((), jnp.int32), tables,
        s((icfg.max_batch_slots,), jnp.int32), s((), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 4
    assert "sparse_prefill_attention" in text
    assert "lightning_prefill_scan" in text
    assert " sort(" not in text          # the choice counts (PR 41)
    header = text.split("\n", 1)[0]
    assert header.count("may-alias") + header.count("must-alias") == 4
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9


def test_the_four_stream_decode_and_prefill_fit_beside_their_cache(
        v5e, monkeypatch):
    """Xing-4.0's programs at the published widths.  A decode step of 14
    kernel calls (6 latent attentions, 8 grouped products; its 24 mixes of
    32 rows are XLA's: they fill no 128-row tile), the 3.27 GB cache aliased
    and temporaries of tens of megabytes.  The 12,288-token prefill: 38
    calls (6 flash attentions, 8 grouped products, 12 + 12 stream mixes),
    under 2.5 GB of temporaries beside 11.65 GB of weights and cache, and no
    copy of the stream — it is held [tokens, 4 x 3584]: a [tokens, 4, 3584]
    float32 array is tiled over its last two dimensions and copied whole on
    the way into every mix."""
    cell = "xing4_29b_6l.docqa_backlog"
    compiled = _compiled_serve_decode(cell, v5e, monkeypatch)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 14
    assert not re.findall(r'%mhc_[\w.]* = [^\n]*custom_call_target', text)
    assert len(re.findall(
        r'%mla_paged_decode_attention[\w.]* = [^\n]*'
        r'custom_call_target="tpu_custom_call"', text)) == 6
    header = text.split("\n", 1)[0]
    assert header.count("may-alias") + header.count("must-alias") == 1
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == 6 * 6657 * 64 * 640 * 2
    assert memory.temp_size_in_bytes < 64 * 2 ** 20
    serving, icfg, s, params, caches = _serving_shapes(cell, v5e,
                                                       monkeypatch)
    bucket = max(icfg.prefill_buckets)
    tables = tuple(s((g.table_width(icfg),), jnp.int32)
                   for g in serving.cache_groups(icfg))
    prefill = jax.jit(serving.build_prefill(icfg, bucket),
                      donate_argnums=(1,)).lower(
        params, caches, s((1, bucket), jnp.int32), s((), jnp.int32), tables,
        s((icfg.max_batch_slots,), jnp.int32), s((), jnp.int32)).compile()
    text = prefill.as_text()
    assert text.count("tpu_custom_call") == 38
    assert "mla_prefill_attention" in text
    for name in ("mhc_pre_mix", "mhc_post_res_mix"):
        assert len(re.findall(
            rf'%{name}[\w.]* = [^\n]*custom_call_target="tpu_custom_call"',
            text)) == 12, name
    memory = prefill.memory_analysis()
    assert memory.alias_size_in_bytes == 6 * 6657 * 64 * 640 * 2
    assert memory.temp_size_in_bytes < 2.5e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 14.5e9
    # no copy of the stream: nothing of [12288, 4 x 3584] float32 is re-laid
    assert not [line for op, size, _, line in _top_level(
        text, ("copy", "transpose")) if size >= 12288 * 14336 * 4]


# -- a training cell's whole step, as the chip gets it ----------------------

def _lowered_step(cell, sharding, layers=None):
    """(the cell's ``train_step`` lowered for the described chip, its global
    batch, heads, sequence length): the engine of a ``BENCHMARK.json``
    training cell in plan mode (``aot_plan=True``: shapes alone) — the
    model, the engine config and the batch shapes the harness builds,
    ``layers`` of the model's where given."""
    import deepspeed_tpu as deepspeed
    from benchmarks import common, generators, models, train
    from deepspeed_tpu.parallel import make_mesh
    from deepspeed_tpu.parallel.mesh import (get_current_mesh,
                                             set_current_mesh)

    # the engine registers its mesh of DESCRIBED devices as the current
    # one: left behind, every later test of this worker would build its
    # programs for a TPU
    mesh_before = get_current_mesh()
    spec = common.load_cell(cell)
    cfg, traffic = spec["config"], spec["traffic"]
    mc = dict(cfg["model_config"])
    if layers is not None:
        mc["num_hidden_layers"] = layers
    model = models.load(cfg["model"])
    batch_rows = traffic["batch_per_chip"]
    batch = generators.load(traffic["generator"]).make(
        traffic, mc, 1, batch_rows)[0]
    engine, *_ = deepspeed.initialize(
        model=model.build_program_model(mc, traffic),
        config=train._engine_config(spec, batch_rows),
        mesh=make_mesh(cfg["mesh"], devices=list(sharding.device_set)),
        aot_plan=True)
    try:
        lowered = engine.aot_lower_train_step(
            {k: np.asarray(v) for k, v in batch.items()})
    finally:
        engine.close()
        set_current_mesh(mesh_before)
    return (lowered, batch_rows, mc["num_attention_heads"],
            traffic["seq_len"])


def _fresh_kernel_traces():
    flash_kernels._fwd_kernels.clear_cache()
    flash_kernels._bwd_kernels.clear_cache()
    return flash_kernels.trace_stats()


@pytest.mark.parametrize("cell,rows", [("bert_large.seq128", 8),
                                       ("bert_large.seq512", 1)])
def test_a_four_layer_model_traces_each_kernel_builder_once(
        v5e, monkeypatch, cell, rows):
    """Four layers of BERT-large, three of them self-attention over the
    whole sequence (the last gathers its queries: XLA's attention): the
    forward and the backward kernel builder are each traced ONCE and the
    other two layers are answered from the cache — at eight rows a step
    (seq 128) and at one (seq 512) alike — and the program still holds a
    kernel call a layer each way."""
    logged = []
    monkeypatch.setattr(flash_kernels, "_log_geometry",
                        lambda *a: logged.append(a[-3]))
    before = _fresh_kernel_traces()
    stats = CompileStats()
    try:
        lowered, *_ = _lowered_step(cell, v5e, layers=4)
    finally:
        stats.close()
    after = flash_kernels.trace_stats()
    assert set(logged) == {rows}
    assert {k: after[k] - before[k] for k in after} == {
        "geometries_traced": 2, "calls_from_cache": 4}
    assert "flash_attention" in stats.summary()
    assert lowered.as_text().count("tpu_custom_call") == 6


def test_the_seq128_step_compiles_to_a_kernel_call_a_layer_each_way(v5e):
    """``bert_large.seq128``'s step as the chip gets it (b 112, 24 layers,
    23 of them self-attention with mask and dropout): 46 Mosaic calls —
    two builders traced, 44 calls from the cache — and, compiled, no
    ``[b, h, s, s]`` buffer anywhere: the score tensor XLA's attention
    materialised (and relaid) is gone from the program.  The compile
    takes this machine's CPU about a minute."""
    before = _fresh_kernel_traces()
    lowered, b, h, s = _lowered_step("bert_large.seq128", v5e)
    after = flash_kernels.trace_stats()
    assert {k: after[k] - before[k] for k in after} == {
        "geometries_traced": 2, "calls_from_cache": 44}
    text = lowered.compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 46
    assert re.findall(rf"\[{b},{h},{s},{s}\]", text) == []


# -- the training kernels of grouped, windowed heads and held experts --------

@pytest.mark.parametrize("window,name", [(None, "gqa_train_attention"),
                                         (1024, "window_train_attention")])
def test_flash_gradient_compiles_with_grouped_heads_and_a_window(
        v5e, window, name):
    """Mellum's attention at its widths (32 query heads over 4 KV heads of
    128, one row of 8192, blocks of 512): forward, dq and the dk/dv kernel
    that sums each group in VMEM, each under its own name; dk and dv leave
    with the KV heads' shape."""
    def s(heads):
        return jax.ShapeDtypeStruct((1, 8192, heads, 128), jnp.bfloat16,
                                    sharding=v5e)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, None, None, True, 512, 512,
                                       False, 0.0, window)
                       .astype(jnp.float32))

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    compiled = grad.lower(s(32), s(4), s(4)).compile()
    text = compiled.as_text()
    for part in ("fwd", "bwd_dq", "bwd_dkv"):
        assert f"{name}_{part}" in text
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert re.findall(r"\[\d*,?8192,8192\]", text) == []
    dq, dk, dv = grad.eval_shape(s(32), s(4), s(4))
    assert (dq.shape, dk.shape, dv.shape) == (
        (1, 8192, 32, 128), (1, 8192, 4, 128), (1, 8192, 4, 128))


def test_grouped_matmul_gradient_compiles_for_the_trained_expert_layer(v5e):
    """16 held experts of 2304 x 1792 (gate and up fused) and 896 x 2304
    over a pass of 131,072 sorted pairs, 17 groups: the forward, d lhs (the
    same kernel, weights transposed) and d rhs (``tgmm``), by name."""
    from deepspeed_tpu.ops.transformer.grouped_matmul import (
        moe_grouped_matmul)

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    for k, n in ((2304, 1792), (896, 2304)):
        text = _compile(jax.grad(
            lambda l, r, g: jnp.sum(moe_grouped_matmul(
                l, r, g, tiling=(512, 1024, 1024)).astype(jnp.float32) ** 2),
            argnums=(0, 1)),
            s((131072, k)), s((16, k, n)), s((17,), jnp.int32))
        assert text.count('custom_call_target="tpu_custom_call"') == 3
        calls = re.findall(
            r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"', text)
        # a differentiated forward is ``jvp_jit_moe_grouped_matmul__``: one
        # pattern, ``moe_grouped_matmul``, finds all three in a trace
        assert all("moe_grouped_matmul" in name for name in calls), calls
        assert sum("bwd_lhs" in name for name in calls) == 1
        assert sum("bwd_rhs" in name for name in calls) == 1


def test_the_trained_expert_layer_gathers_no_row_nobody_here_owns(v5e):
    """Mellum's layer a chip (32,768 tokens of 2,304, 8 of 64 experts a
    token, 16 held, a pass of 131,072 rows), forward and backward under
    ``reverse``: both ways out — the choices' weighted sum and d x — are
    ``moe_gather_combine`` through Mosaic, so no gather gives a ``[32768,
    2304]`` array; the ways in stay XLA's gathers of ``[131072, 2304]``."""
    from deepspeed_tpu.models import expert_shard

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    def loss(x, w, experts, ids, valid):
        y, _ = expert_shard.held_experts_ffn(
            x, w, ids, valid, experts, first_expert=0, interpret=False,
            tiling=(512, 1024, 1024), routed=64, reverse=True)
        return jnp.sum(y * y)

    text = _compile(
        jax.grad(loss, argnums=(0, 1, 2)), s((32768, 2304)),
        s((32768, 8), jnp.float32),
        {"gate_up": s((16, 2304, 1792)), "down": s((16, 896, 2304))},
        s((32768, 8), jnp.int32), s((32768,), jnp.bool_))
    calls = re.findall(
        r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    # the first pass, and the one further pass the routing could need
    # under its ``cond``: each its two ways out
    assert sum(name.startswith("moe_gather_combine") for name in calls) == 4
    gathered = re.findall(r"= \w+\[([\d,]+)\]\S* gather\(", text)
    assert "32768,2304" not in gathered
    # x[token] and g[token] of the first pass; of the further one x[token]
    # forward and recomputed, and g[token]
    assert gathered.count("131072,2304") == 5


def test_a_recomputed_mellum_layer_keeps_its_routing(v5e, monkeypatch):
    """The expert half of a Mellum layer at the cell's size (4 rows of
    8,192, 8 of 64 experts a token, 16 held) under ``jax.checkpoint`` with
    the model's ``SAVED_NAMES``, loss and gradient both taken: the choice
    and the sort are made ONCE — one sort of the 262,144 pairs and one of
    the scores (``top_k``), not two — and the index arithmetic scatters
    and gathers no scalars but d weights' way back: no group-size
    histogram (``s32[17]``), no scatter of the places, no scatter of ``d
    weights`` into the scores' shape, no gather of a pass's 131,072 pairs
    or of their weights, no histogram of the aux loss's load.  The ways in and
    out are the ones ``test_the_trained_expert_layer_gathers_no_row_nobody_
    here_owns`` counts."""
    from benchmarks import common
    from benchmarks.models import mellum as bench_model
    from deepspeed_tpu.models import mellum

    monkeypatch.setattr(mellum, "current_platform", lambda: "tpu")
    spec = common.load_cell("mellum2_ep4.code8k")
    model = bench_model.build_program_model(
        spec["config"]["model_config"], spec["traffic"])

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    def loss(lp, x):
        # linear in y, as the residual stream is: nothing on the way back
        # reads the recomputed forward's sum
        y, reports = model._moe(lp, x)
        return jnp.sum(y) + reports[0]

    layer = jax.tree_util.tree_map(
        s, model.param_shapes()["layers"]["layer_0"],
        is_leaf=lambda x: isinstance(x, tuple))
    text = _compile(
        jax.value_and_grad(jax.checkpoint(
            loss, policy=jax.checkpoint_policies.save_only_these_names(
                *mellum.SAVED_NAMES)), argnums=(0, 1)),
        layer, s((4, 8192, 2304), jnp.float32))
    sorts = re.findall(r"= \(?(\w+\[[\d,]+\])\S*[^=\n]*? sort\(", text)
    assert sorted(sorts) == ["f32[32768,64]", "s32[262144]"], sorts
    scattered = re.findall(r"= (\w+\[[\d,]+\])\S* scatter\(", text)
    for shape in ("s32[17]", "f32[32768,64]", "f32[2097152]", "f32[64]",
                  "f32[4,64]", "f32[256]"):
        assert shape not in scattered, (shape, scattered)
    # places are counted, not scattered; the one scatter of scalars left
    # is d weights, a pass's held rows back to (token, choice): the first
    # pass's and the further one's under its ``cond``
    assert "s32[262144]" not in scattered
    assert scattered.count("f32[393216]") == 2
    # no pass's pairs gathered out of the order, no pair's weight gathered
    # out of ``[tokens, top_k]``, no dot gathered back by row
    gathers = re.findall(r"= (\w+\[[\d,]+\])\S* gather\(", text)
    for shape in ("s32[131072]", "f32[131072]", "f32[32768,8]",
                  "f32[262144]"):
        assert shape not in gathers, (shape, gathers)
    gathered = [shape.split("[")[1][:-1] for shape in gathers]
    calls = re.findall(
        r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    assert sum(name.startswith("moe_gather_combine") for name in calls) == 4
    assert "32768,2304" not in gathered
    # a pass's x[token] forward and recomputed, and its g[token], for the
    # first pass and for the further one under its ``cond``
    assert gathered.count("131072,2304") == 6
