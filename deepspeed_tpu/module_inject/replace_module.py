"""Module injection: swap HuggingFace (Flax) BERT layers for the
framework's fused transformer layer, by pure weight surgery.

TPU-native analog of the reference ``deepspeed/module_inject/
replace_module.py:6-193``: the reference walks an ``nn.Module`` tree and
replaces ``BertLayer`` instances with ``DeepSpeedTransformerLayer``,
concatenating q/k/v weights into the fused qkv parameter; the revert path
restores the original module for checkpoint export.  Parameters in JAX are
plain pytrees, so injection is a pytree→pytree transform:

- :func:`inject_bert_layer` / :func:`revert_bert_layer` — one encoder
  layer's HF Flax params ↔ ``TransformerLayer`` params (qkv concat, the
  reference's ``replace_transformer_layer`` weight copy).
- :func:`replace_transformer_layer` — full HF ``FlaxBertModel`` encoder
  params → ``{layer_i: our params}`` (+ revert).
- :func:`replace_module` — generic walker applying a policy at every
  matching subtree (reference ``replace_module`` ``:161-193``).

Numerics: our layer is post-LayerNorm with tanh-GELU, matching HF's
``hidden_act='gelu_new'``; exact-GELU checkpoints differ only in the MLP
activation (<1e-3 in bf16).
"""

import jax.numpy as jnp


def inject_bert_layer(hf_layer):
    """HF FlaxBertLayer params → ``TransformerLayer`` params (qkv fused)."""
    att = hf_layer["attention"]
    self_att = att["self"]
    qkv_kernel = jnp.concatenate(
        [self_att["query"]["kernel"], self_att["key"]["kernel"],
         self_att["value"]["kernel"]], axis=1)
    qkv_bias = jnp.concatenate(
        [self_att["query"]["bias"], self_att["key"]["bias"],
         self_att["value"]["bias"]], axis=0)
    return {
        "qkv": {"kernel": qkv_kernel, "bias": qkv_bias},
        "attn_out": {"kernel": att["output"]["dense"]["kernel"],
                     "bias": att["output"]["dense"]["bias"]},
        "fc1": {"kernel": hf_layer["intermediate"]["dense"]["kernel"],
                "bias": hf_layer["intermediate"]["dense"]["bias"]},
        "fc2": {"kernel": hf_layer["output"]["dense"]["kernel"],
                "bias": hf_layer["output"]["dense"]["bias"]},
        "ln_attn": {"scale": att["output"]["LayerNorm"]["scale"],
                    "bias": att["output"]["LayerNorm"]["bias"]},
        "ln_mlp": {"scale": hf_layer["output"]["LayerNorm"]["scale"],
                   "bias": hf_layer["output"]["LayerNorm"]["bias"]},
    }


def revert_bert_layer(ours, hidden_size):
    """``TransformerLayer`` params → HF FlaxBertLayer params (checkpoint
    export; reference revert path)."""
    h = hidden_size
    k = ours["qkv"]["kernel"]
    b = ours["qkv"]["bias"]
    return {
        "attention": {
            "self": {
                "query": {"kernel": k[:, :h], "bias": b[:h]},
                "key": {"kernel": k[:, h:2 * h], "bias": b[h:2 * h]},
                "value": {"kernel": k[:, 2 * h:], "bias": b[2 * h:]},
            },
            "output": {
                "dense": {"kernel": ours["attn_out"]["kernel"],
                          "bias": ours["attn_out"]["bias"]},
                "LayerNorm": {"scale": ours["ln_attn"]["scale"],
                              "bias": ours["ln_attn"]["bias"]},
            },
        },
        "intermediate": {"dense": {"kernel": ours["fc1"]["kernel"],
                                   "bias": ours["fc1"]["bias"]}},
        "output": {
            "dense": {"kernel": ours["fc2"]["kernel"],
                      "bias": ours["fc2"]["bias"]},
            "LayerNorm": {"scale": ours["ln_mlp"]["scale"],
                          "bias": ours["ln_mlp"]["bias"]},
        },
    }


def replace_transformer_layer(hf_encoder_params, revert=False,
                              hidden_size=None):
    """Convert every layer of an HF Flax BERT encoder param tree
    (``{'layer': {'0': ..., '1': ...}}`` or ``{'0': ...}``) to fused-layer
    params keyed ``layer_i`` — or back with ``revert=True`` (reference
    ``replace_transformer_layer``, ``module_inject/replace_module.py:6``).
    """
    layers = hf_encoder_params.get("layer", hf_encoder_params)
    out = {}
    for key, sub in layers.items():
        idx = int(str(key).split("_")[-1]) if not str(key).isdigit() else int(key)
        if revert:
            assert hidden_size is not None, "revert needs hidden_size"
            out[str(idx)] = revert_bert_layer(sub, hidden_size)
        else:
            out[f"layer_{idx}"] = inject_bert_layer(sub)
    return out


def inject_gpt2_layer(hf_block):
    """HF FlaxGPT2Block params → ``TransformerLayer`` params.

    GPT-2's ``c_attn`` already stores the fused ``[h, 3h]`` qkv kernel
    (HF keeps the original TF Conv1D layout, which in Flax lands as a
    plain ``[in, out]`` dense kernel), so unlike the BERT policy there
    is no concat — the surgery is a pure re-keying: ``ln_1``/``ln_2``
    become the pre-LN ``ln_attn``/``ln_mlp`` our layer's
    ``pre_layer_norm`` path reads."""
    att = hf_block["attn"]
    mlp = hf_block["mlp"]
    return {
        "qkv": {"kernel": att["c_attn"]["kernel"],
                "bias": att["c_attn"]["bias"]},
        "attn_out": {"kernel": att["c_proj"]["kernel"],
                     "bias": att["c_proj"]["bias"]},
        "fc1": {"kernel": mlp["c_fc"]["kernel"],
                "bias": mlp["c_fc"]["bias"]},
        "fc2": {"kernel": mlp["c_proj"]["kernel"],
                "bias": mlp["c_proj"]["bias"]},
        "ln_attn": {"scale": hf_block["ln_1"]["scale"],
                    "bias": hf_block["ln_1"]["bias"]},
        "ln_mlp": {"scale": hf_block["ln_2"]["scale"],
                   "bias": hf_block["ln_2"]["bias"]},
    }


def revert_gpt2_layer(ours):
    """``TransformerLayer`` params → HF FlaxGPT2Block params (checkpoint
    export).  Exact inverse of :func:`inject_gpt2_layer` — the fused qkv
    kernel passes through whole, so no ``hidden_size`` is needed."""
    return {
        "ln_1": {"scale": ours["ln_attn"]["scale"],
                 "bias": ours["ln_attn"]["bias"]},
        "attn": {
            "c_attn": {"kernel": ours["qkv"]["kernel"],
                       "bias": ours["qkv"]["bias"]},
            "c_proj": {"kernel": ours["attn_out"]["kernel"],
                       "bias": ours["attn_out"]["bias"]},
        },
        "ln_2": {"scale": ours["ln_mlp"]["scale"],
                 "bias": ours["ln_mlp"]["bias"]},
        "mlp": {
            "c_fc": {"kernel": ours["fc1"]["kernel"],
                     "bias": ours["fc1"]["bias"]},
            "c_proj": {"kernel": ours["fc2"]["kernel"],
                       "bias": ours["fc2"]["bias"]},
        },
    }


def replace_gpt2_transformer_layer(hf_blocks, revert=False):
    """Convert every block of an HF Flax GPT-2 transformer
    (``{'h': {'0': ..., '1': ...}}`` or ``{'0': ...}``) to fused-layer
    params keyed ``layer_i`` — or back with ``revert=True`` — mirroring
    the BERT pair above."""
    blocks = hf_blocks.get("h", hf_blocks)
    out = {}
    for key, sub in blocks.items():
        idx = int(str(key).split("_")[-1]) if not str(key).isdigit() \
            else int(key)
        if revert:
            out[str(idx)] = revert_gpt2_layer(sub)
        else:
            out[f"layer_{idx}"] = inject_gpt2_layer(sub)
    return out


def ingest_gpt2_model(hf_params):
    """Full HF ``FlaxGPT2LMHeadModel`` param tree →
    :class:`~deepspeed_tpu.models.gpt2.GPT2LMHeadTPU` params: embeddings
    remapped (``wte.embedding`` → ``wte``), every block through the
    injection policy, final layernorm carried over.  Accepts either the
    full tree (``{'transformer': {...}}``) or the transformer subtree."""
    t = hf_params.get("transformer", hf_params)
    return {
        "wte": t["wte"]["embedding"],
        "wpe": t["wpe"]["embedding"],
        "blocks": replace_gpt2_transformer_layer(t),
        "ln_f": {"scale": t["ln_f"]["scale"], "bias": t["ln_f"]["bias"]},
    }


def cast_weights(params, dtype):
    """Cast every floating-point leaf of a param tree to ``dtype``
    (serving-time bf16 ingestion; integer leaves — e.g. token tables —
    pass through untouched, and so does a leaf that already has the
    dtype: no second copy of weights handed over ready)."""
    import jax

    def cast(leaf):
        arr = jnp.asarray(leaf)
        if jnp.issubdtype(arr.dtype, jnp.floating) and arr.dtype != dtype:
            return arr.astype(dtype)
        return arr

    return jax.tree_util.tree_map(cast, params)


def replace_module(params, policy, match):
    """Generic walker (reference ``replace_module``, ``:161-193``): apply
    ``policy(subtree)`` to every subtree for which ``match(path, subtree)``
    is True; other nodes copied unchanged.  ``path`` is a '/'-joined key
    string."""

    def walk(node, path):
        if isinstance(node, dict):
            if match(path, node):
                return policy(node)
            return {k: walk(v, f"{path}/{k}" if path else str(k))
                    for k, v in node.items()}
        return node

    return walk(params, "")
