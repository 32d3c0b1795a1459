"""Mixture-of-Experts layer with expert parallelism over the ``expert`` axis.

Beyond-reference capability (the reference predates MoE — SURVEY §2.5 lists
EP as absent): a top-k routed expert FFN whose experts shard over the
``expert`` mesh axis.  Written GSPMD-style: dispatch and combine are
einsums against a routing tensor, with sharding constraints on the
expert-major intermediates — XLA inserts the all-to-all over ICI, exactly
as it inserts ZeRO's reduce-scatters.  No hand-written collective, no
uneven shapes (capacity is static, overflow tokens fall back to the
residual stream).

Routing is *grouped* per sequence (GShard-style): each batch row routes its
own S tokens with capacity ``ceil(k · S / E · capacity_factor)``, so the
dispatch/combine tensors are [B, S, E, C] with C ∝ S/E — linear in total
tokens — instead of the quadratic [T, E, k·T/E] a global route would cost.

Router: top-k gating with the Switch-Transformer load-balancing auxiliary
loss ``E · Σ_e fraction_e · mean_prob_e``.  Top-1 keeps the raw gate
probability as the combine weight (Switch semantics — renormalizing a
single weight to 1 would starve the router of task-loss gradient); top-k>1
renormalizes over the selected experts (GShard semantics).
"""

import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..parallel.mesh import get_current_mesh
from .layers import TransformerLayer, dense, dropout, gelu, layer_norm


def _constrain_expert(t, spec):
    """Sharding constraint against the engine's current mesh; a no-op
    outside an engine/mesh context (plain single-device model calls)."""
    mesh = get_current_mesh()
    if mesh is not None and "expert" in mesh.axis_names:
        return jax.lax.with_sharding_constraint(t, NamedSharding(mesh, spec))
    return t


def _router_dispatch(probs, k, capacity):
    """Routing tensors for ONE group from its gate probabilities.

    probs: [T, E] fp32 softmax.  Returns ``(dispatch [T, E, C] bool,
    combine [T, E, C] fp32, aux_loss scalar)``.
    """
    T, E = probs.shape
    gates = []  # (weight [T], index [T]) per choice
    masked = probs
    for _ in range(k):
        idx = jnp.argmax(masked, axis=-1)
        w = jnp.take_along_axis(masked, idx[:, None], axis=-1)[:, 0]
        gates.append((w, idx))
        masked = masked * (1.0 - jax.nn.one_hot(idx, E, dtype=probs.dtype))

    if k > 1:
        # GShard: kept tokens combine to weight ~1 across their k experts
        total = sum(w for w, _ in gates) + 1e-9
        gates = [(w / total, idx) for w, idx in gates]
    # k == 1 keeps the raw gate probability (Switch): scaling the expert
    # output by the prob is what feeds task-loss gradient to the router

    dispatch = jnp.zeros((T, E, capacity), bool)
    combine = jnp.zeros((T, E, capacity), jnp.float32)
    # running per-expert fill count, so later choices queue behind earlier
    fill = jnp.zeros((E,), jnp.int32)
    for w, idx in gates:
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)  # [T, E]
        pos_in_expert = (jnp.cumsum(onehot, axis=0) - 1) * onehot  # [T, E]
        pos = jnp.sum(pos_in_expert, axis=-1) + fill[idx]  # [T]
        keep = pos < capacity
        slot = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)  # [T, C]
        contrib = (onehot.astype(jnp.float32)[:, :, None] * slot[:, None, :]
                   * keep.astype(jnp.float32)[:, None, None])
        dispatch = dispatch | (contrib > 0.0)
        combine = combine + contrib * w[:, None, None]
        fill = fill + jnp.sum(onehot * keep[:, None].astype(jnp.int32), axis=0)

    # Switch load-balancing loss on the FIRST choice distribution
    first_idx = gates[0][1]
    fraction = jnp.mean(jax.nn.one_hot(first_idx, E, dtype=jnp.float32), axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(fraction * mean_prob)
    return dispatch, combine, aux


class MoEFFN:
    """Routed expert FFN: x [B, S, H] → (y [B, S, H], aux_loss).

    Expert parameters carry a leading ``num_experts`` dim sharded over
    ``expert``; tokens that overflow an expert's per-group capacity
    contribute zero here and survive through the residual connection.
    """

    def __init__(self, hidden_size, intermediate_size, num_experts, k=2,
                 capacity_factor=1.25, initializer_range=0.02):
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_experts = num_experts
        self.k = min(k, num_experts)
        self.capacity_factor = capacity_factor
        self.initializer_range = initializer_range

    def init(self, rng):
        kr, k1, k2 = jax.random.split(rng, 3)
        E, H, I = self.num_experts, self.hidden_size, self.intermediate_size
        s = self.initializer_range
        return {
            "router": {"kernel": jax.random.normal(kr, (H, E), jnp.float32) * s},
            "fc1": {"kernel": jax.random.normal(k1, (E, H, I), jnp.float32) * s,
                    "bias": jnp.zeros((E, I), jnp.float32)},
            "fc2": {"kernel": jax.random.normal(k2, (E, I, H), jnp.float32) * s,
                    "bias": jnp.zeros((E, H), jnp.float32)},
        }

    @staticmethod
    def partition_specs():
        return {"router": {"kernel": P()},
                "fc1": {"kernel": P("expert", None, "model"),
                        "bias": P("expert", "model")},
                "fc2": {"kernel": P("expert", "model", None),
                        "bias": P("expert")}}

    def capacity(self, group_tokens):
        cap = int(math.ceil(self.k * group_tokens / self.num_experts
                            * self.capacity_factor))
        # pad to a sublane multiple so expert blocks tile cleanly
        return max(8, ((cap + 7) // 8) * 8)

    def apply(self, params, x):
        B, S, H = x.shape
        E, C = self.num_experts, self.capacity(S)

        with jax.named_scope("router"):
            logits = (x.astype(jnp.float32)
                      @ params["router"]["kernel"])  # [B, S, E] fp32 routing
            probs = jax.nn.softmax(logits, axis=-1)
            # grouped routing: each sequence routes independently
            dispatch, combine, aux = jax.vmap(
                lambda p: _router_dispatch(p, self.k, C))(probs)
            aux = jnp.mean(aux)

        # expert-major dispatch with the group dim along for the ride; the
        # sharding constraint makes XLA move token blocks to their expert's
        # devices (all-to-all over ICI)
        with jax.named_scope("experts"):
            expert_in = jnp.einsum("bsec,bsh->bech", dispatch.astype(x.dtype),
                                   x)
            expert_in = _constrain_expert(expert_in,
                                          P(None, "expert", None, None))
            h = gelu(jnp.einsum("bech,ehi->beci", expert_in,
                                params["fc1"]["kernel"].astype(x.dtype))
                     + params["fc1"]["bias"].astype(x.dtype)[None, :, None, :])
            out_e = (jnp.einsum("beci,eih->bech", h,
                                params["fc2"]["kernel"].astype(x.dtype))
                     + params["fc2"]["bias"].astype(x.dtype)[None, :, None, :])
            out_e = _constrain_expert(out_e, P(None, "expert", None, None))
            y = jnp.einsum("bsec,bech->bsh", combine.astype(x.dtype), out_e)
        return y, aux


class MoETransformerLayer:
    """Pre-LN decoder/encoder block with a routed-expert FFN.

    The attention half IS a :class:`TransformerLayer` (shared
    ``attention_core`` plus its init/partition specs for the attention
    parameters), so ``attn_impl``/``sparsity_config`` and the memory knobs
    behave identically in dense and MoE blocks.  ``apply`` returns
    ``(y, aux_loss)`` — the model adds ``moe_aux_coef · mean(aux)`` to its
    training objective.
    """

    _ATTN_PARAM_KEYS = ("qkv", "attn_out", "ln_attn", "ln_mlp")

    def __init__(self, hidden_size, heads, num_experts, intermediate_size=None,
                 causal=True, k=2, capacity_factor=1.25,
                 attn_dropout_ratio=0.1, hidden_dropout_ratio=0.1,
                 initializer_range=0.02, layer_norm_eps=1e-5,
                 attn_impl="auto", sparsity_config=None,
                 gelu_checkpoint=False, attn_dropout_checkpoint=False,
                 normalize_invertible=False):
        self.hidden_size = hidden_size
        self.hidden_dropout_ratio = hidden_dropout_ratio
        self.layer_norm_eps = layer_norm_eps
        self.gelu_checkpoint = gelu_checkpoint
        self.attn_dropout_checkpoint = attn_dropout_checkpoint
        self.normalize_invertible = normalize_invertible
        self.attn = TransformerLayer(
            hidden_size=hidden_size, heads=heads, causal=causal,
            attn_dropout_ratio=attn_dropout_ratio,
            hidden_dropout_ratio=hidden_dropout_ratio,
            initializer_range=initializer_range,
            layer_norm_eps=layer_norm_eps, attn_impl=attn_impl,
            sparsity_config=sparsity_config)
        self.moe = MoEFFN(hidden_size, intermediate_size or 4 * hidden_size,
                          num_experts, k=k, capacity_factor=capacity_factor,
                          initializer_range=initializer_range)

    def init(self, rng):
        k1, k2 = jax.random.split(rng)
        # attention params come from the real TransformerLayer init (minus
        # its dense FFN), so layout changes there propagate here
        attn_full = self.attn.init(k1)
        params = {k: attn_full[k] for k in self._ATTN_PARAM_KEYS}
        params["moe"] = self.moe.init(k2)
        return params

    @classmethod
    def partition_specs(cls):
        attn_full = TransformerLayer.partition_specs()
        specs = {k: attn_full[k] for k in cls._ATTN_PARAM_KEYS}
        specs["moe"] = MoEFFN.partition_specs()
        return specs

    def apply(self, params, x, key_padding_mask=None, rng=None,
              deterministic=True):
        r1 = r2 = r3 = None
        if rng is not None and not deterministic:
            r1, r2, r3 = jax.random.split(rng, 3)

        @jax.named_scope("attention")
        def attention_block(p, y):
            ctx = self.attn.attention_core(p, y,
                                           key_padding_mask=key_padding_mask,
                                           attn_rng=r1,
                                           deterministic=deterministic)
            out = dense(p["attn_out"], ctx)
            return dropout(r2, out, self.hidden_dropout_ratio, deterministic)

        @jax.named_scope("moe")
        def moe_block(p, y):
            moe_out, aux = self.moe.apply(p["moe"], y)
            # residual dropout on the FFN path, matching the dense mlp_block
            return dropout(r3, moe_out, self.hidden_dropout_ratio,
                           deterministic), aux

        def ln(p, y):
            return layer_norm(p, y, self.layer_norm_eps)

        # same memory knobs as the dense block (reference kernel flags)
        if self.attn_dropout_checkpoint:
            attention_block = jax.checkpoint(attention_block)
        if self.gelu_checkpoint:
            moe_block = jax.checkpoint(moe_block)
        if self.normalize_invertible:
            ln = jax.checkpoint(ln)

        x = x + attention_block(params, ln(params["ln_attn"], x))
        moe_out, aux = moe_block(params, ln(params["ln_mlp"], x))
        return x + moe_out, aux
