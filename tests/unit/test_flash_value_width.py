"""Flash forward with a value width of its own (latent attention expands
keys of nope + rope beside narrower values), against
``reference_attention`` through Pallas' interpreter.  The width-64 cases
of ``test_flash_attention.py`` stay as they are."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer.attention import reference_attention
from deepspeed_tpu.ops.transformer.flash_attention import (
    flash_attention, flash_attention_forward)


def _qkv(seq, heads, d, dv, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (2, seq, heads, d), jnp.float32),
            jax.random.normal(keys[1], (2, seq, heads, d), jnp.float32),
            jax.random.normal(keys[2], (2, seq, heads, dv), jnp.float32))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("d,dv,block", [
    (48, 32, 64),    # one tile: the straight-line kernel
    (48, 32, 16),    # streamed: the accumulator is dv wide
    (24, 40, 32),    # values wider than keys
    (32, 32, 32),    # equal widths still go the same way
])
def test_forward_matches_reference(d, dv, block, causal):
    q, k, v = _qkv(64, 3, d, dv)
    got = flash_attention_forward(q, k, v, causal=causal, block_q=block,
                                  block_k=block, interpret=True,
                                  name="mla_prefill_attention")
    want = reference_attention(q, k, v, causal=causal)
    assert got.shape == (2, 64, 3, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_backward_refuses_a_value_width_of_its_own():
    q, k, v = _qkv(32, 2, 48, 32)

    def loss(q, k, v):
        return flash_attention(q, k, v, None, None, True, 32, 32,
                               True).sum()

    with pytest.raises(AssertionError, match="forward-only"):
        jax.grad(loss)(q, k, v)
