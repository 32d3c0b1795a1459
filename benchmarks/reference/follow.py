"""Follow the first optimizer steps in the reference: Adam (Kingma & Ba,
with bias correction) on float32 weights, gradients gathered over blocks
of rows."""

import jax
import jax.numpy as jnp
import numpy as np


def leaf_norms(tree):
    """The L2 norm of every leaf, in ``jax.tree_util`` leaf order."""
    return np.asarray(jax.device_get(jax.jit(
        lambda t: jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32)))) for x in jax.tree_util.tree_leaves(t)])
    )(tree)), np.float64)


def _blocks(batch, block_rows):
    rows = next(iter(batch.values())).shape[0]
    for lo in range(0, rows, block_rows):
        yield {k: v[lo:lo + block_rows] for k, v in batch.items()}


def follow_steps(params, init_again, batches, block_loss, batch_totals, hp,
                 block_rows, key, put=jnp.asarray):
    """Train ``params`` (consumed) through ``batches``, one step each.

    Returns ``{"losses", "grad_norms", "delta_norms"}``: each step's loss,
    the per-leaf norm of the first step's gradient, and the per-leaf norm
    of the weights' change after the last step (``init_again()`` makes the
    starting weights anew for that difference)."""
    beta1, beta2 = hp["betas"]
    # gradients and moments live where their weights live (one chip, or
    # spread over the cell's chips)
    where = jax.tree_util.tree_map(lambda x: x.sharding, params)
    grad = jax.jit(jax.value_and_grad(block_loss),
                   out_shardings=(None, where))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=0)

    def adam(p, g, m, v, t):
        m = jax.tree_util.tree_map(
            lambda m, g: beta1 * m + (1 - beta1) * g, m, g)
        v = jax.tree_util.tree_map(
            lambda v, g: beta2 * v + (1 - beta2) * g * g, v, g)
        c1, c2 = 1 - beta1 ** t, 1 - beta2 ** t
        p = jax.tree_util.tree_map(
            lambda p, m, v: p - hp["lr"] * (
                (m / c1) / (jnp.sqrt(v / c2) + hp["eps"])
                + hp["weight_decay"] * p), p, m, v)
        return p, m, v

    adam = jax.jit(adam, donate_argnums=(0, 2, 3),
                   out_shardings=(where, where, where))
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    out = {"losses": []}
    for step, batch in enumerate(batches):
        totals = batch_totals(batch)
        loss, grads = 0.0, None
        for n, block in enumerate(_blocks(batch, block_rows)):
            block = {k: put(x) for k, x in block.items()}
            k = jax.random.fold_in(jax.random.fold_in(key, step), n)
            part, g = grad(params, block, k, totals)
            loss += float(part)
            grads = g if grads is None else add(grads, g)
        out["losses"].append(loss)
        if step == 0:
            out["grad_norms"] = leaf_norms(grads)
        params, m, v = adam(params, grads, m, v, jnp.float32(step + 1))
        del grads
    del m, v
    start = init_again()
    out["delta_norms"] = leaf_norms(jax.jit(
        lambda a, b: jax.tree_util.tree_map(jnp.subtract, a, b),
        donate_argnums=0)(params, start))
    return out
