"""Seeded weights in one jitted call: N(0, initializer_range) for every
matrix and table, zeros for biases, ones for layer-norm scales."""

import jax
import jax.numpy as jnp


def seed_key(seed):
    """A key for any whole number: ``--seed`` may exceed 32 signed bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _fill(path, shape, key, index, std):
    leaf = path[-1]
    if leaf == "scale":
        return jnp.ones(shape, jnp.float32)
    if leaf in ("bias", "decoder_bias"):
        return jnp.zeros(shape, jnp.float32)
    return std * jax.random.normal(jax.random.fold_in(key, index), shape,
                                   jnp.float32)


def init_from_shapes(shapes, seed, std, out_shardings=None):
    """``shapes``: nested dict of tuples.  Returns the same tree of float32
    arrays, made on the device in one jitted call."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    paths = [tuple(k.key for k in path) for path, _ in leaves]
    dims = [shape for _, shape in leaves]

    def make(key):
        out = [_fill(p, s, key, i, std)
               for i, (p, s) in enumerate(zip(paths, dims))]
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make, out_shardings=out_shardings)(seed_key(seed))


def leaf_paths(shapes):
    """The leaves' names ("a/b/c"), in ``jax.tree_util`` leaf order."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    return ["/".join(str(k.key) for k in path) for path, _ in leaves]
