"""Weights from the seed, made by the benchmark on the device in one jitted
call, in the tree that the model's ``init`` would give (only its shapes are
read from the program). Program and reference get the same tree."""

import jax
import jax.numpy as jnp

STD = 0.02


def seed_key(seed: int):
    """A key from any whole number up to 2**33 (the driver's seeds pass
    2**31)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _fill(path, shape, dtype, key):
    name = str(getattr(path[-1], "key", path[-1]))
    noise = STD * jax.random.normal(key, shape, jnp.float32)
    if name == "scale":  # layer norms stay near one
        noise = 1.0 + noise
    return noise.astype(dtype)


def make_params(shapes):
    """``build(key)``, to be jitted: fills the tree of
    ``jax.ShapeDtypeStruct`` with normal(0, 0.02) values (1 + that for a
    layer norm's scale), each leaf from its own fold of ``key``
    (:func:`seed_key`). Biases and scales are not left at 0 and 1, so that
    their gradients, and a fault in them, show."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        return jax.tree_util.tree_unflatten(treedef, [
            _fill(path, leaf.shape, leaf.dtype, jax.random.fold_in(key, i))
            for i, (path, leaf) in enumerate(leaves)
        ])

    return build


def leaf_names(tree):
    """'/'-joined path of every leaf, in flattening order."""
    return [
        "/".join(str(getattr(p, "key", p)) for p in path)
        for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]
    ]
