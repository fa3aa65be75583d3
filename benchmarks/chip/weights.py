"""The benchmark's own weights and inputs, made on the device from the
seed, in the layout and dtype the program serves them in.

The layout is read from the program's own init with ``jax.eval_shape``
(shapes only; the program makes no value the benchmark uses). Values
follow the configuration file: every matrix ~ N(0, initializer_range);
a norm's ``scale`` (its weight is 1 + scale) ~ N(0, 0.1); a norm's bias
~ N(0, initializer_range).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NORM_SCALE_STD = 0.1


def base_key(seed: int, stream: int):
    """A key for (seed, stream); any whole seed, 64 bits kept."""
    seed %= 2**64
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, seed >> 32)
    return jax.random.fold_in(key, stream)


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def make_params(shapes, init_range: float, key):
    """One jitted call: every leaf of ``shapes`` (a tree of
    ``ShapeDtypeStruct``) drawn from ``key``."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(k):
        out = []
        for i, (path, sds) in enumerate(leaves):
            name = _leaf_name(path)
            std = NORM_SCALE_STD if name == "scale" else init_range
            out.append(std * jax.random.normal(jax.random.fold_in(k, i),
                                               sds.shape, sds.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(key)


def make_tokens(shape, vocab: int, key):
    return jax.jit(lambda k: jax.random.randint(k, shape, 0, vocab,
                                                jnp.int32))(key)


def make_caches(shapes, key):
    """A decode cache as if a prompt had filled it: every position
    ~ N(0, 1), the scale of a key or value projection of a normed input."""
    leaves, treedef = jax.tree_util.tree_flatten(shapes)

    def make(k):
        return jax.tree_util.tree_unflatten(treedef, [
            jax.random.normal(jax.random.fold_in(k, i), s.shape, s.dtype)
            for i, s in enumerate(leaves)])

    return jax.jit(make)(key)
