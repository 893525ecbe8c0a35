"""Weights and keys from ``--seed``, made by the benchmark, not the program.

The program gives only the tree's structure (``jax.eval_shape`` of its own
init); every value is drawn here, on the device, in one jitted call per
tree, in the dtype it is served in. The reference draws the same values
from the same seed, leaf by leaf, without the program.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np


def seed_words(seed: int, purpose: int) -> tuple:
    """Two 32-bit words from a seed of any size (``--seed`` may pass 2**31)
    and a purpose tag, so no two uses of one seed share a stream."""
    ss = np.random.SeedSequence([int(seed) & (2**64 - 1), purpose])
    a, b = ss.generate_state(2)
    return int(a), int(b)


def program_seed(seed: int) -> int:
    """A 31-bit seed for the program's own draws (it keeps only 32 bits)."""
    return seed_words(seed, 99)[0] & 0x7FFFFFFF


def leaf_init(path: str, shape, dtype, key):
    """One leaf's values: norms' scales are ones, everything else (kernels,
    embeddings, biases, adapter factors) N(0, 0.02²)."""
    import jax
    import jax.numpy as jnp
    if path.endswith("scale") and len(shape) <= 2:
        return jnp.ones(shape, dtype)
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


def _base_key(seed: int, purpose: int):
    """The base key's raw words, passed to the jitted draws as an argument
    so one compiled program serves every seed."""
    import jax.numpy as jnp
    return jnp.asarray(seed_words(seed, purpose), jnp.uint32)


def _keys(words, paths):
    import jax
    base = jax.random.fold_in(jax.random.key(words[0]), words[1])
    return {p: jax.random.fold_in(base, i) for i, p in enumerate(paths)}


def make_tree(shapes: Dict[str, Any], seed: int, purpose: int):
    """Flat ``{path: ShapeDtypeStruct}`` → flat ``{path: array}``, one
    jitted call."""
    import jax

    paths = sorted(shapes)

    @jax.jit
    def draw(words):
        keys = _keys(words, paths)
        return {p: leaf_init(p, shapes[p].shape, shapes[p].dtype, keys[p])
                for p in paths}

    return draw(_base_key(seed, purpose))


def make_leaf(shapes: Dict[str, Any], seed: int, purpose: int, path: str):
    """The single leaf ``path`` of :func:`make_tree`, bit for bit."""
    import jax

    paths = sorted(shapes)

    @jax.jit
    def draw(words):
        key = _keys(words, paths)[path]
        return leaf_init(path, shapes[path].shape, shapes[path].dtype, key)

    return draw(_base_key(seed, purpose))


def flatten(tree) -> Dict[str, Any]:
    """Nested dict → ``{"a/b/c": leaf}`` (the layout's own key names)."""
    import jax
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out["/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                     for p in path)] = leaf
    return out


def unflatten(flat: Dict[str, Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for path, leaf in flat.items():
        node = out
        *head, last = path.split("/")
        for p in head:
            node = node.setdefault(p, {})
        node[last] = leaf
    return out


def jnp_dtype(name: str):
    """``numpy`` dtype of a name JAX knows (``bfloat16`` included)."""
    import jax.numpy as jnp
    return jnp.dtype(name)
