"""The benchmark's weights: random, from the run's seed, made on the device.

One jitted call builds every leaf in the type it is served in, in the
layout the serving program takes, which the configuration's layout module
describes (``bench/layouts``; ``dense``: ``params["blocks"]["0"]`` stacked
over layers, query head h reading key/value head h % num_kv_heads). The
configuration's reference (``configs/<reference>.py``) reads the same
arrays; nothing here comes from the program. ``run.py`` checks this layout
against the program's own before it serves.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import layouts


def _key(seed: int) -> jax.Array:
    seed = int(seed)
    key = jax.random.PRNGKey(abs(seed) & 0xFFFFFFFF)
    return jax.random.fold_in(key, (abs(seed) >> 32) * 2 + (seed < 0))


def shapes(cfg: dict) -> dict:
    """The parameter tree of ``cfg``'s layout (``bench/layouts``)."""
    return layouts.load(cfg).shapes(cfg)


def _flat(tree, dtype: str):
    """(path, shape, dtype) of every leaf, and the tree's structure; a leaf
    is a shape, or a ``(shape, dtype)`` pair."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    leaves = []
    for p, s in flat:
        shape, dt = s if len(s) == 2 and isinstance(s[1], str) else (s, dtype)
        leaves.append((jax.tree_util.keystr(p), tuple(shape), dt))
    return leaves, treedef


@functools.partial(jax.jit, static_argnums=(0, 1))
def _init(leaves, std: float, key):
    out = []
    for i, (path, shape, dtype) in enumerate(leaves):
        if "scale" in path:
            out.append(jnp.ones(shape, dtype))
        else:
            w = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
            out.append((w * std).astype(dtype))
    return out


def make(cfg: dict, seed: int):
    """Every weight of ``cfg``, N(0, initializer_range) in the served
    dtype (or the leaf's own), RMSNorm scales 1, from ``seed``."""
    leaves, treedef = _flat(shapes(cfg), cfg["torch_dtype"])
    arrays = _init(tuple(leaves), float(cfg["initializer_range"]), _key(seed))
    return jax.tree_util.tree_unflatten(treedef, arrays)
