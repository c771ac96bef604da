"""The benchmark's weights: random, from the run's seed, made on the device.

One jitted call builds every leaf in the type it is served in, in the
layout the serving program takes (``params["blocks"]["0"]`` stacked over
layers, query head h reading key/value head h % num_kv_heads). The
reference (``configs/dense_reference.py``) reads the same arrays; nothing
here comes from the program. ``run.py`` checks this layout against the
program's own before it serves.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import counts


def _key(seed: int) -> jax.Array:
    seed = int(seed)
    key = jax.random.PRNGKey(abs(seed) & 0xFFFFFFFF)
    return jax.random.fold_in(key, (abs(seed) >> 32) * 2 + (seed < 0))


def shapes(cfg: dict) -> dict:
    m = counts.dims(cfg)
    d, f, h, kv, hd, n, v = (m["d"], m["f"], m["h"], m["kv"], m["hd"],
                             m["layers"], m["vocab"])
    return {
        "embed": (v, d),
        "final_norm": {"scale": (d,)},
        "lm_head": (d, v),
        "blocks": {"0": {
            "ln1": {"scale": (n, d)},
            "attn": {"wq": (n, d, h * hd), "wk": (n, d, kv * hd),
                     "wv": (n, d, kv * hd), "wo": (n, h * hd, d)},
            "ln2": {"scale": (n, d)},
            "mlp": {"wg": (n, d, f), "wu": (n, d, f), "wd": (n, f, d)},
        }},
    }


def _flat(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    return [(jax.tree_util.keystr(p), s) for p, s in flat], treedef


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _init(leaves, dtype: str, std: float, key):
    out = []
    for i, (path, shape) in enumerate(leaves):
        if "scale" in path:
            out.append(jnp.ones(shape, dtype))
        else:
            w = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
            out.append((w * std).astype(dtype))
    return out


def make(cfg: dict, seed: int):
    """Every weight of ``cfg``, N(0, initializer_range) in the served
    dtype, RMSNorm scales 1, from ``seed``."""
    leaves, treedef = _flat(shapes(cfg))
    arrays = _init(tuple(leaves), cfg["torch_dtype"],
                   float(cfg["initializer_range"]), _key(seed))
    return jax.tree_util.tree_unflatten(treedef, arrays)
