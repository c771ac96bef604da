"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
XLA_FLAGS before any jax initialization.

Every mesh here has ``Auto`` axes. The model's sharding is GSPMD-style: the
param rules of :mod:`repro.parallel.sharding` place the weights and
``with_sharding_constraint`` re-anchors activations (``models/model.py``
``_shard_batch``), leaving the compiler to resolve the rest. ``jax.make_mesh``
defaults to ``Explicit`` axes, under which every gather and contraction
must name its output sharding, and the model's rules would not type-check
(the embedding gather of a ``[V:model, D:data]`` table by ``[B:data, S]``
tokens asks for ``data`` twice).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD propagation)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 (512 chips, 2 pods).

    Axes: 'pod' carries only cross-pod gradient reduction; 'data' is
    batch/FSDP; 'model' is TP/EP/sequence-sharding."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for CPU tests (requires >= n_data*n_model host devices)."""
    return make_mesh((n_data, n_model), ("data", "model"))
