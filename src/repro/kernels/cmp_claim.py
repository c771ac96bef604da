"""Fused CMP claim (Pallas kernel): earliest-cycle AVAILABLE slot selection +
state transition, tiled over a grid so the pool may exceed one VMEM block.

This is the device analogue of the paper's dequeue Phases 1-2 (scan-cursor
probe + claim CAS): a deterministic k-way earliest-claim over the slot state
and cycle arrays. Two paths:

* single-block (pool fits one VMEM tile): one fused pass computes the k-way
  argmin cascade and the AVAILABLE -> CLAIMED transition in VMEM, avoiding
  the masked key materialization and the separate scatter XLA would emit
  (3 HBM round-trips -> 1);
* tiled (pool larger than one tile): a ``pl.pallas_call`` grid runs the same
  k-way cascade per block, emitting each block's k best (cycle, id)
  candidates; any global winner is necessarily among its block's local top-k,
  so a cross-block lexicographic merge of ``num_blocks x k`` candidates
  (tiny, O(k) per block) recovers the exact global earliest-claim order,
  ties broken by lowest id — bit-identical to the single-block kernel and
  the ``kernels/ref.py`` oracle.

State constants come from the unified protection domain
(:mod:`repro.core.domain`), the same definitions the host queue uses.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.domain import AVAILABLE, CLAIMED

_INT_MAX = jnp.iinfo(jnp.int32).max

# Default tile: state+cycle at 8 bytes/slot -> 16 KiB per block, a lane-
# aligned slice that leaves VMEM headroom for the double-buffered grid.
_DEFAULT_BLOCK = 2048


def _claim_kernel(state_ref, cycle_ref, new_state_ref, ids_ref, *, k: int, n: int):
    """Single-block fused path: k-way cascade + state transition in VMEM."""
    state = state_ref[...].reshape(1, n)
    cycle = cycle_ref[...].reshape(1, n)
    key = jnp.where(state == AVAILABLE, cycle, _INT_MAX)
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    new_state = state
    ids = jnp.zeros((1, k), jnp.int32)
    for i in range(k):  # k is small & static: unrolled argmin cascade
        m = jnp.min(key)
        # lowest index among minima (deterministic tie-break)
        idx = jnp.min(jnp.where(key == m, iota, _INT_MAX))
        found = m != _INT_MAX
        take = found & (iota == idx)
        new_state = jnp.where(take, CLAIMED, new_state)
        key = jnp.where(take, _INT_MAX, key)
        # lane i by a select on a lane iota (Mosaic lowers no scatter)
        ids = jnp.where(lane == i, jnp.where(found, idx, n), ids)
    new_state_ref[...] = new_state.reshape(n)
    ids_ref[...] = ids


def _claim_block_kernel(state_ref, cycle_ref, cand_cycle_ref, cand_id_ref,
                        *, k: int, block_n: int, n: int):
    """Tiled path, per-grid-block body: local k-way min over this tile,
    emitting the k best (cycle, global id) candidates for the merge."""
    b = pl.program_id(0)
    state = state_ref[...]
    cycle = cycle_ref[...]
    gids = jax.lax.broadcasted_iota(jnp.int32, (1, block_n), 1) + b * block_n
    # Padding lanes (gids >= n) were materialized as CLAIMED by the wrapper,
    # but mask them here too so the kernel is safe for any input.
    key = jnp.where((state == AVAILABLE) & (gids < n), cycle, _INT_MAX)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    cand_c = jnp.full((1, k), _INT_MAX, jnp.int32)
    cand_i = jnp.full((1, k), n, jnp.int32)
    for i in range(k):
        m = jnp.min(key)
        idx = jnp.min(jnp.where(key == m, gids, _INT_MAX))
        found = m != _INT_MAX
        take = found & (gids == idx)
        key = jnp.where(take, _INT_MAX, key)
        cand_c = jnp.where(lane == i, jnp.where(found, m, _INT_MAX), cand_c)
        cand_i = jnp.where(lane == i, jnp.where(found, idx, n), cand_i)
    cand_cycle_ref[...] = cand_c
    cand_id_ref[...] = cand_i


def _cmp_claim_tiled(state, cycle, *, k: int, block_n: int, interpret: bool):
    n = state.shape[0]
    nb = -(-n // block_n)  # cdiv
    pad = nb * block_n - n
    state_p = jnp.pad(state, (0, pad), constant_values=CLAIMED) if pad else state
    cycle_p = jnp.pad(cycle, (0, pad)) if pad else cycle
    kernel = functools.partial(_claim_block_kernel, k=k, block_n=block_n, n=n)
    # Tiles are [nb, 1, block_n] with the grid dim squeezed: each block's
    # last two dims (1, block_n) span the whole array, which the TPU block
    # layout accepts for any block_n (a (1, block_n) block of an
    # [nb, block_n] array would not be sublane-aligned).
    cand_c, cand_i = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((None, 1, block_n), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, 1, block_n), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, k), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, 1, k), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, 1, k), jnp.int32),
            jax.ShapeDtypeStruct((nb, 1, k), jnp.int32),
        ],
        interpret=interpret,
    )(state_p.reshape(nb, 1, block_n), cycle_p.reshape(nb, 1, block_n))
    # Cross-block merge: global order is lexicographic (cycle, id) ascending —
    # identical to the fused kernel's cascade and lax.top_k's tie-breaking.
    flat_c = cand_c.reshape(-1)
    flat_i = cand_i.reshape(-1)
    order = jnp.lexsort((flat_i, flat_c))
    sel = order[:k]
    ids = jnp.where(flat_c[sel] != _INT_MAX, flat_i[sel], n).astype(jnp.int32)
    new_state = state.at[ids].set(CLAIMED, mode="drop")  # ids==n dropped
    return new_state, ids


@functools.partial(jax.jit, static_argnames=("k", "block_n", "interpret"))
def cmp_claim(state: jax.Array, cycle: jax.Array, *, k: int,
              block_n: Optional[int] = None, interpret: bool = False):
    """Returns (new_state [N], ids [k]); ids==N marks invalid (pool empty).

    Pools up to ``block_n`` slots take the single fused VMEM pass; larger
    pools take the tiled grid (block-local k-way min + cross-block merge).
    """
    n = state.shape[0]
    bn = block_n or _DEFAULT_BLOCK
    if n > bn:
        return _cmp_claim_tiled(state, cycle, k=k, block_n=bn,
                                interpret=interpret)
    kernel = functools.partial(_claim_kernel, k=k, n=n)
    new_state, ids = pl.pallas_call(
        kernel,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((1, k), jnp.int32),
        ],
        interpret=interpret,
    )(state, cycle)
    return new_state, ids.reshape(k)
