"""Device-resident CMP admission ring (Pallas kernel, DESIGN.md §12).

A bounded ring of ``N`` slots living on the accelerator, carrying the CMP
protection domain (:mod:`repro.core.domain` constants) in two int32 arrays
(``state``, ``cycle``) plus a 2-word ``meta`` vector ``[enq_cycle,
deque_cycle]``. One fused kernel invocation — ``cmp_ring_step`` — runs a whole
admission step without a host sync:

* stage R (paper Alg 4): window reclaim — ``CLAIMED`` slots whose cycle fell
  behind ``deque_cycle - W`` return to ``FREE``;
* stage E (paper Alg 1, Phases 1-2): batched enqueue — the ``push_n`` new
  items take the contiguous cycle range ``[enq+1, enq+push_n]``; slot for
  cycle ``c`` is ``(c-1) mod N``, and the *contiguous prefix* whose slots are
  FREE is accepted (stopping at the first occupied slot preserves FIFO cycle
  assignment: no holes in the accepted range). Rejected suffixes fall back to
  the host path;
* stage C (paper Alg 3, Phases 1-3): the k-way earliest-cycle claim cascade —
  the same unrolled argmin cascade as :mod:`repro.kernels.cmp_claim` — claims
  up to ``want`` AVAILABLE slots in cycle order;
* stage P (paper Alg 3, Phase 5): monotone frontier publish,
  ``deque_cycle' = max(deque_cycle, max claimed cycle)``.

The payload handle IS the cycle number (unique, monotone), so the kernel
returns claimed *cycles*; the host keeps an authoritative cycle -> envelope
mirror (see :mod:`repro.serving.admission`).

``ref.ref_ring_step`` is the bit-exact pure-jnp oracle; it doubles as the
fast compiled path on hosts without a TPU (host-fallback rules: DESIGN.md
§12).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.domain import AVAILABLE, CLAIMED, FREE

_INT_MAX = jnp.iinfo(jnp.int32).max


def _ring_kernel(state_ref, cycle_ref, meta_ref, req_ref,
                 new_state_ref, new_cycle_ref, new_meta_ref, claimed_ref,
                 *, k: int, n: int, window: int):
    state = state_ref[...].reshape(1, n)
    cycle = cycle_ref[...].reshape(1, n)
    enq = meta_ref[0]
    dc = meta_ref[1]
    push_n = req_ref[0]
    want = req_ref[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)

    # Stage R: window reclaim (Alg 4) — monotone, coordination-free.
    freeable = (state == CLAIMED) & (cycle < dc - window)
    state = jnp.where(freeable, FREE, state)

    # Stage E: batched enqueue (Alg 1). Slot j hosts candidate cycle
    # enq+1+off_j with off_j = (j - enq) mod n; accept the contiguous
    # offset prefix whose slots are FREE.
    off = jnp.mod(iota - enq, n)
    blocked = (off < push_n) & (state != FREE)
    accepted = jnp.min(jnp.where(blocked, off, push_n))
    take = off < accepted
    state = jnp.where(take, AVAILABLE, state)
    cycle = jnp.where(take, enq + 1 + off, cycle)

    # Stage C: k-way earliest-claim cascade (Alg 3 Phases 1-3), masked to
    # the first `want` lanes. k is small & static: unrolled. Claim lane i is
    # written by a select on a lane iota (Mosaic lowers no scatter).
    key = jnp.where(state == AVAILABLE, cycle, _INT_MAX)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    claimed = jnp.full((1, k), -1, jnp.int32)
    max_claimed = dc
    for i in range(k):
        m = jnp.min(key)
        idx = jnp.min(jnp.where(key == m, iota, _INT_MAX))
        found = (m != _INT_MAX) & (i < want)
        tk = found & (iota == idx)
        state = jnp.where(tk, CLAIMED, state)
        key = jnp.where(tk, _INT_MAX, key)
        claimed = jnp.where(lane == i, jnp.where(found, m, -1), claimed)
        max_claimed = jnp.where(found, jnp.maximum(max_claimed, m), max_claimed)

    # Stage P: monotone frontier publish (Alg 3 Phase 5).
    new_meta_ref[0] = enq + accepted
    new_meta_ref[1] = max_claimed
    new_state_ref[...] = state.reshape(n)
    new_cycle_ref[...] = cycle.reshape(n)
    claimed_ref[...] = claimed


@functools.partial(jax.jit, static_argnames=("k", "window", "interpret"))
def cmp_ring_step(state: jax.Array, cycle: jax.Array, meta: jax.Array,
                  req: jax.Array, *, k: int, window: int,
                  interpret: bool = False):
    """One fused admission step over the device ring.

    Args:
      state, cycle: int32 [N] slot arrays (domain constants / cycle stamps).
      meta: int32 [2] = [enq_cycle, deque_cycle].
      req: int32 [2] = [push_n, want] (dynamic; push_n is clamped to N).
    Returns (new_state, new_cycle, new_meta, claimed_cycles[k]); claimed
    entries are cycle numbers, -1 marks an unfilled claim lane. The number
    of accepted pushes is ``new_meta[0] - meta[0]``.
    """
    n = state.shape[0]
    req = jnp.stack([jnp.minimum(req[0], n), req[1]]).astype(jnp.int32)
    kernel = functools.partial(_ring_kernel, k=k, n=n, window=window)
    new_state, new_cycle, new_meta, claimed = pl.pallas_call(
        kernel,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.int32),
            jax.ShapeDtypeStruct((1, k), jnp.int32),
        ],
        interpret=interpret,
    )(state, cycle, meta, req)
    return new_state, new_cycle, new_meta, claimed.reshape(k)
