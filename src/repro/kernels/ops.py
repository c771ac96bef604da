"""Jit'd public wrappers for the Pallas kernels.

Off a TPU, kernels run in interpret mode — the kernel body
executes in Python for correctness validation; on TPU the same calls compile
to Mosaic. Model code calls these; layouts are adapted here.
"""

from __future__ import annotations

import jax

from repro.kernels import cmp_claim as _claim
from repro.kernels import flash_attention as _fa
from repro.kernels import paged_attention as _pa


def on_tpu() -> bool:
    """True when JAX's default device is a TPU: kernels compile to Mosaic.
    Elsewhere they run in interpret mode (or as their jnp oracle)."""
    return jax.devices()[0].platform == "tpu"


def flash_attention(q, k, v, *, causal=True, sliding_window=0,
                    block_q=128, block_k=128):
    """Model layout: q [B, S, H, hd]; k/v [B, T, KV, hd] -> [B, S, H, hd]."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    S = q.shape[1]
    bq = min(block_q, max(16, 1 << (S - 1).bit_length()))
    bk = min(block_k, bq)
    out = _fa.flash_attention(qt, kt, vt, causal=causal,
                              sliding_window=sliding_window,
                              block_q=bq, block_k=bk, interpret=not on_tpu())
    return out.transpose(0, 2, 1, 3)


def paged_attention(q, k_new, v_new, k_pages, v_pages, cached_lens, sched,
                    layer=0, *, sm_scale):
    """Decode attention of each lane's new token over its cached pages and
    itself: q [B, H, d]; k/v_new [B, KV, d]; pages [L, P, KV, page, d],
    read at ``layer``; ``sched`` from ``paged_schedule``, one per call of
    the model; scores scaled by ``sm_scale`` -> [B, H, d]."""
    return _pa.paged_attention(q, k_new, v_new, k_pages, v_pages,
                               cached_lens, sched, layer, sm_scale=sm_scale,
                               interpret=not on_tpu())


def latent_attention(q, c_new, r_new, c_pages, r_pages, cached_lens, sched,
                     layer=0, *, sm_scale):
    """Absorbed MLA decode of each lane's new token over its cached latent
    pages and itself: q [B, H, dc + dr] (q̃ then q_rope); c/r_new [B, dc],
    [B, dr]; pages [L, P, 1, page, dc] and [.., dr] -> the weighted latent
    [B, H, dc]."""
    return _pa.latent_attention(q, c_new, r_new, c_pages, r_pages,
                                cached_lens, sched, layer, sm_scale=sm_scale,
                                interpret=not on_tpu())


paged_schedule = _pa.schedule


_ref_ring_jit = None


def ring_step(state, cycle, meta, req, *, k, window, use_pallas: bool):
    """Fused admission-ring step (reclaim + enqueue-many + k-way claim +
    frontier publish) in ONE device invocation: the Pallas kernel when
    ``use_pallas`` (the ring picks it on a TPU), else the jit'd pure-jnp
    oracle (interpret-mode Pallas is reserved for the equivalence tests)."""
    if use_pallas:
        from repro.kernels import cmp_ring as _ring

        return _ring.cmp_ring_step(state, cycle, meta, req, k=k, window=window)
    global _ref_ring_jit
    if _ref_ring_jit is None:
        from repro.kernels import ref as _ref

        _ref_ring_jit = jax.jit(_ref.ref_ring_step,
                                static_argnames=("k", "window"))
    return _ref_ring_jit(state, cycle, meta, req, k=k, window=window)


def claim(state, cycle, *, k, block_n=None):
    """Fused earliest-claim: (new_state, ids). ids==N => invalid.
    Pools larger than one VMEM block dispatch to the tiled grid kernel
    (block-local k-way min + cross-block merge)."""
    return _claim.cmp_claim(state, cycle, k=k, block_n=block_n,
                            interpret=not on_tpu())
