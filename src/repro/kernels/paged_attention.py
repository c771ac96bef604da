"""Paged decode attention over CMP-managed KV blocks (Pallas TPU kernel).

The serving engine stores KV in fixed-size pages whose lifecycle is governed
by the CMP slot pool (core/slotpool.py): pages are produced (allocated) with
monotone cycles, retired when a request finishes, and reclaimed only outside
the protection window — so a page referenced by an in-flight decode step can
never be recycled underneath it (the paper's UAF guarantee, transplanted).

TPU adaptation: instead of CUDA-style gather loads, the page indirection uses
*scalar prefetch* — block tables are SMEM-prefetched scalars consumed by the
BlockSpec index_map, so the pipeline DMAs exactly the pages each sequence
needs from HBM into VMEM. The last grid axis (pages) iterates sequentially,
carrying the online-softmax state in VMEM scratch.

Layouts: q [B, H, hd] (one decode token); k/v pages [P, KV, page, hd];
block_tables [B, pages_per_seq] int32; seq_lens [B] int32. Inside the call q
and the output are viewed as [B, H, 1, hd], so each (1, hd) block spans the
last two dims whole, as the TPU block layout requires.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(bt_ref, sl_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                  l_ref, *, page: int, sm_scale: float):
    b = pl.program_id(0)
    p = pl.program_id(2)
    np_ = pl.num_programs(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    seq_len = sl_ref[b]

    @pl.when(p * page < seq_len)
    def _compute():
        q = q_ref[...].astype(jnp.float32)                       # [1, hd]
        k = k_ref[...].astype(jnp.float32)                       # [page, hd]
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * sm_scale  # [1, page]
        pos = p * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        mask = pos < seq_len
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        pr = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(pr, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(pr, v)
        m_ref[...] = m_new

    @pl.when(p == np_ - 1)
    def _out():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(
    q: jax.Array,             # [B, H, hd]
    k_pages: jax.Array,       # [P, KV, page, hd]
    v_pages: jax.Array,       # [P, KV, page, hd]
    block_tables: jax.Array,  # [B, pages_per_seq] int32 (pad with any valid id)
    seq_lens: jax.Array,      # [B] int32
    *,
    interpret: bool = False,
) -> jax.Array:
    B, H, hd = q.shape
    P, KV, page, _ = k_pages.shape
    pps = block_tables.shape[1]
    sm_scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(_paged_kernel, page=page, sm_scale=sm_scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, pps),
        in_specs=[
            pl.BlockSpec((None, None, 1, hd),
                         lambda b, h, p, bt, sl: (b, h, 0, 0)),
            pl.BlockSpec((None, None, page, hd),
                         lambda b, h, p, bt, sl: (bt[b, p], h % KV, 0, 0)),
            pl.BlockSpec((None, None, page, hd),
                         lambda b, h, p, bt, sl: (bt[b, p], h % KV, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, 1, hd),
                               lambda b, h, p, bt, sl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, hd), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, 1, hd), q.dtype),
        interpret=interpret,
    )(block_tables, seq_lens, q.reshape(B, H, 1, hd), k_pages, v_pages)
    return out.reshape(B, H, hd)
