"""Paged decode attention over CMP-managed KV blocks (Pallas TPU kernel).

The serving engine stores KV in fixed-size pages whose lifecycle is governed
by the CMP slot pool (core/slotpool.py): pages are produced (allocated) with
monotone cycles, retired when a request finishes, and reclaimed only outside
the protection window — so a page referenced by an in-flight decode step can
never be recycled underneath it (the paper's UAF guarantee, transplanted).

Each lane's new token attends over the positions its block table already
holds and over itself; the kernel reads only those live pages, straight
from the pool, and takes the new token's k/v from its arguments (the model
writes them into the pool after the call). The grid is a list of (lane,
block) steps of dynamic length, one per block of ``pages_per_block`` live
pages, lanes in order (``schedule``, computed once per model call and shared
by every layer); a lane with nothing cached has one step, for its new token.
A step reads a whole page (every KV head) through each of
``pages_per_block`` inputs of the same pool, whose scalar-prefetched index
map gives that step's page ids. Where a step has fewer live pages than
inputs, the idle inputs repeat the page they held the step before, so the
pipeline sees an unchanged block index and copies nothing: each live page is
read once per call, whatever the query group, and no other page is read.

Numerics: bf16 (or float32) operands on the MXU with float32 accumulation,
and an online softmax in float32. GQA grouping is r-major, as in the model
(query head ``h`` reads KV head ``h % KV``).

Layouts: q [B, H, hd]; k/v_new [B, KV, hd]; k/v pages [L, P, KV, page, hd],
read at ``layer``; block_tables [B, pages_per_seq] int32 (entries past a
lane's live pages are never read); cached_lens [B] int32, the positions each
lane holds before this token.

Latent heads (MLA, ``latent_attention``): the same kernel over a pool of
one head whose k stream holds the normed latent ``c`` and whose v stream
holds the rotary key; every query head of a lane is one row of the group,
its query ``[q̃ | q_rope]``, and the values are the k stream's pages.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Positions per grid step: 8 pages of 16 tokens.
BLOCK_POSITIONS = 128


class Schedule(NamedTuple):
    """The grid of one decode call, shared by every layer: ``steps`` live
    (lane, block) pairs; per step its lane and block, and the page id each
    of the ``pages_per_block`` inputs holds ([T * pages_per_block])."""
    steps: jax.Array
    lane: jax.Array
    blk: jax.Array
    pages: jax.Array


def schedule(block_tables, cached_lens, page: int) -> Schedule:
    """Every lane has at least one step, where its new token is folded in.
    The tables are padded to the static bound ``T = B * ceil(pps / ppb)``
    by repeating the last step. An input with no live page at a step holds
    the page it held at its previous live step, so the pipeline never
    copies for it, and every page the pipeline fetches is a live one."""
    B, pps = block_tables.shape
    ppb = min(max(1, BLOCK_POSITIONS // page), pps)
    T = B * pl.cdiv(pps, ppb)
    npages = jnp.minimum(pl.cdiv(cached_lens, page), pps)
    nblk = jnp.maximum(pl.cdiv(npages, ppb), 1)
    ends = jnp.cumsum(nblk)
    steps = ends[-1]
    t = jnp.arange(T, dtype=jnp.int32)
    t_live = jnp.minimum(t, steps - 1)
    lane = jnp.sum(ends[None, :] <= t_live[:, None], axis=1, dtype=jnp.int32)
    blk = t_live - (ends[lane] - nblk[lane])
    pos = blk[:, None] * ppb + jnp.arange(ppb, dtype=jnp.int32)[None, :]
    live = (t < steps)[:, None] & (pos < npages[lane][:, None])
    raw = block_tables[lane[:, None], jnp.minimum(pos, pps - 1)]
    held = jax.lax.cummax(jnp.where(live, t[:, None], -1), axis=0)
    pages = jnp.take_along_axis(raw, jnp.maximum(held, 0), axis=0)
    # before its first live step an input holds the page it will first
    # read; one that is never live holds the call's first live page
    first = jnp.take_along_axis(raw, jnp.argmax(live, axis=0)[None], axis=0)
    first = jnp.where(live.any(axis=0), first[0], raw.reshape(-1)[
        jnp.argmax(live.reshape(-1))])
    pages = jnp.where(held < 0, first[None, :], pages)
    return Schedule(steps, lane, blk.astype(jnp.int32),
                    pages.reshape(-1).astype(jnp.int32))


def _paged_kernel(layer_ref, lane_ref, blk_ref, pages_ref, cl_ref, q_ref,
                  kn_ref, vn_ref, *refs, ppb: int, page: int, sm_scale: float,
                  latent: bool = False):
    k_refs, v_refs = refs[:ppb], refs[ppb:2 * ppb]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * ppb:]
    t = pl.program_id(0)
    blk = blk_ref[t]
    cached = cl_ref[lane_ref[t]]
    bk = ppb * page

    @pl.when(blk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]                                              # [KV, rep, hd]

    def online(s, v):
        """Fold scores s [KV, rep, n] over values v [KV, n, hd] into the
        running max, sum and accumulator."""
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.where(s > NEG_INF, jnp.exp(s - m_new), 0.0)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.einsum(
            "grt,gtd->grd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if latent:
        # one latent head: the k stream holds c (the values too), the v
        # stream the rotary key; the query is [q̃ | q_rope]
        dc = kn_ref.shape[-1]
        q_c, q_r = q[..., :dc], q[..., dc:]

        def scores(k, v):
            return (jnp.einsum("grd,gtd->grt", q_c, k,
                               preferred_element_type=jnp.float32)
                    + jnp.einsum("grd,gtd->grt", q_r, v,
                                 preferred_element_type=jnp.float32))

        def new_scores(kn):
            rn = vn_ref[...]
            return (jnp.sum(q_c.astype(jnp.float32) * kn.astype(jnp.float32),
                            axis=-1, keepdims=True)
                    + jnp.sum(q_r.astype(jnp.float32) * rn.astype(jnp.float32),
                              axis=-1, keepdims=True))

        def values(k, v):
            return k
    else:
        def scores(k, v):
            return jnp.einsum("grd,gtd->grt", q, k,
                              preferred_element_type=jnp.float32)

        def new_scores(kn):
            return jnp.sum(q.astype(jnp.float32) * kn.astype(jnp.float32),
                           axis=-1, keepdims=True)

        def values(k, v):
            return v

    @pl.when(blk * bk < cached)
    def _pages():
        k = jnp.concatenate([r[...] for r in k_refs], axis=1)  # [KV, bk, hd]
        v = jnp.concatenate([r[...] for r in v_refs], axis=1)
        s = scores(k, v) * sm_scale
        pos = blk * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        online(jnp.where(pos < cached, s, NEG_INF), values(k, v))

    @pl.when((blk + 1) * bk >= cached)
    def _new_token():
        kn = kn_ref[...]                                        # [KV, 1, hd]
        s = new_scores(kn) * sm_scale                           # [KV, rep, 1]
        online(s, kn if latent else vn_ref[...])
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _call(q, k_new, v_new, k_pages, v_pages, cached_lens, sched, layer, *,
          latent: bool, sm_scale: float, name: str, interpret: bool):
    """The kernel over grid ``sched``: q [B, KV, rep, dq], k/v_new
    [B, KV, 1, dk/dv], pages [L, P, KV, page, dk/dv] -> [B, KV, rep, do]
    (``do`` is the k stream's width for a latent head, else the v's)."""
    B, KV, rep, dq = q.shape
    page = k_pages.shape[3]
    dk, dv = k_pages.shape[-1], v_pages.shape[-1]
    do = dk if latent else dv
    ppb = sched.pages.shape[0] // sched.lane.shape[0]

    def lane_block(rows, width):
        return pl.BlockSpec((None, KV, rows, width),
                            lambda t, ly, ln, bl, pg, cl: (ln[t], 0, 0, 0))

    def page_block(j, width):
        return pl.BlockSpec(
            (None, None, KV, page, width),
            lambda t, ly, ln, bl, pg, cl: (ly[0], pg[t * ppb + j], 0, 0, 0))

    if not interpret:
        # The pool stays in HBM: unconstrained, XLA may stage the whole pool
        # in VMEM before the call, a copy of every page, live or not.
        k_pages = pltpu.with_memory_space_constraint(k_pages, pltpu.HBM)
        v_pages = pltpu.with_memory_space_constraint(v_pages, pltpu.HBM)
    kernel = functools.partial(_paged_kernel, ppb=ppb, page=page,
                               sm_scale=sm_scale, latent=latent)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(sched.steps,),
        in_specs=([lane_block(rep, dq), lane_block(1, dk), lane_block(1, dv)]
                  + [page_block(j, dk) for j in range(ppb)]
                  + [page_block(j, dv) for j in range(ppb)]),
        out_specs=lane_block(rep, do),
        scratch_shapes=[pltpu.VMEM((KV, rep, 1), jnp.float32),
                        pltpu.VMEM((KV, rep, 1), jnp.float32),
                        pltpu.VMEM((KV, rep, do), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, rep, do), q.dtype),
        name=name,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), sched.lane, sched.blk,
      sched.pages, cached_lens, q, k_new, v_new, *([k_pages] * ppb),
      *([v_pages] * ppb))


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(
    q: jax.Array,             # [B, H, hd]
    k_new: jax.Array,         # [B, KV, hd]
    v_new: jax.Array,         # [B, KV, hd]
    k_pages: jax.Array,       # [L, P, KV, page, hd]
    v_pages: jax.Array,
    cached_lens: jax.Array,   # [B] int32
    sched: Schedule,          # schedule(block_tables, cached_lens, page)
    layer: jax.Array | int = 0,
    *,
    interpret: bool = False,
) -> jax.Array:
    """Each lane's new token (q, k_new, v_new) attends over the
    ``cached_lens[b]`` positions its block table holds in layer ``layer`` of
    the pool, and over itself. Returns [B, H, hd]."""
    B, H, hd = q.shape
    KV = k_pages.shape[2]
    rep = H // KV
    # r-major groups: head h = r * KV + g reads KV head g
    qg = q.reshape(B, rep, KV, hd).transpose(0, 2, 1, 3)       # [B, KV, rep, hd]
    kn = k_new.astype(k_pages.dtype)[:, :, None]                # [B, KV, 1, hd]
    vn = v_new.astype(v_pages.dtype)[:, :, None]
    out = _call(qg, kn, vn, k_pages, v_pages, cached_lens, sched, layer,
                latent=False, sm_scale=1.0 / (hd ** 0.5),
                name="paged_decode_attention", interpret=interpret)
    return out.transpose(0, 2, 1, 3).reshape(B, H, hd)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def latent_attention(
    q: jax.Array,             # [B, H, dc + dr]: q̃ then q_rope
    c_new: jax.Array,         # [B, dc]
    r_new: jax.Array,         # [B, dr]
    c_pages: jax.Array,       # [L, P, 1, page, dc]
    r_pages: jax.Array,       # [L, P, 1, page, dr]
    cached_lens: jax.Array,   # [B] int32
    sched: Schedule,
    layer: jax.Array | int = 0,
    *,
    sm_scale: float,
    interpret: bool = False,
) -> jax.Array:
    """Absorbed latent attention (MLA decode): every query head of a lane
    reads the lane's one latent head, keys ``[c | k_rope]`` and values
    ``c``, from the same pages and schedule as ``paged_attention``. The
    score is ``q̃·c + q_rope·k_rope``; returns the weighted latent
    [B, H, dc]."""
    B, H, _ = q.shape
    out = _call(q[:, None].astype(c_pages.dtype),
                c_new.astype(c_pages.dtype)[:, None, None],
                r_new.astype(r_pages.dtype)[:, None, None],
                c_pages, r_pages, cached_lens, sched, layer, latent=True,
                sm_scale=sm_scale, name="mla_decode_attention",
                interpret=interpret)
    return out[:, 0]
