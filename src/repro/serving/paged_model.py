"""Paged prefill and decode: the model forward with attention reading and
writing CMP-managed KV pages instead of a dense per-request cache.

Supports attention-bearing families (dense / moe, GQA or latent
attention). Pages allocated to a request are *sequential in position*
(page j covers positions [j*page, (j+1)*page)), so a lane's gathered pages
are position-ordered and the attention mask is a length mask.

One layout: ``kv_cache.page_widths`` sets the width of a pool row, whole
128-lane tiles, so XLA stores each pool page by page and every layer of it
is read where it lies. ``_proj`` pads each layer's new rows to that width.

One layer loop (``_layers``) serves every call, prefill and decode: each
layer reads the pool as the call received it, and after the loop every
layer's new tokens are written, one slice update per lane (decode) or per
page (prefill). A model's leading dense layers run before the scan over
its pattern, in pool layers 0 .. first_dense_layers - 1.

Two attention providers, chosen by ``kernel_attention`` from the call's
shape and the model alone. ``_kernel_attention``: decode on a TPU, through
the ``repro.kernels.paged_attention`` Pallas kernel, which reads only each
lane's live pages (its latent mode for MLA, absorbed).
``_gathered_attention``: everything else; each lane's whole table
gathered, the call's tokens laid over it at the lane's length (MLA expands
keys and values from the latent, unabsorbed).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops as kops
from repro.models import blocks as BL
from repro.models import layers as L
from repro.models import mla
from repro.models import model as M
from repro.serving.kv_cache import page_widths


def _proj_qkv(x, p, cfg: ModelConfig, positions):
    B, S, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (x @ p["wk"]).reshape(B, S, KV, hd)
    v = (x @ p["wv"]).reshape(B, S, KV, hd)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _pad_to(x, width: int):
    """``x`` with zeros after its last axis, up to ``width``."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def _proj(x, p, cfg: ModelConfig, positions):
    """(q, the k and v streams' new rows [B, S, heads, width] zero-padded
    to the pool's row widths): GQA's q, k, v; or MLA's (q_nope, q_rope), c
    and k_rope."""
    if cfg.is_mla:
        q_nope, q_rope, c, k_rope = mla.project(x, p, cfg, positions)
        q, k, v = (q_nope, q_rope), c[:, :, None], k_rope[:, :, None]
    else:
        q, k, v = _proj_qkv(x, p, cfg, positions)
    (_, dk), (_, dv) = page_widths(cfg)
    return q, _pad_to(k, dk), _pad_to(v, dv)


def kernel_attention(S: int, cfg: ModelConfig) -> bool:
    """True where attention over ``S`` new tokens per lane takes the paged
    decode kernel: one token (decode), no logit softcap, on a TPU."""
    return S == 1 and cfg.attn_softcap == 0.0 and kops.on_tpu()


def decode_path(cfg: ModelConfig) -> str:
    """The attention a decode step of ``cfg`` takes (the ``engine.decode``
    span's ``attn``): the paged kernel or the whole-table gather, prefixed
    ``mla_`` for latent attention."""
    path = "kernel" if kernel_attention(1, cfg) else "gather"
    return f"mla_{path}" if cfg.is_mla else path


def _paged_block(x, p, cfg: ModelConfig, kind: str, positions, attend):
    """One block; ``attend(q, k_new, v_new, p_attn) -> (attn, out)`` reads
    the KV pages, and its ``out`` is handed back with ``x`` and the block's
    claims on held experts (None where it has none)."""
    h_in = L.norm(x, p["ln1"], cfg.norm)
    q, k_new, v_new = _proj(h_in, p["attn"], cfg, positions)
    attn, out = attend(q, k_new, v_new, p["attn"])
    B, S = x.shape[0], x.shape[1]
    attn = attn.reshape(B, S, -1) @ p["attn"]["wo"]
    x = x + attn
    routed = None
    if kind == "moe":
        with jax.named_scope("moe_experts"):
            y, _, routed = BL.expert_layer(L.norm(x, p["ln2"], cfg.norm),
                                           p["moe"], cfg)
        x = x + y
    else:
        x = x + L.swiglu(L.norm(x, p["ln2"], cfg.norm), p["mlp"], cfg.act)
    return x, out, routed


def _routes_sum(routed):
    return sum((n for n in routed if n is not None), jnp.zeros((), jnp.int32))


def _kernel_attention(cfg: ModelConfig, k_pages, v_pages, block_tables,
                      seq_lens):
    """Decode attention through the Pallas kernel: each lane's one new
    token over the live pages its table holds in layer ``layer`` of the
    pool as the call received it, and over itself. Returns
    ``attention(q, k_new, v_new, p_attn, layer) -> attn``: GQA, or latent
    attention absorbed (MLA)."""
    sched = kops.paged_schedule(block_tables, seq_lens, k_pages.shape[3])

    def gqa(q, k_new, v_new, pa, layer):
        hd = cfg.resolved_head_dim
        with jax.named_scope("paged_decode_attention"):
            attn = kops.paged_attention(
                _pad_to(q[:, 0], k_new.shape[-1]), k_new[:, 0], v_new[:, 0],
                k_pages, v_pages, seq_lens, sched, layer,
                sm_scale=1.0 / (hd ** 0.5))
        return attn[:, None, :, :hd]

    def latent(q, c_new, r_new, pa, layer):
        q_nope, q_rope = q
        q_lat = mla.absorb(q_nope[:, 0], pa, cfg)                 # [B, H, dc]
        q_r = _pad_to(q_rope[:, 0], r_new.shape[-1])
        with jax.named_scope("mla_decode_attention"):
            o_lat = kops.latent_attention(
                jnp.concatenate([q_lat, q_r.astype(q_lat.dtype)], -1),
                c_new[:, 0, 0], r_new[:, 0, 0], k_pages, v_pages, seq_lens,
                sched, layer, sm_scale=mla.softmax_scale(cfg))
        return mla.expand(o_lat, pa, cfg)[:, None]

    return latent if cfg.is_mla else gqa


def _gathered_attention(cfg: ModelConfig, k_pages, v_pages, block_tables,
                        seq_lens):
    """Attention without the kernel (prefill; decode off a TPU or with a
    logit softcap): each lane's whole table gathered from layer ``layer``
    of the pool as the call received it, cut to the model's widths, the
    call's S new tokens laid over it at ``seq_lens[b]``, and masked to
    ``seq_lens + S``. Returns ``attention(q, k_new, v_new, p_attn, layer)
    -> attn``: GQA, or latent attention with keys and values expanded from
    the latent (MLA, unabsorbed)."""
    B, pps = block_tables.shape
    T = pps * k_pages.shape[3]

    def rows(pages, new, layer):
        """[B, T, heads, d]: the lanes' rows, ``d`` the width of ``new``
        [B, S, heads, d], with ``new`` laid over them, one slice update per
        lane."""
        d = new.shape[-1]
        g = pages[layer, block_tables][..., :d]          # [B, pps, h, pg, d]
        g = jnp.moveaxis(g, 2, 3).reshape(B, T, g.shape[2], d)
        new = new.astype(g.dtype)
        for b in range(B):
            g = jax.lax.dynamic_update_slice(g, new[b:b + 1], (b, seq_lens[b], 0, 0))
        return g

    def positions(S):
        """(the queries' positions [B, S], the rows' [B, T], -1 past each
        lane's length)."""
        k_pos = jnp.arange(T, dtype=jnp.int32)[None, :].repeat(B, axis=0)
        k_pos = jnp.where(k_pos < (seq_lens + S)[:, None], k_pos, -1)
        return seq_lens[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :], k_pos

    def gqa(q, k_new, v_new, pa, layer):
        hd = cfg.resolved_head_dim
        k = rows(k_pages, k_new[..., :hd], layer)
        v = rows(v_pages, v_new[..., :hd], layer)
        return L.cache_attention(q, k, v, *positions(q.shape[1]),
                                 softcap=cfg.attn_softcap)

    def latent(q, c_new, r_new, pa, layer):
        q_nope, q_rope = q
        c = rows(k_pages, c_new, layer)[:, :, 0]
        r = rows(v_pages, r_new[..., :q_rope.shape[-1]], layer)[:, :, 0]
        return mla.attend(q_nope, q_rope, c, r, pa, cfg,
                          *positions(q_rope.shape[1]))

    return latent if cfg.is_mla else gqa


def _write_slots(pages, new, block_tables, positions):
    """Each lane's one new token (new [L, B, KV, d]) into its slot, one
    slice update per lane: it keeps the pool in the layout it came in (a
    scatter here made XLA relayout the whole pool, twice)."""
    pg = pages.shape[3]
    rows = jnp.take_along_axis(block_tables, positions // pg, axis=1)[:, 0]
    slots = positions[:, 0] % pg
    for b in range(new.shape[1]):
        pages = jax.lax.dynamic_update_slice(
            pages, new[:, b, :, None, None].swapaxes(1, 2),
            (0, rows[b], 0, slots[b], 0))
    return pages


def _write_runs(pages, new, block_tables, positions):
    """Each lane's run of S new tokens (new [L, B, S, KV, d]) written page
    by page, one slice update per page and lane, the last page's rows past
    the run zero. The run starts at a page boundary (the engine prefills
    every prompt from position 0); the rows it leaves past its end lie
    beyond the lane's length, which masks them until decode writes them."""
    L_, _, KV, pg, d = pages.shape
    B, S = positions.shape
    n = -(-S // pg)
    runs = jnp.pad(new, ((0, 0), (0, 0), (0, n * pg - S), (0, 0), (0, 0)))
    runs = runs.reshape(L_, B, n, pg, KV, d).swapaxes(3, 4).astype(pages.dtype)
    for b in range(B):
        first = positions[b, 0] // pg
        for j in range(n):
            pages = jax.lax.dynamic_update_slice(
                pages, runs[:, b, j, None], (0, block_tables[b, first + j], 0, 0, 0))
    return pages


def _layers(x, params, cfg: ModelConfig, k_pages, v_pages, block_tables,
            positions, attention):
    """The layer loop of every call: each layer reads the pool as the call
    received it through ``attention(q, k_new, v_new, p_attn, layer)``;
    after the loop every layer's new tokens are written. (A write inside
    the scan would make the pool its stacked output, copied whole for each
    layer's read.) The leading dense layers run first, in pool layers
    0 .. first_dense_layers - 1."""
    n_pat, lead = len(cfg.block_pattern), cfg.first_dense_layers
    S = positions.shape[1]

    def layer(x, p, kind, i):
        def attend(q, k_new, v_new, pa):
            out = (k_new[:, 0], v_new[:, 0]) if S == 1 else (k_new, v_new)
            return attention(q, k_new, v_new, pa, i), out
        return _paged_block(x, p, cfg, kind, positions, attend)

    lead_k, lead_v, routed = [], [], []
    for i in range(lead):
        x, (nk, nv), n = layer(x, M.lead_layer(params, i), "dense", i)
        lead_k.append(nk)
        lead_v.append(nv)
        routed.append(n)

    def step(x, xs):
        layer_p, i = xs
        new_k, new_v, routed = [], [], []
        for j, kind in enumerate(cfg.block_pattern):
            x, (nk, nv), n = layer(x, layer_p[str(j)], kind,
                                   i * n_pat + (lead + j))
            new_k.append(nk)
            new_v.append(nv)
            routed.append(n)
        ys = (jnp.stack(new_k), jnp.stack(new_v))
        return x, ys + ((_routes_sum(routed),) if cfg.moe_dropless else ())

    r = cfg.pattern_repeats
    x, ys = jax.lax.scan(step, x, (params["blocks"], jnp.arange(r, dtype=jnp.int32)))
    # [r, n_pat, B, (S,) KV, d] -> [L, B, (S,) KV, d]
    new_k = ys[0].reshape((-1,) + ys[0].shape[2:]).astype(k_pages.dtype)
    new_v = ys[1].reshape((-1,) + ys[1].shape[2:]).astype(v_pages.dtype)
    if lead:
        new_k = jnp.concatenate([jnp.stack(lead_k).astype(k_pages.dtype), new_k])
        new_v = jnp.concatenate([jnp.stack(lead_v).astype(v_pages.dtype), new_v])
    write = _write_slots if S == 1 else _write_runs
    k_pages = write(k_pages, new_k, block_tables, positions)
    v_pages = write(v_pages, new_v, block_tables, positions)
    n = _routes_sum(routed) + jnp.sum(ys[2]) if cfg.moe_dropless else None
    return x, k_pages, v_pages, n


def paged_forward(params, tokens, cfg: ModelConfig, k_pages, v_pages,
                  block_tables, seq_lens):
    """Shared prefill/decode body. tokens [B, S] start at position seq_lens
    (S=prompt for prefill with seq_lens=0, S=1 for decode).
    k/v_pages: [L_attn, P, KV, pg, d] stacked over attention layers.
    Returns (last-token logits [B, V], k_pages', v_pages'), and where the
    expert layer is dropless the claims the call's tokens made on held
    experts, summed over its layers."""
    x = params["embed"][tokens]
    B, S = tokens.shape
    positions = seq_lens[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    attn_kinds = [k for k in cfg.block_pattern if k in ("dense", "moe")]
    assert len(attn_kinds) == len(cfg.block_pattern), (
        "paged serving supports attention-based families only")
    provider = _kernel_attention if kernel_attention(S, cfg) else _gathered_attention
    x, k_pages, v_pages, n = _layers(
        x, params, cfg, k_pages, v_pages, block_tables, positions,
        provider(cfg, k_pages, v_pages, block_tables, seq_lens))
    x = L.norm(x, params["final_norm"], cfg.norm)
    logits = M._logits(x[:, -1:], params, cfg)[:, 0]
    return (logits, k_pages, v_pages) + ((n,) if cfg.moe_dropless else ())


def make_paged_forward(cfg: ModelConfig):
    """The engine's compiled step: ``(params, tokens, k_pages, v_pages,
    block_tables, seq_lens) -> (logits, k_pages', v_pages')``, and the
    claims on held experts too where the expert layer is dropless. Prefill
    and decode are this one jit traced at different sequence lengths.

    The two pools are donated: the output pools alias the input buffers, so
    the call writes its tokens in place instead of copying the whole pool
    first, and the arrays passed in are deleted by the call."""
    return jax.jit(lambda p, t, kp, vp, bt, sl:
                   paged_forward(p, t, cfg, kp, vp, bt, sl),
                   donate_argnums=(2, 3))
