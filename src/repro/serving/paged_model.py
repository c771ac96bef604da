"""Paged decode/prefill paths: model forward where attention reads/writes
CMP-managed KV pages instead of a dense per-request cache.

Supports attention-bearing families (dense / moe / vlm / audio backbone).
Pages allocated to a request are *sequential in position* (page j covers
positions [j*page, (j+1)*page)), so the gathered page sequence is position-
ordered and the attention mask is a simple length mask.

Two paths, chosen by ``kernel_attention`` from the call's shape and the
model alone. Decode (one token per lane) on a TPU reads each lane's live
pages straight from the pool the call received, through the
``repro.kernels.paged_attention`` Pallas kernel, and writes every layer's
new token into the pool after the layer loop. Everything
else (prefill, softcapped logits, hosts without a TPU) writes each layer's
tokens into its page slice and gathers each lane's whole page table, masked
to its length (``_gathered_attention``).

Latent attention (MLA) keeps one latent head per token and layer
(``kv_cache.page_widths``: c in the k stream, the rotary key in the v
stream) and always reads the pool as the call received it, writing every
layer's new tokens after the layer loop: decode on a TPU through the
kernel's latent mode (absorbed), everything else by gathering each lane's
latent and expanding keys and values from it (unabsorbed). A model's
leading dense layers run before the scan over its pattern, in pool layers
0 .. first_dense_layers - 1.
"""

from __future__ import annotations

from typing import Tuple

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


def _proj(x, p, cfg: ModelConfig, positions):
    """(q, the k and v streams' new rows): GQA's q, k, v; or MLA's
    (q_nope, q_rope), c [B,S,1,dc] and k_rope [B,S,1,dr] zero-padded to the
    v stream's width."""
    if not cfg.is_mla:
        return _proj_qkv(x, p, cfg, positions)
    q_nope, q_rope, c, k_rope = mla.project(x, p, cfg, positions)
    pad = page_widths(cfg)[1][1] - cfg.qk_rope_head_dim
    k_rope = jnp.pad(k_rope, ((0, 0), (0, 0), (0, pad)))
    return (q_nope, q_rope), c[:, :, None], k_rope[:, :, None]


def _scatter_pages(k_pages, v_pages, k_new, v_new, block_tables, positions):
    """k_pages [P,KV,pg,hd]; k_new [B,S,KV,hd]; positions [B,S] absolute."""
    pg = k_pages.shape[2]
    page_rows = jnp.take_along_axis(block_tables, positions // pg, axis=1)  # [B,S]
    slots = positions % pg
    k_pages = k_pages.at[page_rows, :, slots].set(k_new.astype(k_pages.dtype))
    v_pages = v_pages.at[page_rows, :, slots].set(v_new.astype(v_pages.dtype))
    return k_pages, v_pages


def _gathered_attention(q, k_pages, v_pages, block_tables, positions, seq_lens,
                        softcap: float = 0.0):
    """Gather each request's pages and run masked attention.
    q [B,S,H,hd]; returns [B,S,H,hd]."""
    B = q.shape[0]
    P, KV, pg, hd = k_pages.shape
    pps = block_tables.shape[1]
    kg = k_pages[block_tables]  # [B, pps, KV, pg, hd]
    vg = v_pages[block_tables]
    kg = jnp.moveaxis(kg, 2, 3).reshape(B, pps * pg, KV, hd)
    vg = jnp.moveaxis(vg, 2, 3).reshape(B, pps * pg, KV, hd)
    k_pos = jnp.arange(pps * pg, dtype=jnp.int32)[None, :].repeat(B, axis=0)
    k_pos = jnp.where(k_pos < seq_lens[:, None], k_pos, -1)  # mask invalid
    return L.cache_attention(q, kg, vg, positions, k_pos, softcap=softcap)


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
    (and may write) the KV pages, and its ``out`` is handed back with ``x``
    and the block's claims on held experts (None where it has none)."""
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


def _gathered_layers(x, params, cfg: ModelConfig, k_pages, v_pages,
                     block_tables, positions, seq_lens):
    """Each layer writes its tokens into its page slice, then gathers every
    lane's whole table; the scan stacks the written slices."""
    def step(x, xs):
        layer_p, kp, vp = xs
        new_kp, new_vp, routed = [], [], []
        for j, kind in enumerate(cfg.block_pattern):
            def attend(q, k_new, v_new, pa, kp=kp[j], vp=vp[j]):
                kp, vp = _scatter_pages(kp, vp, k_new, v_new, block_tables,
                                        positions)
                return _gathered_attention(q, kp, vp, block_tables, positions,
                                           seq_lens, cfg.attn_softcap), (kp, vp)
            x, (nk, nv), n = _paged_block(x, layer_p[str(j)], cfg, kind,
                                          positions, attend)
            new_kp.append(nk)
            new_vp.append(nv)
            routed.append(n)
        ys = (jnp.stack(new_kp), jnp.stack(new_vp))
        return x, ys + ((_routes_sum(routed),) if cfg.moe_dropless else ())

    r, n_pat = cfg.pattern_repeats, len(cfg.block_pattern)
    kp_s = k_pages.reshape((r, n_pat) + k_pages.shape[1:])
    vp_s = v_pages.reshape((r, n_pat) + v_pages.shape[1:])
    x, ys = jax.lax.scan(step, x, (params["blocks"], kp_s, vp_s))
    n = jnp.sum(ys[2]) if cfg.moe_dropless else None
    return x, ys[0].reshape(k_pages.shape), ys[1].reshape(v_pages.shape), n


def _layer_pool(k_pages, v_pages, layer):
    """(k, v, layer to read): the pool where it lies when its pages are
    whole 128-lane tiles, else layer ``layer``'s slice. A pool whose rows
    fill whole tiles is stored page by page, and the kernel reads it in
    place. Otherwise XLA stores it with the page index minor (no padding;
    phi3-mini's head_dim 96), so no page is contiguous: the kernel then
    reads each layer's slice, which XLA relayouts layer by layer (the whole
    pool at once does not fit beside phi3-mini's weights)."""
    if k_pages.shape[-1] % 128 == 0 and v_pages.shape[-1] % 128 == 0:
        return k_pages, v_pages, layer
    return (jax.lax.dynamic_slice_in_dim(k_pages, layer, 1),
            jax.lax.dynamic_slice_in_dim(v_pages, layer, 1), 0)


def _kernel_attention(cfg, k_pages, v_pages, block_tables, seq_lens):
    """Decode attention through the Pallas kernel, reading the pool as the
    call received it (the lanes' earlier tokens) plus the step's own token:
    ``attention(q, k_new, v_new, p_attn, layer) -> attn``."""
    sched = kops.paged_schedule(block_tables, seq_lens, k_pages.shape[3])

    def gqa(q, k_new, v_new, pa, layer):
        kp, vp, at = _layer_pool(k_pages, v_pages, layer)
        with jax.named_scope("paged_decode_attention"):
            attn = kops.paged_attention(
                q[:, 0], k_new[:, 0], v_new[:, 0], kp, vp, seq_lens, sched, at)
        return attn[:, None]

    def latent(q, c_new, r_new, pa, layer):
        q_nope, q_rope = q
        q_lat = mla.absorb(q_nope[:, 0], pa, cfg)                 # [B, H, dc]
        pad = r_new.shape[-1] - q_rope.shape[-1]
        q_r = jnp.pad(q_rope[:, 0], ((0, 0), (0, 0), (0, pad)))
        kp, vp, at = _layer_pool(k_pages, v_pages, layer)
        with jax.named_scope("mla_decode_attention"):
            o_lat = kops.latent_attention(
                jnp.concatenate([q_lat, q_r.astype(q_lat.dtype)], -1),
                c_new[:, 0, 0], r_new[:, 0, 0], kp, vp, seq_lens, sched, at,
                sm_scale=mla.softmax_scale(cfg))
        return mla.expand(o_lat, pa, cfg)[:, None]

    return latent if cfg.is_mla else gqa


def _latent_gathered_attention(cfg, k_pages, v_pages, block_tables, positions,
                               seq_lens):
    """Latent attention without the kernel (prefill; decode off a TPU):
    each lane's cached latent gathered from the pool as the call received
    it, masked to its ``seq_lens``, then the new tokens; keys and values
    expanded from the latent (unabsorbed)."""
    B = block_tables.shape[0]
    pps, pg = block_tables.shape[1], k_pages.shape[3]
    k_pos = jnp.arange(pps * pg, dtype=jnp.int32)[None, :].repeat(B, axis=0)
    k_pos = jnp.concatenate(
        [jnp.where(k_pos < seq_lens[:, None], k_pos, -1), positions], axis=1)

    def latent(q, c_new, r_new, pa, layer):
        q_nope, q_rope = q
        dr = q_rope.shape[-1]
        c = k_pages[layer, block_tables].reshape(B, pps * pg, -1)
        r = v_pages[layer, block_tables].reshape(B, pps * pg, -1)
        c = jnp.concatenate([c, c_new[:, :, 0].astype(c.dtype)], axis=1)
        r = jnp.concatenate([r, r_new[:, :, 0].astype(r.dtype)], axis=1)
        return mla.attend(q_nope, q_rope, c, r[..., :dr], pa, cfg, positions,
                          k_pos)

    return latent


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


def _read_then_write_layers(x, params, cfg: ModelConfig, k_pages, v_pages,
                            block_tables, positions, attention):
    """Every layer reads the pool as the call received it through
    ``attention(q, k_new, v_new, p_attn, layer)``; after the layer loop
    each layer's new tokens are written. (A slice of the scan's stacked
    output would be copied whole for the kernel, every layer.) The leading
    dense layers run first, in pool layers 0 .. first_dense_layers - 1."""
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
    if kernel_attention(S, cfg):
        x, k_pages, v_pages, n = _read_then_write_layers(
            x, params, cfg, k_pages, v_pages, block_tables, positions,
            _kernel_attention(cfg, k_pages, v_pages, block_tables, seq_lens))
    elif cfg.is_mla:
        x, k_pages, v_pages, n = _read_then_write_layers(
            x, params, cfg, k_pages, v_pages, block_tables, positions,
            _latent_gathered_attention(cfg, k_pages, v_pages, block_tables,
                                       positions, seq_lens))
    else:
        assert not cfg.first_dense_layers, (
            "leading dense layers are served with latent attention only")
        x, k_pages, v_pages, n = _gathered_layers(
            x, params, cfg, k_pages, v_pages, block_tables, positions,
            seq_lens + S)
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
