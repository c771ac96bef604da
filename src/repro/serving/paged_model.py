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
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops as kops
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import model as M


def _proj_qkv(x, p, cfg: ModelConfig, positions):
    B, S, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (x @ p["wk"]).reshape(B, S, KV, hd)
    v = (x @ p["wv"]).reshape(B, S, KV, hd)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


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


def _paged_block(x, p, cfg: ModelConfig, kind: str, positions, attend):
    """One block; ``attend(q, k_new, v_new) -> (attn, out)`` reads (and may
    write) the KV pages, and its ``out`` is handed back with ``x``."""
    h_in = L.norm(x, p["ln1"], cfg.norm)
    q, k_new, v_new = _proj_qkv(h_in, p["attn"], cfg, positions)
    attn, out = attend(q, k_new, v_new)
    B, S = x.shape[0], x.shape[1]
    attn = attn.reshape(B, S, cfg.num_heads * cfg.resolved_head_dim) @ p["attn"]["wo"]
    x = x + attn
    if kind == "moe":
        y, _ = MOE.moe_block(L.norm(x, p["ln2"], cfg.norm), p["moe"],
                             num_experts=cfg.num_experts,
                             top_k=cfg.num_experts_per_tok,
                             capacity_factor=cfg.capacity_factor, act=cfg.act)
        x = x + y
    else:
        x = x + L.swiglu(L.norm(x, p["ln2"], cfg.norm), p["mlp"], cfg.act)
    return x, out


def _gathered_layers(x, params, cfg: ModelConfig, k_pages, v_pages,
                     block_tables, positions, seq_lens):
    """Each layer writes its tokens into its page slice, then gathers every
    lane's whole table; the scan stacks the written slices."""
    def step(x, xs):
        layer_p, kp, vp = xs
        new_kp, new_vp = [], []
        for j, kind in enumerate(cfg.block_pattern):
            def attend(q, k_new, v_new, kp=kp[j], vp=vp[j]):
                kp, vp = _scatter_pages(kp, vp, k_new, v_new, block_tables,
                                        positions)
                return _gathered_attention(q, kp, vp, block_tables, positions,
                                           seq_lens, cfg.attn_softcap), (kp, vp)
            x, (nk, nv) = _paged_block(x, layer_p[str(j)], cfg, kind,
                                       positions, attend)
            new_kp.append(nk)
            new_vp.append(nv)
        return x, (jnp.stack(new_kp), jnp.stack(new_vp))

    r, n_pat = cfg.pattern_repeats, len(cfg.block_pattern)
    kp_s = k_pages.reshape((r, n_pat) + k_pages.shape[1:])
    vp_s = v_pages.reshape((r, n_pat) + v_pages.shape[1:])
    x, (new_kp, new_vp) = jax.lax.scan(step, x, (params["blocks"], kp_s, vp_s))
    return x, new_kp.reshape(k_pages.shape), new_vp.reshape(v_pages.shape)


def _kernel_decode_layers(x, params, cfg: ModelConfig, k_pages, v_pages,
                          block_tables, positions, seq_lens):
    """Decode: each layer's kernel reads the pool as the call received it
    (the lanes' earlier tokens) plus the step's own token; after the layer
    loop every layer's token is written, one slice update per lane. (A slice
    of the scan's stacked output would be copied whole for the kernel, every
    layer.)"""
    n_pat = len(cfg.block_pattern)
    sched = kops.paged_schedule(block_tables, seq_lens, k_pages.shape[3])
    # A pool whose head_dim fills whole 128-lane tiles is stored page by
    # page, and the kernel reads it where it lies. Otherwise XLA stores it
    # with the page index minor (no padding; phi3-mini's head_dim 96), so no
    # page is contiguous: the kernel then reads each layer's slice, which
    # XLA relayouts layer by layer (the whole pool at once does not fit
    # beside phi3-mini's weights).
    in_place = k_pages.shape[-1] % 128 == 0

    def step(x, xs):
        layer_p, i = xs
        new_k, new_v = [], []
        for j, kind in enumerate(cfg.block_pattern):
            def attend(q, k_new, v_new, layer=i * n_pat + j):
                kp, vp, at = ((k_pages, v_pages, layer) if in_place else
                              (jax.lax.dynamic_slice_in_dim(k_pages, layer, 1),
                               jax.lax.dynamic_slice_in_dim(v_pages, layer, 1),
                               0))
                with jax.named_scope("paged_decode_attention"):
                    attn = kops.paged_attention(
                        q[:, 0], k_new[:, 0], v_new[:, 0], kp, vp, seq_lens,
                        sched, at)
                return attn[:, None], (k_new[:, 0], v_new[:, 0])
            x, (nk, nv) = _paged_block(x, layer_p[str(j)], cfg, kind,
                                       positions, attend)
            new_k.append(nk)
            new_v.append(nv)
        return x, (jnp.stack(new_k), jnp.stack(new_v))

    r = cfg.pattern_repeats
    x, (new_k, new_v) = jax.lax.scan(
        step, x, (params["blocks"], jnp.arange(r, dtype=jnp.int32)))
    # [r, n_pat, B, KV, hd] -> [L, B, KV, hd]
    new_k = new_k.reshape((-1,) + new_k.shape[2:]).astype(k_pages.dtype)
    new_v = new_v.reshape((-1,) + new_v.shape[2:]).astype(v_pages.dtype)
    pg = k_pages.shape[3]
    rows = jnp.take_along_axis(block_tables, positions // pg, axis=1)[:, 0]
    slots = positions[:, 0] % pg
    # one slice update per lane keeps the pool in the layout it came in; a
    # scatter here made XLA relayout the whole pool, twice
    for b in range(x.shape[0]):
        at = (0, rows[b], 0, slots[b], 0)
        k_pages = jax.lax.dynamic_update_slice(
            k_pages, new_k[:, b, :, None, None].swapaxes(1, 2), at)
        v_pages = jax.lax.dynamic_update_slice(
            v_pages, new_v[:, b, :, None, None].swapaxes(1, 2), at)
    return x, k_pages, v_pages


def paged_forward(params, tokens, cfg: ModelConfig, k_pages, v_pages,
                  block_tables, seq_lens) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Shared prefill/decode body. tokens [B, S] start at position seq_lens
    (S=prompt for prefill with seq_lens=0, S=1 for decode).
    k/v_pages: [L_attn, P, KV, pg, hd] stacked over attention layers.
    Returns (last-token logits [B, V], k_pages', v_pages')."""
    x = params["embed"][tokens]
    B, S = tokens.shape
    positions = seq_lens[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    attn_kinds = [k for k in cfg.block_pattern if k in ("dense", "moe")]
    assert len(attn_kinds) == len(cfg.block_pattern), (
        "paged serving supports attention-based families only")
    if kernel_attention(S, cfg):
        x, k_pages, v_pages = _kernel_decode_layers(
            x, params, cfg, k_pages, v_pages, block_tables, positions, seq_lens)
    else:
        x, k_pages, v_pages = _gathered_layers(
            x, params, cfg, k_pages, v_pages, block_tables, positions,
            seq_lens + S)
    x = L.norm(x, params["final_norm"], cfg.norm)
    logits = M._logits(x[:, -1:], params, cfg)[:, 0]
    return logits, k_pages, v_pages


def make_paged_forward(cfg: ModelConfig):
    """The engine's compiled step: ``(params, tokens, k_pages, v_pages,
    block_tables, seq_lens) -> (logits, k_pages', v_pages')``. Prefill and
    decode are this one jit traced at different sequence lengths."""
    return jax.jit(lambda p, t, kp, vp, bt, sl:
                   paged_forward(p, t, cfg, kp, vp, bt, sl))
