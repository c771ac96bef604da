"""Paged decode/prefill paths: model forward where attention reads/writes
CMP-managed KV pages instead of a dense per-request cache.

Supports attention-bearing families (dense / moe / vlm / audio backbone).
Pages allocated to a request are *sequential in position* (page j covers
positions [j*page, (j+1)*page)), so the gathered page sequence is position-
ordered and the attention mask is a simple length mask.

The gather formulation lowers to XLA gathers (shardable); on TPU the
``repro.kernels.paged_attention`` Pallas kernel implements the same op with
scalar-prefetch DMA (validated against the same oracle).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
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


def _paged_block(x, p, cfg: ModelConfig, kind: str, k_pages, v_pages,
                 block_tables, positions, seq_lens):
    h_in = L.norm(x, p["ln1"], cfg.norm)
    q, k_new, v_new = _proj_qkv(h_in, p["attn"], cfg, positions)
    k_pages, v_pages = _scatter_pages(k_pages, v_pages, k_new, v_new,
                                      block_tables, positions)
    attn = _gathered_attention(q, k_pages, v_pages, block_tables, positions,
                               seq_lens, cfg.attn_softcap)
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

    def step(carry, xs):
        x = carry
        layer_p, kp, vp = xs
        new_kp, new_vp = [], []
        for j, kind in enumerate(cfg.block_pattern):
            x, nk, nv = _paged_block(x, layer_p[str(j)], cfg, kind,
                                     kp[j], vp[j], block_tables,
                                     positions, seq_lens + S)
            new_kp.append(nk)
            new_vp.append(nv)
        return x, (jnp.stack(new_kp), jnp.stack(new_vp))

    r = cfg.pattern_repeats
    n_pat = len(cfg.block_pattern)
    kp_s = k_pages.reshape((r, n_pat) + k_pages.shape[1:])
    vp_s = v_pages.reshape((r, n_pat) + v_pages.shape[1:])
    x, (new_kp, new_vp) = jax.lax.scan(step, x, (params["blocks"], kp_s, vp_s))
    x = L.norm(x, params["final_norm"], cfg.norm)
    logits = M._logits(x[:, -1:], params, cfg)[:, 0]
    return logits, new_kp.reshape(k_pages.shape), new_vp.reshape(v_pages.shape)


def make_paged_forward(cfg: ModelConfig):
    """The engine's compiled step: ``(params, tokens, k_pages, v_pages,
    block_tables, seq_lens) -> (logits, k_pages', v_pages')``. Prefill and
    decode are this one jit traced at different sequence lengths."""
    return jax.jit(lambda p, t, kp, vp, bt, sl:
                   paged_forward(p, t, cfg, kp, vp, bt, sl))
