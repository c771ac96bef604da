"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1).

A token's keys and values are one low-rank latent per layer:
``c = RMSNorm(x W_kv_a[:, :r])`` (``r = kv_lora_rank``) and one rotary key
``k_rope = RoPE(x W_kv_a[:, r:])`` shared by every head. That is all a cache
keeps: ``r + qk_rope_head_dim`` values per token and layer. Head i expands
the latent into its keys ``c W_UK,i`` and values ``c W_UV,i`` (``W_kv_b``);
its query is ``[q_nope,i, RoPE(q_rope,i)]`` from ``W_q`` (no ``q_lora``).

Two ways to the same scores. Unabsorbed (prefill, the full forward): expand
keys and values for every cached position. Absorbed (decode): fold ``W_UK,i``
into the query, ``q̃_i = W_UK,i q_nope,i``, score the latent itself,
``q̃_i·c_s + q_rope,i·k_rope,s``, sum ``õ_i = Σ_s p_s c_s`` and expand once,
``o_i = õ_i W_UV,i``: each cached position is read as its ``r + rope``
values, whatever the number of heads.

RoPE on the rope dims is YaRN where the configuration scales it
(``layers.rope_tables``), and the softmax scale is
``(nope + rope)^-0.5 * yarn_mscale(factor, mscale_all_dim)^2``.

Parameters: ``wq`` [D, H*(nope+rope)]; ``wkv_a`` [D, r+rope]; ``kv_norm``
{"scale": [r]}; ``wkv_b`` [r, H*(nope+v)], each head's nope key columns
then its value columns; ``wo`` [H*v, D].
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L

NEG_INF = -1e30


def softmax_scale(cfg: ModelConfig) -> float:
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.rope_factor > 1 and cfg.yarn_mscale_all_dim:
        m = L.yarn_mscale(cfg.rope_factor, cfg.yarn_mscale_all_dim)
        scale *= m * m
    return scale


def project(h: jax.Array, p: dict, cfg: ModelConfig, positions: jax.Array):
    """h [B, S, D] -> (q_nope [B,S,H,nope], q_rope [B,S,H,rope] rotated,
    c [B,S,r] normed, k_rope [B,S,rope] rotated)."""
    B, S, _ = h.shape
    H, dn, dr, r = (cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                    cfg.kv_lora_rank)
    freqs, scale = L.rope_tables(cfg, dr)
    q = (h @ p["wq"]).reshape(B, S, H, dn + dr)
    q_rope = L.apply_rope(q[..., dn:], positions, cfg.rope_theta, freqs, scale)
    kv = h @ p["wkv_a"]
    c = L.rms_norm(kv[..., :r], p["kv_norm"]["scale"])
    k_rope = L.apply_rope(kv[..., None, r:], positions, cfg.rope_theta, freqs,
                          scale)[:, :, 0]
    return q[..., :dn], q_rope, c, k_rope


def _heads(p: dict, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """(W_UK [r, H, nope], W_UV [r, H, v]) from ``wkv_b``."""
    dn = cfg.qk_nope_head_dim
    w = p["wkv_b"].reshape(cfg.kv_lora_rank, cfg.num_heads, dn + cfg.v_head_dim)
    return w[..., :dn], w[..., dn:]


def absorb(q_nope: jax.Array, p: dict, cfg: ModelConfig) -> jax.Array:
    """q̃ = W_UK q_nope per head: [..., H, nope] -> [..., H, r]."""
    return jnp.einsum("...hd,chd->...hc", q_nope, _heads(p, cfg)[0])


def expand(o_lat: jax.Array, p: dict, cfg: ModelConfig) -> jax.Array:
    """o = õ W_UV per head: [..., H, r] -> [..., H, v]."""
    return jnp.einsum("...hc,chd->...hd", o_lat, _heads(p, cfg)[1])


def _softmax(s, q_pos, k_pos):
    """Scores [B, H, S, T] masked to valid (k_pos >= 0), causal keys."""
    mask = (k_pos[:, None, :] >= 0) & (q_pos[:, :, None] >= k_pos[:, None, :])
    return jax.nn.softmax(jnp.where(mask[:, None], s, NEG_INF), axis=-1)


def attend(q_nope, q_rope, c, k_rope, p: dict, cfg: ModelConfig,
           q_pos: jax.Array, k_pos: jax.Array) -> jax.Array:
    """Unabsorbed attention of queries at ``q_pos`` [B, S] over the latent
    ``c`` [B, T, r] and ``k_rope`` [B, T, rope] at ``k_pos`` [B, T] (-1:
    empty): keys and values expanded per head. -> [B, S, H, v]."""
    w_uk, w_uv = _heads(p, cfg)
    k_nope = jnp.einsum("btc,chd->bthd", c, w_uk)
    v = jnp.einsum("btc,chd->bthd", c, w_uv)
    s = (jnp.einsum("bshd,bthd->bhst", q_nope, k_nope).astype(jnp.float32)
         + jnp.einsum("bshd,btd->bhst", q_rope, k_rope).astype(jnp.float32))
    w = _softmax(s * softmax_scale(cfg), q_pos, k_pos)
    return jnp.einsum("bhst,bthd->bshd", w.astype(v.dtype), v)


def attend_absorbed(q_nope, q_rope, c, k_rope, p: dict, cfg: ModelConfig,
                    q_pos: jax.Array, k_pos: jax.Array) -> jax.Array:
    """The same attention in decode's arithmetic (no kernel): scores over
    the latent itself, one expansion of the weighted latent."""
    q_lat = absorb(q_nope, p, cfg)
    s = (jnp.einsum("bshc,btc->bhst", q_lat, c).astype(jnp.float32)
         + jnp.einsum("bshd,btd->bhst", q_rope, k_rope).astype(jnp.float32))
    w = _softmax(s * softmax_scale(cfg), q_pos, k_pos)
    o_lat = jnp.einsum("bhst,btc->bshc", w.astype(c.dtype), c)
    return expand(o_lat, p, cfg)


def make_cache(cfg: ModelConfig, batch: int, t_cache: int, dtype) -> L.KVCache:
    """A dense per-request cache of the latent: ``k`` holds c [B, T, 1, r],
    ``v`` holds k_rope [B, T, 1, rope]."""
    return L.KVCache(
        k=jnp.zeros((batch, t_cache, 1, cfg.kv_lora_rank), dtype),
        v=jnp.zeros((batch, t_cache, 1, cfg.qk_rope_head_dim), dtype),
        pos=jnp.full((batch, t_cache), -1, jnp.int32))


def mla_block(h: jax.Array, p: dict, cfg: ModelConfig,
              positions: Optional[jax.Array] = None,
              cache: Optional[L.KVCache] = None):
    """Latent attention of h [B, S, D] (normed) -> (out [B, S, D], cache')."""
    B, S, _ = h.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    q_nope, q_rope, c, k_rope = project(h, p, cfg, positions)
    if cache is None:
        o = attend(q_nope, q_rope, c, k_rope, p, cfg, positions, positions)
    else:
        cache = L.cache_insert(cache, c[:, :, None], k_rope[:, :, None], positions)
        o = attend(q_nope, q_rope, cache.k[:, :, 0], cache.v[:, :, 0], p, cfg,
                   positions, cache.pos)
    return o.reshape(B, S, -1) @ p["wo"], cache
