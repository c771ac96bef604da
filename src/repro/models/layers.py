"""Model building blocks: norms, RoPE, GQA attention (full / sliding-window /
ring-buffer decode cache), SwiGLU MLP.

Attention dispatches through :mod:`repro.kernels.ops` so the same model code
runs the Pallas kernel on TPU (or in interpret mode in tests) and the pure-jnp
reference when lowering the dry-run.

KV caches carry an explicit per-slot position array, so a *ring buffer* cache
(sliding-window attention) and a linear cache are the same code path. The ring
is the CMP protection window made literal: a slot whose position falls out of
the window is reclaimed by the next insert, coordination-free (DESIGN.md §4).
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def norm(x: jax.Array, p: dict, kind: str) -> jax.Array:
    if kind == "layernorm":
        return layer_norm(x, p["scale"], p["bias"])
    return rms_norm(x, p["scale"])


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor 0.1 * mscale * ln(factor) + 1 (1 unscaled)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_freqs(dim: int, theta: float, factor: float, original_max_pos: int,
               beta_fast: float, beta_slow: float) -> jax.Array:
    """YaRN frequencies (arXiv:2309.00071, "NTK-by-parts"): pair j keeps
    theta^(-2j/dim) below the correction range, is divided by ``factor``
    above it, and ramps linearly between. The range is where a pair turns
    ``beta_fast`` to ``beta_slow`` times over ``original_max_pos``."""
    def pair(rotations):
        return (dim * math.log(original_max_pos / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(pair(beta_fast)), 0)
    high = min(math.ceil(pair(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    extra = rope_freqs(dim, theta)
    return extra / factor * ramp + extra * (1.0 - ramp)


def rope_tables(cfg, dim: int) -> Tuple[jax.Array, float]:
    """(frequencies, cos/sin scale) of a ``dim``-wide rotary head under
    ``cfg``'s RoPE: plain, or YaRN where ``cfg.rope_factor > 1``."""
    if cfg.rope_factor <= 1:
        return rope_freqs(dim, cfg.rope_theta), 1.0
    freqs = yarn_freqs(dim, cfg.rope_theta, cfg.rope_factor,
                       cfg.rope_original_max_pos, cfg.yarn_beta_fast,
                       cfg.yarn_beta_slow)
    scale = (yarn_mscale(cfg.rope_factor, cfg.yarn_mscale)
             / yarn_mscale(cfg.rope_factor, cfg.yarn_mscale_all_dim))
    return freqs, scale


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               freqs: Optional[jax.Array] = None,
               scale: float = 1.0) -> jax.Array:
    """x: [B, S, num_heads, head_dim]; positions: [B, S]. ``freqs`` and
    ``scale`` (of cos and sin) from :func:`rope_tables` replace the plain
    ``theta`` frequencies."""
    hd = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(hd, theta)  # [hd/2]
    angles = positions[..., :, None, None].astype(jnp.float32) * freqs
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    if scale != 1.0:
        sin, cos = sin * scale, cos * scale
    x1, x2 = x[..., : hd // 2], x[..., hd // 2 :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def _sdpa(q, k, v, mask, softcap: float = 0.0):
    """q:[B,S,H,hd] k,v:[B,T,KV,hd] mask broadcastable to [B,rep,KV,S,T].

    GQA grouping is r-major (query head h uses KV head h % KV): the reshape
    H -> (rep, KV) then keeps a model-axis sharding of H expressible as a
    sharding of `rep`, so GSPMD shards attention over TP instead of
    replicating it (a 16x compute difference at KV=2, TP=16)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    rep = H // KV
    qh = q.reshape(B, S, rep, KV, hd)
    logits = jnp.einsum("bsrgd,btgd->brgst", qh, k).astype(jnp.float32)
    logits = logits / jnp.sqrt(jnp.float32(hd))
    if softcap > 0.0:
        logits = jnp.tanh(logits / softcap) * softcap
    logits = jnp.where(mask, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("brgst,btgd->bsrgd", w.astype(v.dtype), v)
    return out.reshape(B, S, H, hd)


def self_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    sliding_window: int = 0, softcap: float = 0.0, impl: str = "ref",
) -> jax.Array:
    """Causal self-attention over equal-length q/k/v (train & prefill)."""
    if impl == "pallas" and softcap == 0.0:
        from repro.kernels import ops as kops
        return kops.flash_attention(q, k, v, causal=True, sliding_window=sliding_window)
    S, T = q.shape[1], k.shape[1]
    q_pos = jnp.arange(S)[:, None]
    k_pos = jnp.arange(T)[None, :]
    mask = q_pos >= k_pos
    if sliding_window > 0:
        mask = mask & (q_pos - k_pos < sliding_window)
    return _sdpa(q, k, v, mask[None, None, None], softcap=softcap)


def cache_attention(
    q: jax.Array,            # [B, S, H, hd] (S=1 decode, or prefill chunk)
    k: jax.Array, v: jax.Array,  # [B, T, KV, hd] cache contents
    q_pos: jax.Array,        # [B, S] absolute positions of queries
    k_pos: jax.Array,        # [B, T] absolute positions of cache slots (-1 invalid)
    *, sliding_window: int = 0, softcap: float = 0.0,
) -> jax.Array:
    mask = (k_pos[:, None, :] >= 0) & (q_pos[:, :, None] >= k_pos[:, None, :])
    if sliding_window > 0:
        mask = mask & (q_pos[:, :, None] - k_pos[:, None, :] < sliding_window)
    return _sdpa(q, k, v, mask[:, None, None], softcap=softcap)


def chunked_cache_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    q_pos: jax.Array, k_pos: jax.Array,
    *, sliding_window: int = 0, softcap: float = 0.0,
    block_k: int = 1024, unroll: int = 1, kv_block_axis=None,
    batch_axes=None,
) -> jax.Array:
    """Online-softmax attention over the cache in KV blocks — O(S*block_k)
    working set instead of O(S*T). Forward-only (used for prefill/decode, the
    pure-JAX equivalent of the Pallas flash kernel; grads go through the ref
    path under remat)."""
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    rep = H // KV
    pad = (-T) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
    nb = (T + pad) // block_k
    kb = jnp.moveaxis(k.reshape(B, nb, block_k, KV, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nb, block_k, KV, hd), 1, 0)
    pb = jnp.moveaxis(k_pos.reshape(B, nb, block_k), 1, 0)
    seq_parallel = False
    if kv_block_axis is not None:
        # Sequence-parallel attention: queries (and the running softmax
        # state) shard over ``kv_block_axis``; each scanned KV block is
        # broadcast (small) instead of scanning across a sharded time dim,
        # which would force either an involuntary full rematerialization of
        # the cache or a full-accumulator psum every step (both measured —
        # EXPERIMENTS.md §Perf cell A).
        from jax.sharding import PartitionSpec as P
        ba = tuple(batch_axes) if batch_axes else None
        try:
            kb = jax.lax.with_sharding_constraint(kb, P(None, ba, None, None, None))
            vb = jax.lax.with_sharding_constraint(vb, P(None, ba, None, None, None))
            pb = jax.lax.with_sharding_constraint(pb, P(None, ba, None))
            seq_parallel = True
        except (ValueError, RuntimeError):
            pass  # no ambient mesh
    qh = q.reshape(B, S, rep, KV, hd)  # r-major GQA (see _sdpa)
    if seq_parallel:
        from jax.sharding import PartitionSpec as P
        ba = tuple(batch_axes) if batch_axes else None
        qh = jax.lax.with_sharding_constraint(
            qh, P(ba, kv_block_axis, None, None, None))
        q_pos = jax.lax.with_sharding_constraint(q_pos, P(ba, kv_block_axis))
    scale = 1.0 / (hd ** 0.5)

    def body(carry, xs):
        acc, m, l = carry
        kc, vc, kp = xs  # [B, bk, KV, hd], [B, bk]
        s = jnp.einsum("bsrgd,btgd->bsrgt", qh, kc).astype(jnp.float32) * scale
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        mask = (kp[:, None, :] >= 0) & (q_pos[:, :, None] >= kp[:, None, :])
        if sliding_window > 0:
            mask = mask & (q_pos[:, :, None] - kp[:, None, :] < sliding_window)
        mask = mask[:, :, None, None, :]
        s = jnp.where(mask, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bsrgt,btgd->bsrgd", p.astype(vc.dtype), vc).astype(jnp.float32)
        return (acc, m_new, l), None

    acc0 = jnp.zeros((B, S, rep, KV, hd), jnp.float32)
    m0 = jnp.full((B, S, rep, KV), -1e30, jnp.float32)
    l0 = jnp.zeros((B, S, rep, KV), jnp.float32)
    if seq_parallel:
        from jax.sharding import PartitionSpec as P
        ba = tuple(batch_axes) if batch_axes else None
        acc0 = jax.lax.with_sharding_constraint(
            acc0, P(ba, kv_block_axis, None, None, None))
        m0 = jax.lax.with_sharding_constraint(m0, P(ba, kv_block_axis, None, None))
        l0 = jax.lax.with_sharding_constraint(l0, P(ba, kv_block_axis, None, None))
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), (kb, vb, pb),
                                  unroll=unroll)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, S, H, hd).astype(q.dtype)


def kv_chunks(seq: int, t_cache: int, block_k: int) -> int:
    """Number of chunked-attention scan steps (0 = direct path). Must mirror
    the dispatch condition in attention_block exactly (dry-run extrapolation
    depends on it)."""
    if block_k <= 0 or seq <= 1 or t_cache <= block_k:
        return 0
    return -(-t_cache // block_k)


class KVCache(NamedTuple):
    k: jax.Array    # [B, T, KV, hd]
    v: jax.Array    # [B, T, KV, hd]
    pos: jax.Array  # [B, T] int32, -1 = empty slot


def make_kv_cache(batch: int, t_cache: int, num_kv: int, head_dim: int, dtype) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, t_cache, num_kv, head_dim), dtype),
        v=jnp.zeros((batch, t_cache, num_kv, head_dim), dtype),
        pos=jnp.full((batch, t_cache), -1, jnp.int32),
    )


def cache_insert(cache: KVCache, k_new: jax.Array, v_new: jax.Array,
                 positions: jax.Array) -> KVCache:
    """Insert S new entries at ring slots ``positions % T``. For a full-
    attention cache T >= max position so the ring never wraps."""
    B, S = positions.shape
    T = cache.k.shape[1]
    if S >= T:  # only the last T entries survive (static shapes)
        k_new, v_new, positions = k_new[:, -T:], v_new[:, -T:], positions[:, -T:]
        S = T
    slots = positions % T  # [B, S]
    b_idx = jnp.arange(B)[:, None]
    return KVCache(
        k=cache.k.at[b_idx, slots].set(k_new.astype(cache.k.dtype)),
        v=cache.v.at[b_idx, slots].set(v_new.astype(cache.v.dtype)),
        pos=cache.pos.at[b_idx, slots].set(positions),
    )


def attention_block(
    x: jax.Array,  # [B, S, D]
    p: dict,       # wq [D, H*hd], wk/wv [D, KV*hd], wo [H*hd, D]
    *,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    rope_theta: float,
    sliding_window: int = 0,
    softcap: float = 0.0,
    positions: Optional[jax.Array] = None,  # [B, S] absolute positions
    cache: Optional[KVCache] = None,
    impl: str = "ref",
    chunk_kv: int = 0,
    attn_unroll: int = 1,
    kv_block_axis=None,
    batch_axes=None,
) -> Tuple[jax.Array, Optional[KVCache]]:
    """Returns (out [B,S,D], new_cache|None). With a cache, RoPE is applied at
    insert time (keys rotated by absolute position) and attention runs against
    the full ring."""
    B, S, D = x.shape
    q = (x @ p["wq"]).reshape(B, S, num_heads, head_dim)
    kx = (x @ p["wk"]).reshape(B, S, num_kv_heads, head_dim)
    vx = (x @ p["wv"]).reshape(B, S, num_kv_heads, head_dim)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    q = apply_rope(q, positions, rope_theta)
    kx = apply_rope(kx, positions, rope_theta)

    if cache is None:
        out = self_attention(q, kx, vx, sliding_window=sliding_window,
                             softcap=softcap, impl=impl)
        new_cache = None
    else:
        new_cache = cache_insert(cache, kx, vx, positions)
        t_cache = new_cache.k.shape[1]
        if kv_chunks(S, t_cache, chunk_kv) > 0:
            out = chunked_cache_attention(
                q, new_cache.k, new_cache.v, positions, new_cache.pos,
                sliding_window=sliding_window, softcap=softcap,
                block_k=chunk_kv, unroll=attn_unroll,
                kv_block_axis=kv_block_axis, batch_axes=batch_axes)
        else:
            out = cache_attention(q, new_cache.k, new_cache.v, positions,
                                  new_cache.pos, sliding_window=sliding_window,
                                  softcap=softcap)
    out = out.reshape(B, S, num_heads * head_dim) @ p["wo"]
    return out, new_cache


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def swiglu(x: jax.Array, p: dict, act: str = "silu") -> jax.Array:
    """Gated MLP: wg/wu [D, F], wd [F, D]."""
    g = x @ p["wg"]
    u = x @ p["wu"]
    a = jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g)
    return (a * u) @ p["wd"]
