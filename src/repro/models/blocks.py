"""Decoder block variants (dense / moe / mlstm / slstm / hymba) with a uniform
interface so the model can ``lax.scan`` over stacked per-kind parameters.

Each kind defines:
  init_<kind>(cfg, key)            -> param pytree for ONE layer
  apply_<kind>(x, p, cfg, ...)     -> (x', aux_loss, new_cache)

Caches are kind-specific NamedTuple/array pytrees; ``init_cache_<kind>``
builds the per-layer cache for decoding.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import mla
from repro.models import moe as M
from repro.models import ssm as S


def _dt(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _norm_params(cfg: ModelConfig, d: int) -> dict:
    p = {"scale": jnp.ones((d,), _dt(cfg))}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), _dt(cfg))
    return p


def _dense_init(key, shape, dtype, scale=0.02):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# attention + MLP params (shared by dense/moe/hymba kinds)
# ---------------------------------------------------------------------------


def _init_attn(cfg: ModelConfig, key) -> dict:
    if cfg.is_mla:
        return _init_mla(cfg, key)
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    out_scale = 0.02 / max(1.0, (2 * cfg.num_layers) ** 0.5)
    return {
        "wq": _dense_init(k1, (D, H * hd), _dt(cfg)),
        "wk": _dense_init(k2, (D, KV * hd), _dt(cfg)),
        "wv": _dense_init(k3, (D, KV * hd), _dt(cfg)),
        "wo": _dense_init(k4, (H * hd, D), _dt(cfg), out_scale),
    }


def _init_mla(cfg: ModelConfig, key) -> dict:
    """Latent attention's weights (``models/mla.py``)."""
    D, H, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    out_scale = 0.02 / max(1.0, (2 * cfg.num_layers) ** 0.5)
    return {
        "wq": _dense_init(k1, (D, H * (dn + dr)), _dt(cfg)),
        "wkv_a": _dense_init(k2, (D, r + dr), _dt(cfg)),
        "kv_norm": {"scale": jnp.ones((r,), _dt(cfg))},
        "wkv_b": _dense_init(k3, (r, H * (dn + dv)), _dt(cfg)),
        "wo": _dense_init(k4, (H * dv, D), _dt(cfg), out_scale),
    }


def attention(x, p, cfg: ModelConfig, positions=None, cache=None):
    """The block's attention on normed ``x``: latent (MLA) or GQA."""
    if cfg.is_mla:
        return mla.mla_block(x, p, cfg, positions, cache)
    return L.attention_block(
        x, p,
        num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
        sliding_window=cfg.sliding_window, softcap=cfg.attn_softcap,
        positions=positions, cache=cache, impl=cfg.attention_impl,
        chunk_kv=cfg.attn_chunk_kv, attn_unroll=cfg.attn_scan_unroll,
        kv_block_axis=cfg.kv_block_axis, batch_axes=cfg.batch_axes,
    )


def _init_mlp(cfg: ModelConfig, key, width: int = 0) -> dict:
    D, F = cfg.d_model, width or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    out_scale = 0.02 / max(1.0, (2 * cfg.num_layers) ** 0.5)
    return {
        "wg": _dense_init(k1, (D, F), _dt(cfg)),
        "wu": _dense_init(k2, (D, F), _dt(cfg)),
        "wd": _dense_init(k3, (F, D), _dt(cfg), out_scale),
    }


# ---------------------------------------------------------------------------
# dense
# ---------------------------------------------------------------------------


def init_dense(cfg: ModelConfig, key) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": _norm_params(cfg, cfg.d_model),
        "attn": _init_attn(cfg, k1),
        "ln2": _norm_params(cfg, cfg.d_model),
        "mlp": _init_mlp(cfg, k2),
    }


def apply_dense(x, p, cfg: ModelConfig, positions=None, cache=None):
    h, new_cache = attention(L.norm(x, p["ln1"], cfg.norm), p["attn"], cfg,
                             positions, cache)
    x = x + h
    x = x + L.swiglu(L.norm(x, p["ln2"], cfg.norm), p["mlp"], cfg.act)
    return x, jnp.zeros((), jnp.float32), new_cache


# ---------------------------------------------------------------------------
# moe
# ---------------------------------------------------------------------------


def init_moe(cfg: ModelConfig, key) -> dict:
    """The router scores ``num_experts``; the layer holds ``held_experts``
    of them (and a shared expert where ``shared_expert_d_ff``)."""
    D, E, F = cfg.d_model, cfg.num_experts, cfg.expert_d_ff or cfg.d_ff
    G = cfg.held_experts
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    out_scale = 0.02 / max(1.0, (2 * cfg.num_layers) ** 0.5)
    moe = {
        "router": _dense_init(k2, (D, E), jnp.float32),
        "wg": _dense_init(k3, (G, D, F), _dt(cfg)),
        "wu": _dense_init(k4, (G, D, F), _dt(cfg)),
        "wd": _dense_init(k5, (G, F, D), _dt(cfg), out_scale),
    }
    if cfg.shared_expert_d_ff:
        moe["shared"] = _init_mlp(cfg, jax.random.fold_in(key, 6),
                                  cfg.shared_expert_d_ff)
    return {
        "ln1": _norm_params(cfg, D),
        "attn": _init_attn(cfg, k1),
        "ln2": _norm_params(cfg, D),
        "moe": moe,
    }


def expert_layer(x, p, cfg: ModelConfig):
    """The routed MLP on normed ``x`` -> (y, aux, claims on held experts):
    dropless over the held experts where ``moe_dropless``, else by capacity
    over all of them (claims not counted: None)."""
    if cfg.moe_dropless:
        return M.held_moe_block(
            x, p, num_experts=cfg.num_experts, top_k=cfg.num_experts_per_tok,
            expert_offset=cfg.expert_offset,
            norm_topk_prob=cfg.norm_topk_prob, act=cfg.act)
    y, aux = M.moe_block(
        x, p, num_experts=cfg.num_experts, top_k=cfg.num_experts_per_tok,
        capacity_factor=cfg.capacity_factor, act=cfg.act,
        groups=cfg.moe_groups,
    )
    return y, aux, None


def apply_moe(x, p, cfg: ModelConfig, positions=None, cache=None):
    h, new_cache = attention(L.norm(x, p["ln1"], cfg.norm), p["attn"], cfg,
                             positions, cache)
    x = x + h
    with jax.named_scope("moe_experts"):
        y, aux, _ = expert_layer(L.norm(x, p["ln2"], cfg.norm), p["moe"], cfg)
    return x + y, aux, new_cache


# ---------------------------------------------------------------------------
# mlstm / slstm (xLSTM)
# ---------------------------------------------------------------------------


def init_mlstm(cfg: ModelConfig, key) -> dict:
    D, H = cfg.d_model, cfg.ssm_heads or cfg.num_heads
    ks = jax.random.split(key, 7)
    out_scale = 0.02 / max(1.0, (2 * cfg.num_layers) ** 0.5)
    return {
        "ln1": _norm_params(cfg, D),
        "mlstm": {
            "wq": _dense_init(ks[0], (D, D), _dt(cfg)),
            "wk": _dense_init(ks[1], (D, D), _dt(cfg)),
            "wv": _dense_init(ks[2], (D, D), _dt(cfg)),
            "wi": _dense_init(ks[3], (D, H), _dt(cfg)),
            "wf": _dense_init(ks[4], (D, H), _dt(cfg)),
            "ogate": _dense_init(ks[5], (D, D), _dt(cfg)),
            "wo": _dense_init(ks[6], (D, D), _dt(cfg), out_scale),
        },
    }


def apply_mlstm(x, p, cfg: ModelConfig, positions=None, cache=None):
    H = cfg.ssm_heads or cfg.num_heads
    h, new_state = S.mlstm_block(L.norm(x, p["ln1"], cfg.norm), p["mlstm"],
                                 num_heads=H, state=cache,
                                 unroll=cfg.time_scan_unroll,
                                 shard_axis=cfg.ssm_shard_axis)
    return x + h, jnp.zeros((), jnp.float32), new_state


def init_slstm(cfg: ModelConfig, key) -> dict:
    D, H = cfg.d_model, cfg.ssm_heads or cfg.num_heads
    hd = D // H
    ks = jax.random.split(key, 9)
    out_scale = 0.02 / max(1.0, (2 * cfg.num_layers) ** 0.5)
    r = lambda k: _dense_init(k, (H, hd, hd), _dt(cfg))
    return {
        "ln1": _norm_params(cfg, D),
        "slstm": {
            "wz": _dense_init(ks[0], (D, D), _dt(cfg)),
            "wi": _dense_init(ks[1], (D, D), _dt(cfg)),
            "wf": _dense_init(ks[2], (D, D), _dt(cfg)),
            "wo": _dense_init(ks[3], (D, D), _dt(cfg)),
            "rz": r(ks[4]), "ri": r(ks[5]), "rf": r(ks[6]), "ro": r(ks[7]),
            "wout": _dense_init(ks[8], (D, D), _dt(cfg), out_scale),
        },
    }


def apply_slstm(x, p, cfg: ModelConfig, positions=None, cache=None):
    H = cfg.ssm_heads or cfg.num_heads
    h, new_state = S.slstm_block(L.norm(x, p["ln1"], cfg.norm), p["slstm"],
                                 num_heads=H, state=cache,
                                 unroll=cfg.time_scan_unroll)
    return x + h, jnp.zeros((), jnp.float32), new_state


# ---------------------------------------------------------------------------
# hymba (parallel attention + mamba heads, fused by mean of normed outputs)
# ---------------------------------------------------------------------------


def init_hymba(cfg: ModelConfig, key) -> dict:
    D = cfg.d_model
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    Di = H * P
    k1, k2, k3, k4 = jax.random.split(key, 4)
    out_scale = 0.02 / max(1.0, (2 * cfg.num_layers) ** 0.5)
    return {
        "ln1": _norm_params(cfg, D),
        "attn": _init_attn(cfg, k1),
        "mamba": {
            "win": _dense_init(k2, (D, 2 * Di + 2 * H * N + H), _dt(cfg)),
            "a_log": jnp.zeros((H,), jnp.float32),
            "d_skip": jnp.ones((H,), jnp.float32),
            "wout": _dense_init(k3, (Di, D), _dt(cfg), out_scale),
        },
        "norm_attn": _norm_params(cfg, D),
        "norm_ssm": _norm_params(cfg, D),
        "ln2": _norm_params(cfg, D),
        "mlp": _init_mlp(cfg, k4),
    }


def apply_hymba(x, p, cfg: ModelConfig, positions=None, cache=None):
    xin = L.norm(x, p["ln1"], cfg.norm)
    kv_cache = cache[0] if cache is not None else None
    ssm_state = cache[1] if cache is not None else None
    attn_out, new_kv = L.attention_block(
        xin, p["attn"],
        num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
        sliding_window=cfg.sliding_window, positions=positions,
        cache=kv_cache, impl=cfg.attention_impl,
        chunk_kv=cfg.attn_chunk_kv, attn_unroll=cfg.attn_scan_unroll,
        kv_block_axis=cfg.kv_block_axis, batch_axes=cfg.batch_axes,
    )
    ssm_out, new_state = S.mamba_block(
        xin, p["mamba"], num_heads=cfg.ssm_heads, ssm_state=cfg.ssm_state,
        chunk=cfg.ssd_chunk, state=ssm_state,
        decode=cache is not None and x.shape[1] == 1,
        unroll=cfg.time_scan_unroll,
    )
    fused = 0.5 * (L.norm(attn_out, p["norm_attn"], cfg.norm)
                   + L.norm(ssm_out, p["norm_ssm"], cfg.norm))
    x = x + fused
    x = x + L.swiglu(L.norm(x, p["ln2"], cfg.norm), p["mlp"], cfg.act)
    new_cache = (new_kv, new_state) if cache is not None else None
    return x, jnp.zeros((), jnp.float32), new_cache


# ---------------------------------------------------------------------------
# registry + cache builders
# ---------------------------------------------------------------------------

INIT = {"dense": init_dense, "moe": init_moe, "mlstm": init_mlstm,
        "slstm": init_slstm, "hymba": init_hymba}
APPLY = {"dense": apply_dense, "moe": apply_moe, "mlstm": apply_mlstm,
         "slstm": apply_slstm, "hymba": apply_hymba}


def cache_len(cfg: ModelConfig, seq_len: int) -> int:
    """Ring size: the CMP window — SWA archs keep only the window."""
    if cfg.sliding_window > 0:
        return min(seq_len, cfg.sliding_window)
    return seq_len


def init_cache_kind(kind: str, cfg: ModelConfig, batch: int, seq_len: int):
    dt = jnp.dtype(cfg.dtype)
    if kind in ("dense", "moe") and cfg.is_mla:
        return mla.make_cache(cfg, batch, cache_len(cfg, seq_len), dt)
    if kind in ("dense", "moe"):
        return L.make_kv_cache(batch, cache_len(cfg, seq_len), cfg.num_kv_heads,
                               cfg.resolved_head_dim, dt)
    if kind == "mlstm":
        H = cfg.ssm_heads or cfg.num_heads
        return S.mlstm_init_state(batch, H, cfg.d_model // H)
    if kind == "slstm":
        H = cfg.ssm_heads or cfg.num_heads
        return S.slstm_init_state(batch, H, cfg.d_model // H)
    if kind == "hymba":
        kv = L.make_kv_cache(batch, cache_len(cfg, seq_len), cfg.num_kv_heads,
                             cfg.resolved_head_dim, dt)
        st = S.mamba_init_state(batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)
        return (kv, st)
    raise ValueError(f"unknown block kind {kind!r}")
