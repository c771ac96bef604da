"""Model assembly: scan-over-stacked-layers causal LM supporting every
assigned architecture family (dense / moe / ssm / hybrid / vlm / audio).

Layers are stacked per block-pattern position and iterated with ``lax.scan``
(small HLO, fast multi-pod compiles, remat-friendly). Multimodal frontends are
stubs per the assignment: ``extra_embeds`` (precomputed patch/frame
embeddings) are prepended to the token embeddings.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import blocks as B
from repro.models import layers as L


def _shard_batch(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Re-anchor the batch sharding after the embedding gather (whose output
    sharding is ambiguous under 2-D sharded embeddings — see ModelConfig
    .batch_axes). A GSPMD constraint: the mesh must have ``Auto`` axes, as
    every mesh from :mod:`repro.launch.mesh` does. No-op when no
    mesh/batch_axes configured."""
    if cfg.batch_axes and x.shape[0] % 2 == 0:
        from jax.sharding import PartitionSpec as P
        spec = P(tuple(cfg.batch_axes), *([None] * (x.ndim - 1)))
        try:
            x = jax.lax.with_sharding_constraint(x, spec)
        except (ValueError, RuntimeError):
            pass  # no ambient mesh (single-device tests)
    return x


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=0)
def init_params(cfg: ModelConfig, key: jax.Array) -> Dict[str, Any]:
    """Random params for ``cfg`` from ``key``. Jitted, so each weight's
    float32 draw is fused into its cast: no whole float32 layer stack is
    ever materialised (at yi-6b widths one would be 5.4 GiB)."""
    dt = jnp.dtype(cfg.dtype)
    k_embed, k_blocks, k_head = jax.random.split(key, 3)
    params: Dict[str, Any] = {
        "embed": (jax.random.normal(k_embed, (cfg.vocab_size, cfg.d_model),
                                    jnp.float32) * 0.02).astype(dt),
        "final_norm": B._norm_params(cfg, cfg.d_model),
    }
    r = cfg.pattern_repeats
    blocks = {}
    keys = jax.random.split(k_blocks, r)
    for j, kind in enumerate(cfg.block_pattern):
        sub = jax.vmap(lambda k: B.INIT[kind](cfg, jax.random.fold_in(k, j)))(keys)
        blocks[str(j)] = sub
    params["blocks"] = blocks
    if cfg.first_dense_layers:
        # leading dense layers, before the pattern, stacked like a block
        lead = jax.random.split(jax.random.fold_in(key, 1), cfg.first_dense_layers)
        params["lead"] = jax.vmap(lambda k: B.init_dense(cfg, k))(lead)
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(
            k_head, (cfg.d_model, cfg.vocab_size), jnp.float32) * 0.02).astype(dt)
    return params


def param_count(params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))


def active_param_count(cfg: ModelConfig, params) -> int:
    """Active params per token (MoE: top_k of num_experts routed; of the
    held experts a token claims top_k / num_experts on average)."""
    total = param_count(params)
    if cfg.num_experts == 0:
        return total
    expert = 0
    for j, kind in enumerate(cfg.block_pattern):
        if kind == "moe":
            sub = params["blocks"][str(j)]["moe"]
            expert += sum(x.size for k, x in sub.items()
                          if k not in ("router", "shared"))
    active_frac = cfg.num_experts_per_tok / cfg.num_experts
    return int(total - expert + expert * active_frac)


# ---------------------------------------------------------------------------
# forward (train / prefill-style full sequence)
# ---------------------------------------------------------------------------


def lead_layer(params, i: int):
    """Parameters of leading dense layer ``i`` (``params["lead"]``)."""
    return jax.tree_util.tree_map(lambda a: a[i], params["lead"])


def _logits(x: jax.Array, params, cfg: ModelConfig) -> jax.Array:
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return (x @ head).astype(jnp.float32)


def apply(
    params,
    tokens: jax.Array,  # [B, S] int32
    cfg: ModelConfig,
    *,
    extra_embeds: Optional[jax.Array] = None,  # [B, n_extra, D] (vlm/audio stubs)
) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward. Returns (logits [B, S', V] float32, aux_loss)."""
    x = params["embed"][tokens]
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    x = _shard_batch(x, cfg)
    for i in range(cfg.first_dense_layers):
        x, _, _ = B.apply_dense(x, lead_layer(params, i), cfg)

    def super_fn(x, layer_p):
        aux = jnp.zeros((), jnp.float32)
        for j, kind in enumerate(cfg.block_pattern):
            x, a, _ = B.APPLY[kind](x, layer_p[str(j)], cfg)
            aux = aux + a
        return x, aux

    f = jax.checkpoint(super_fn) if cfg.remat else super_fn
    x, auxs = jax.lax.scan(lambda c, p: f(c, p), x, params["blocks"],
                           unroll=cfg.scan_unroll)
    x = L.norm(x, params["final_norm"], cfg.norm)
    return _logits(x, params, cfg), jnp.sum(auxs)


# ---------------------------------------------------------------------------
# decode path
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, seq_len: int) -> Dict[str, Any]:
    """Stacked per-pattern-position caches (and one per leading dense
    layer) + shared position counter."""
    r = cfg.pattern_repeats
    blocks = {}
    for j, kind in enumerate(cfg.block_pattern):
        one = B.init_cache_kind(kind, cfg, batch, seq_len)
        blocks[str(j)] = jax.tree_util.tree_map(
            lambda x: jnp.tile(x[None], (r,) + (1,) * x.ndim), one)
    cache = {"blocks": blocks, "pos": jnp.zeros((batch,), jnp.int32)}
    if cfg.first_dense_layers:
        cache["lead"] = [B.init_cache_kind("dense", cfg, batch, seq_len)
                         for _ in range(cfg.first_dense_layers)]
    return cache


def _run_with_cache(params, x, cfg: ModelConfig, cache, positions):
    lead = []
    for i in range(cfg.first_dense_layers):
        x, _, nc = B.apply_dense(x, lead_layer(params, i), cfg,
                                 positions=positions, cache=cache["lead"][i])
        lead.append(nc)

    def step(x, xs):
        layer_p, layer_c = xs
        new_c = {}
        for j, kind in enumerate(cfg.block_pattern):
            x, _, nc = B.APPLY[kind](x, layer_p[str(j)], cfg,
                                     positions=positions, cache=layer_c[str(j)])
            new_c[str(j)] = nc
        return x, new_c

    x, new_blocks = jax.lax.scan(step, x, (params["blocks"], cache["blocks"]),
                                 unroll=cfg.scan_unroll)
    x = L.norm(x, params["final_norm"], cfg.norm)
    return x, new_blocks, lead


def _new_cache(new_blocks, lead, pos):
    out = {"blocks": new_blocks, "pos": pos}
    if lead:
        out["lead"] = lead
    return out


def prefill(params, tokens: jax.Array, cfg: ModelConfig, cache,
            *, extra_embeds: Optional[jax.Array] = None):
    """Process a full prompt, filling the cache. Returns (last-token logits
    [B, V], cache')."""
    x = params["embed"][tokens]
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    x = _shard_batch(x, cfg)
    Bsz, S = x.shape[0], x.shape[1]
    positions = cache["pos"][:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    x, new_blocks, lead = _run_with_cache(params, x, cfg, cache, positions)
    logits = _logits(x[:, -1:], params, cfg)[:, 0]
    return logits, _new_cache(new_blocks, lead, cache["pos"] + S)


def decode_step(params, tokens: jax.Array, cfg: ModelConfig, cache):
    """One-token decode. tokens [B, 1] -> (logits [B, V], cache')."""
    x = _shard_batch(params["embed"][tokens], cfg)
    positions = cache["pos"][:, None]
    x, new_blocks, lead = _run_with_cache(params, x, cfg, cache, positions)
    logits = _logits(x, params, cfg)[:, 0]
    return logits, _new_cache(new_blocks, lead, cache["pos"] + 1)


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------


def loss_fn(params, batch: Dict[str, jax.Array], cfg: ModelConfig,
            aux_weight: float = 0.01):
    """Next-token cross-entropy. batch: {"tokens": [B, S]} (+"extra_embeds").
    Loss is computed on token positions only (frontend embeds are unlabelled)."""
    tokens = batch["tokens"]
    extra = batch.get("extra_embeds")
    logits, aux = apply(params, tokens[:, :-1], cfg, extra_embeds=extra)
    n_extra = 0 if extra is None else extra.shape[1]
    logits = logits[:, n_extra:]
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    loss = jnp.mean(nll)
    metrics = {"loss": loss, "aux_loss": aux, "ppl_proxy": jnp.exp(jnp.minimum(loss, 20.0))}
    return loss + aux_weight * aux, metrics
