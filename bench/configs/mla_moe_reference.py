"""Plain float32 reference of the latent-attention mixture-of-experts
configurations in this directory (DeepSeek-V2 keys, layout ``mla_moe``,
``bench/layouts/mla_moe.py``), one rank of expert parallelism.

Straight ``jax.numpy`` over the whole sequence: no cache, no pages, no
batching, no absorption, every product at ``Precision.HIGHEST`` in float32
on the weights of ``bench/weights.py``. It imports nothing of the serving
program. Per layer, on a = RMSNorm(x):

- attention (arXiv:2405.04434 §2.1, no q_lora): q = a W_q split per head
  into q_nope and q_rope; [c | k_rope] = a W_kv_a; c = RMSNorm(c)
  (kv_a_layernorm); q_rope and k_rope (one head, shared) rotated by YaRN
  RoPE; [k_nope | v] = c W_kv_b per head; scores (q_nope·k_nope +
  q_rope·k_rope) times ``qk_head_dim^-0.5 * m^2``, m = 0.1 * mscale_all_dim
  * ln(factor) + 1; causal softmax; x += (weights @ v) W_o;
- then on m = RMSNorm(x): the first ``first_k_dense_replace`` layers a
  gated MLP; the others a router softmax(m @ router) over the published
  expert count, the top ``num_experts_per_tok`` kept with their raw
  probabilities as gates, the held experts (``n_routed_experts`` from
  ``expert_parallel_rank * n_routed_experts`` on) each weighted by its gate
  where kept and 0 elsewhere, plus the shared experts as one gated MLP.
  Every held expert runs over every position; nothing is dropped.

YaRN (arXiv:2309.00071, as DeepSeek-V2 applies it): pair j of the rope
dims has frequency theta^(-2j/d) below the correction range and that over
``factor`` above it, with a linear ramp between; the range runs from
floor(d ln(L / (2 pi beta_fast)) / (2 ln theta)) to ceil(d ln(L / (2 pi
beta_slow)) / (2 ln theta)), L = original_max_position_embeddings; cos and
sin are scaled by mscale(factor, mscale) / mscale(factor, mscale_all_dim).
The parameters are read from the file's top-level ``yarn_*`` keys (a
reference is handed the top-level numbers only); a file without them has
plain RoPE.

Departures from the published description: RoPE rotates the two halves of
the rope columns (rotate_half) where the published code first de-interleaves
them, a fixed permutation of the rope columns of W_q and W_kv_a under random
weights; the absent experts of the other ranks add nothing (the cut, stated
in the configuration).

``quant`` makes the control, as in ``dense_reference.py``: every matrix
product's operands rounded to int8 or fp8 first, the router's included.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.configs.dense_reference import F32, HI, _mm, _rms, _round


def _yarn(d: int, theta: float, rs: tuple):
    """(frequencies [d/2], cos/sin scale) of a ``d``-wide rotary head, as
    numpy constants (this runs inside the caller's trace)."""
    freqs = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    if not rs:
        return freqs, 1.0
    factor, orig, fast, slow, mscale, mscale_all = rs

    def pair(rotations):
        return d * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low, high = max(math.floor(pair(fast)), 0), min(math.ceil(pair(slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    freqs = freqs / factor * ramp + freqs * (1 - ramp)

    def m(x):
        return 0.1 * x * math.log(factor) + 1.0 if factor > 1 else 1.0
    return freqs, m(mscale) / m(mscale_all)


def _rope(x, freqs, scale):
    """x [S, heads, d], rotate_half by position."""
    d = x.shape[-1]
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None, None] * freqs.astype(np.float32)
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _gated(m, p, quant):
    g = jax.nn.silu(_mm(m, p["wg"], quant)) * _mm(m, p["wu"], quant)
    return _mm(g, p["wd"], quant)


def _held_experts(m, p, k, offset, quant):
    """This rank's part of the routed MLP of m [S, d], plus the shared expert."""
    probs = jax.nn.softmax(_mm(m, p["router"], quant), -1)       # [S, E]
    top, ids = jax.lax.top_k(probs, k)
    gates = jnp.zeros_like(probs).at[jnp.arange(m.shape[0])[:, None], ids].set(top)
    held = p["wg"].shape[0]
    gates = gates[:, offset:offset + held]                        # [S, G]
    mq = _round(m, -1, quant)

    def expert_mm(x, w):  # x [G, S, i], w [G, i, o]
        return jnp.einsum("esi,eio->eso", x, _round(w.astype(F32), 1, quant), precision=HI)

    x = jnp.broadcast_to(mq, (held,) + m.shape)
    g = jax.nn.silu(expert_mm(x, p["wg"])) * expert_mm(x, p["wu"])
    out = expert_mm(_round(g, -1, quant), p["wd"])               # [G, S, d]
    return jnp.einsum("se,esd->sd", gates, out, precision=HI) + _gated(m, p["shared"], quant)


@functools.lru_cache(maxsize=None)
def _compiled(dims: tuple, quant):
    h, dn, dr, dv, r, k, offset, lead, theta, eps, rs = dims
    freqs, rscale = _yarn(dr, theta, rs)
    if rs:
        m = 0.1 * rs[5] * math.log(rs[0]) + 1.0
        sm_scale = (dn + dr) ** -0.5 * m * m
    else:
        sm_scale = (dn + dr) ** -0.5

    def attention(x, p):
        a = _rms(x, p["ln1"]["scale"], eps)
        q = _mm(a, p["attn"]["wq"], quant).reshape(-1, h, dn + dr)
        kv = _mm(a, p["attn"]["wkv_a"], quant)
        c = _rms(kv[:, :r], p["attn"]["kv_norm"]["scale"], eps)
        q_rope = _rope(q[..., dn:], freqs, rscale)
        k_rope = _rope(kv[:, None, r:], freqs, rscale)            # [S, 1, dr]
        kvb = _mm(c, p["attn"]["wkv_b"], quant).reshape(-1, h, dn + dv)
        scores = (jnp.einsum("qhd,khd->hqk", q[..., :dn], kvb[..., :dn], precision=HI)
                  + jnp.einsum("qhd,kd->hqk", q_rope, k_rope[:, 0], precision=HI)) * sm_scale
        s = x.shape[0]
        keep = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
        w = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), -1)
        o = jnp.einsum("hqk,khd->qhd", w, kvb[..., dn:], precision=HI)
        return x + _mm(o.reshape(s, h * dv), p["attn"]["wo"], quant)

    def dense_layer(x, p):
        x = attention(x, p)
        return x + _gated(_rms(x, p["ln2"]["scale"], eps), p["mlp"], quant)

    def expert_layer(x, p):
        x = attention(x, p)
        return x + _held_experts(_rms(x, p["ln2"]["scale"], eps), p["moe"], k, offset,
                                 quant), None

    @jax.jit
    def forward(w, tokens):
        x = w["embed"][tokens].astype(F32)
        for i in range(lead):
            x = dense_layer(x, jax.tree_util.tree_map(lambda a: a[i], w["lead"]))
        x, _ = jax.lax.scan(expert_layer, x, w["blocks"]["0"])
        x = _rms(x, w["final_norm"]["scale"], eps)
        return _mm(x, w["lm_head"], quant)

    return forward


def logits(cfg: dict, weights, tokens, quant=None):
    """Float32 logits [S, vocab] at every position of ``tokens`` [S]."""
    yarn = (float(cfg["yarn_factor"]), int(cfg["yarn_original_max_position_embeddings"]),
            float(cfg["yarn_beta_fast"]), float(cfg["yarn_beta_slow"]),
            float(cfg["yarn_mscale"]), float(cfg["yarn_mscale_all_dim"])
            ) if "yarn_factor" in cfg else ()
    dims = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["kv_lora_rank"], int(cfg["num_experts_per_tok"]),
            cfg["expert_parallel_rank"] * cfg["n_routed_experts"],
            int(cfg["first_k_dense_replace"]), float(cfg["rope_theta"]),
            float(cfg["rms_norm_eps"]), yarn)
    return _compiled(dims, quant)(weights, jnp.asarray(tokens, jnp.int32))
