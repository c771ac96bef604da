"""Plain float32 reference of the mixture-of-experts configurations in this
directory (layout ``moe``, ``bench/layouts/moe.py``).

Attention, norms, RoPE and the control's rounding are those of
``dense_reference.py``; the MLP is routed. Per layer: x += o(attn(...)) on
RMSNorm(x) as there, then on m = RMSNorm(x): router probabilities
softmax(m @ router) over all experts, the top ``num_experts_per_tok``
kept and renormalised to sum to 1, x += sum over the kept experts of
gate * down(silu(gate_proj) * up). No token is dropped: every expert runs
over every position and the unkept ones are weighted 0. Every product is at
``Precision.HIGHEST`` in float32 on the weights of ``bench/weights.py``;
it imports nothing of the serving program.

``quant`` makes the control, as in ``dense_reference.py``: every matrix
product's operands rounded to int8 or fp8 first, the router's included.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.configs.dense_reference import F32, HI, _attention, _mm, _rms, _round, _rope


def _experts(m, p, k, quant):
    """Routed MLP of m [S, d] through p = {router, wg, wu, wd}."""
    probs = jax.nn.softmax(_mm(m, p["router"], quant), -1)  # [S, E]
    top, ids = jax.lax.top_k(probs, k)
    top = top / jnp.sum(top, -1, keepdims=True)
    gates = jnp.zeros_like(probs).at[jnp.arange(m.shape[0])[:, None], ids].set(top)
    mq = _round(m, -1, quant)

    def expert_mm(x, w):  # x [E, S, i], w [E, i, o]
        return jnp.einsum("esi,eio->eso", x, _round(w.astype(F32), 1, quant), precision=HI)

    x = jnp.broadcast_to(mq, (p["wg"].shape[0],) + m.shape)
    g = jax.nn.silu(expert_mm(x, p["wg"])) * expert_mm(x, p["wu"])
    out = expert_mm(_round(g, -1, quant), p["wd"])  # [E, S, d]
    return jnp.einsum("se,esd->sd", gates, out, precision=HI)


@functools.lru_cache(maxsize=None)
def _compiled(dims: tuple, quant):
    h, kv, hd, k, theta, eps, window = dims

    def layer(x, p):
        a = _rms(x, p["ln1"]["scale"], eps)
        q = _mm(a, p["attn"]["wq"], quant).reshape(-1, h, hd)
        kk = _mm(a, p["attn"]["wk"], quant).reshape(-1, kv, hd)
        v = _mm(a, p["attn"]["wv"], quant).reshape(-1, kv, hd)
        o = _attention(_rope(q, theta), _rope(kk, theta), v, window)
        x = x + _mm(o.reshape(-1, h * hd), p["attn"]["wo"], quant)
        return x + _experts(_rms(x, p["ln2"]["scale"], eps), p["moe"], k, quant), None

    @jax.jit
    def forward(w, tokens):
        x = w["embed"][tokens].astype(F32)
        x, _ = jax.lax.scan(layer, x, w["blocks"]["0"])
        x = _rms(x, w["final_norm"]["scale"], eps)
        return _mm(x, w["lm_head"], quant)

    return forward


def logits(cfg: dict, weights, tokens, quant=None):
    """Float32 logits [S, vocab] at every position of ``tokens`` [S]."""
    h = cfg["num_attention_heads"]
    dims = (h, cfg["num_key_value_heads"],
            cfg.get("head_dim") or cfg["hidden_size"] // h,
            int(cfg["num_experts_per_tok"]), float(cfg["rope_theta"]),
            float(cfg["rms_norm_eps"]), int(cfg.get("sliding_window") or 0))
    return _compiled(dims, quant)(weights, jnp.asarray(tokens, jnp.int32))
