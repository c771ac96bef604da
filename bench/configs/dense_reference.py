"""Plain float32 reference of the dense decoder configurations in this
directory (Llama family: Yi-6B; Phi-3-mini, whose fused qkv and gate/up
projections are the same products kept as separate matrices).

Straight ``jax.numpy`` over the whole sequence: no cache, no pages, no
batching, every product at ``Precision.HIGHEST`` in float32 on the bf16
weights of ``bench/weights.py``. It imports nothing of the serving program.
Per layer: x += o(attn(rope(q), rope(k), v)) on RMSNorm(x), then
x += down(silu(gate) * up) on RMSNorm(x); RoPE rotates the two halves of
each head (rotate_half); query head h reads key/value head h % num_kv_heads
(the served layout, see ``weights.py``); a ``sliding_window`` in the
configuration masks keys that many positions back or more.

``quant`` makes the control: every matrix product's operands rounded to a
lower precision first (``"int8"``: symmetric, per row of activations and
per output column of weights; ``"fp8"``: float8_e4m3fn with the same
scales), the rest still float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _round(x, axis, quant):
    if quant is None:
        return x
    top = {"int8": 127.0, "fp8": 448.0}[quant]
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    s = jnp.where(s == 0, 1.0, s)
    if quant == "int8":
        q = jnp.clip(jnp.round(x / s), -127, 127)
    else:
        q = (x / s).astype(jnp.float8_e4m3fn).astype(F32)
    return q * s


def _mm(x, w, quant):
    x = _round(x, -1, quant)
    w = _round(w.astype(F32), 0, quant)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale.astype(F32)


def _rope(x, theta):
    s, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(s, dtype=F32)[:, None, None] * freqs
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _attention(q, k, v, window):
    s, h, hd = q.shape
    kv_of = jnp.arange(h) % k.shape[1]
    k, v = k[:, kv_of], v[:, kv_of]
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / jnp.sqrt(F32(hd))
    qp, kp = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    keep = kp <= qp
    if window:
        keep = keep & (qp - kp < window)
    scores = jnp.where(keep[None], scores, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v, precision=HI)


@functools.lru_cache(maxsize=None)
def _compiled(dims: tuple, quant):
    h, kv, hd, theta, eps, window = dims

    def layer(x, p):
        a = _rms(x, p["ln1"]["scale"], eps)
        q = _mm(a, p["attn"]["wq"], quant).reshape(-1, h, hd)
        k = _mm(a, p["attn"]["wk"], quant).reshape(-1, kv, hd)
        v = _mm(a, p["attn"]["wv"], quant).reshape(-1, kv, hd)
        o = _attention(_rope(q, theta), _rope(k, theta), v, window)
        x = x + _mm(o.reshape(-1, h * hd), p["attn"]["wo"], quant)
        m = _rms(x, p["ln2"]["scale"], eps)
        g = jax.nn.silu(_mm(m, p["mlp"]["wg"], quant)) * _mm(m, p["mlp"]["wu"], quant)
        return x + _mm(g, p["mlp"]["wd"], quant), None

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
            float(cfg["rope_theta"]), float(cfg["rms_norm_eps"]),
            int(cfg.get("sliding_window") or 0))
    return _compiled(dims, quant)(weights, jnp.asarray(tokens, jnp.int32))
