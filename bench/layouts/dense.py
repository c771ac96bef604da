"""Dense decoder layers (Llama family: Yi-6B, Phi-3-mini): GQA attention
with q, k, v and o projections, then a gated MLP, in every layer.

The served layout: ``params["blocks"]["0"]`` stacked over layers, query
head h reading key/value head h % num_kv_heads. The counts are what a
serving step needs, from the published shapes and the live lengths of the
step (``bench/counts.py``).

``cfg`` is a configuration file of ``bench/configs`` (Hugging Face keys).
"""

from __future__ import annotations

from typing import Dict, Sequence

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def dims(cfg: dict) -> Dict[str, int]:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "f": cfg["intermediate_size"], "h": h,
            "kv": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or d // h,
            "layers": cfg["num_hidden_layers"], "vocab": cfg["vocab_size"],
            "bytes": _DTYPE_BYTES[cfg["torch_dtype"]]}


def shapes(cfg: dict) -> dict:
    m = dims(cfg)
    d, f, h, kv, hd, n, v = (m["d"], m["f"], m["h"], m["kv"], m["hd"],
                             m["layers"], m["vocab"])
    return {
        "embed": (v, d),
        "final_norm": {"scale": (d,)},
        "lm_head": (d, v),
        "blocks": {"0": {
            "ln1": {"scale": (n, d)},
            "attn": {"wq": (n, d, h * hd), "wk": (n, d, kv * hd),
                     "wv": (n, d, kv * hd), "wo": (n, h * hd, d)},
            "ln2": {"scale": (n, d)},
            "mlp": {"wg": (n, d, f), "wu": (n, d, f), "wd": (n, f, d)},
        }},
    }


def program_fields(cfg: dict) -> dict:
    return {"d_model": cfg["hidden_size"], "d_ff": cfg["intermediate_size"],
            "num_heads": cfg["num_attention_heads"],
            "num_kv_heads": cfg["num_key_value_heads"],
            "num_layers": cfg["num_hidden_layers"], "vocab_size": cfg["vocab_size"],
            "resolved_head_dim": cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"],
            "rope_theta": cfg["rope_theta"], "dtype": cfg["torch_dtype"],
            "tie_embeddings": cfg["tie_word_embeddings"], "block_pattern": ("dense",),
            "norm": "rmsnorm", "act": cfg["hidden_act"]}


def layer_matmul_params(cfg: dict) -> int:
    """Weights one token multiplies through in one layer: q, k, v, o and
    the gated MLP (norm scales are not matmul weights)."""
    m = dims(cfg)
    attn = m["d"] * m["hd"] * (2 * m["h"] + 2 * m["kv"])
    return attn + 3 * m["d"] * m["f"]


def matmul_params(cfg: dict) -> int:
    """N of the 2*N rule: every layer plus the output head (the embedding
    is a gather, not a product)."""
    m = dims(cfg)
    return m["layers"] * layer_matmul_params(cfg) + m["d"] * m["vocab"]


def weight_bytes(cfg: dict) -> int:
    """Bytes of weights one step reads: every layer's matmul weights and
    norm scales, the final norm and the output head."""
    m = dims(cfg)
    norms = (2 * m["layers"] + 1) * m["d"]
    return (matmul_params(cfg) + norms) * m["bytes"]


def kv_bytes_per_token(cfg: dict) -> int:
    """Keys and values one token keeps in the cache, over all layers."""
    m = dims(cfg)
    return 2 * m["layers"] * m["kv"] * m["hd"] * m["bytes"]


def attention_flops(cfg: dict, query_pos: int) -> int:
    """Scores and weighted sum for one query at 0-based position
    ``query_pos``, which attends to ``query_pos + 1`` keys in every layer."""
    m = dims(cfg)
    return 4 * m["layers"] * m["h"] * m["hd"] * (query_pos + 1)


def decode_flops(cfg: dict, context: Sequence[int]) -> int:
    """One decode step; ``context[i]`` is lane i's length before the step
    (its new token sits at position ``context[i]``). Inactive lanes are
    not listed."""
    return sum(2 * matmul_params(cfg) + attention_flops(cfg, c)
               for c in context)


def decode_bytes(cfg: dict, context: Sequence[int]) -> int:
    """One decode step: the weights once, each lane's live keys and values
    read, and its new ones written."""
    kv = kv_bytes_per_token(cfg)
    return weight_bytes(cfg) + sum(c * kv + kv for c in context)


def prefill_flops(cfg: dict, prompt_len: int) -> int:
    """A whole prompt: 2*N per token plus causal attention,
    sum over positions p < P of attention to p + 1 keys."""
    m = dims(cfg)
    p = prompt_len
    attn = 2 * m["layers"] * m["h"] * m["hd"] * p * (p + 1)
    return 2 * matmul_params(cfg) * p + attn


def prefill_bytes(cfg: dict, prompt_len: int) -> int:
    """The weights once and the prompt's keys and values written."""
    return weight_bytes(cfg) + prompt_len * kv_bytes_per_token(cfg)
