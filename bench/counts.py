"""Operations and bytes a serving step needs, from a configuration's shapes.

Counted from the published shapes and the live lengths of the step, never
from ``compiled.cost_analysis()`` (which counts a 32-layer ``lax.scan``
body once). What the step *needs* is counted, not what the program moves:
the decode step reads each lane's live keys and values, not the whole
page table it gathers, so waste shows as a lower roofline share.

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


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """Roofline bound: the larger of compute time and memory time."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
