"""Latent attention with DeepSeekMoE expert layers (DeepSeek-V2 keys), one
rank of expert parallelism.

Every layer has multi-head latent attention (arXiv:2405.04434 §2.1): a
token keeps ``kv_lora_rank + qk_rope_head_dim`` values per layer, the
normed latent and one rotary key shared by all heads, with YaRN RoPE where
the file gives ``rope_scaling``. The first ``first_k_dense_replace``
layers have a gated MLP of ``intermediate_size``; the others are expert
layers: a float32 router over the published expert count,
softmax over all of them, greedy top ``num_experts_per_tok`` with raw
gates (``norm_topk_prob`` false, ``routed_scaling_factor`` 1), experts of
``moe_intermediate_size``, and ``n_shared_experts`` shared experts run by
every token as one gated MLP of ``n_shared_experts * moe_intermediate_size``.

This chip is rank ``expert_parallel_rank`` of ``expert_parallel_size``: it
holds ``n_routed_experts`` of the ``published_n_routed_experts`` (listed
under ``reduced``), from ``rank * n_routed_experts`` on, and a claim on an
expert held elsewhere adds nothing here. The ``yarn_*`` keys repeat
``rope_scaling`` at the top level, where the reference reads them.

The served layout is the program's: ``params["lead"]`` (the dense layers)
and ``params["blocks"]["0"]`` (the expert layers) stacked over layers, each
with ``attn`` = {wq, wkv_a, kv_norm, wkv_b, wo} and ``moe`` = {router,
wg, wu, wd, shared}.

Counts. A token multiplies through the attention weights, the router, the
shared expert, and on average ``k * G / E`` of the held experts (G held, E
routed over), plus the dense layers' MLP. Decode attention is absorbed
(``q̃·c + q_rope·k_rope``, then the weighted latent): per cached key and
layer ``2 * heads * (r + rope + r)`` operations, reading ``r + rope``
values. A step of T tokens reads each held expert it touches once, taken
as the number a uniform router touches on average, ``G * (1 - (1 - k/E)**T)``.
Prefill attention is counted unabsorbed (keys and values expanded from the
latent, as the program computes it).
"""

from __future__ import annotations

from typing import Dict, Sequence

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def dims(cfg: dict) -> Dict[str, int]:
    if cfg.get("q_lora_rank") is not None:
        raise ValueError("layout mla_moe reads queries without q_lora")
    if (cfg.get("scoring_func", "softmax") != "softmax"
            or cfg.get("topk_method", "greedy") != "greedy"
            or float(cfg.get("routed_scaling_factor", 1)) != 1.0):
        raise ValueError("layout mla_moe routes greedily by softmax, unscaled")
    rs = cfg.get("rope_scaling") or {}
    if any(cfg.get(f"yarn_{k}") != v for k, v in rs.items() if k != "type"):
        raise ValueError("the yarn_* keys differ from rope_scaling")
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "r": cfg["kv_lora_rank"],
            "f": cfg["intermediate_size"], "fe": cfg["moe_intermediate_size"],
            "fs": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            "e": cfg["published_n_routed_experts"], "g": cfg["n_routed_experts"],
            "offset": cfg["expert_parallel_rank"] * cfg["n_routed_experts"],
            "k": cfg["num_experts_per_tok"], "lead": cfg["first_k_dense_replace"],
            "layers": cfg["num_hidden_layers"],
            "moe_layers": cfg["num_hidden_layers"] - cfg["first_k_dense_replace"],
            "vocab": cfg["vocab_size"], "bytes": _DTYPE_BYTES[cfg["torch_dtype"]]}


def _attn_shapes(m: dict, n: int) -> dict:
    d, h, r = m["d"], m["h"], m["r"]
    return {"wq": (n, d, h * (m["nope"] + m["rope"])), "wkv_a": (n, d, r + m["rope"]),
            "kv_norm": {"scale": (n, r)}, "wkv_b": (n, r, h * (m["nope"] + m["v"])),
            "wo": (n, h * m["v"], d)}


def _mlp_shapes(d: int, f: int, n: int) -> dict:
    return {"wg": (n, d, f), "wu": (n, d, f), "wd": (n, f, d)}


def shapes(cfg: dict) -> dict:
    m = dims(cfg)
    d, n, lead = m["d"], m["moe_layers"], m["lead"]
    tree = {
        "embed": (m["vocab"], d),
        "final_norm": {"scale": (d,)},
        "lm_head": (d, m["vocab"]),
        "blocks": {"0": {
            "ln1": {"scale": (n, d)}, "attn": _attn_shapes(m, n), "ln2": {"scale": (n, d)},
            "moe": {"router": ((n, d, m["e"]), "float32"),
                    "wg": (n, m["g"], d, m["fe"]), "wu": (n, m["g"], d, m["fe"]),
                    "wd": (n, m["g"], m["fe"], d),
                    "shared": _mlp_shapes(d, m["fs"], n)},
        }},
    }
    if lead:
        tree["lead"] = {"ln1": {"scale": (lead, d)}, "attn": _attn_shapes(m, lead),
                        "ln2": {"scale": (lead, d)}, "mlp": _mlp_shapes(d, m["f"], lead)}
    return tree


def program_fields(cfg: dict) -> dict:
    m = dims(cfg)
    rs = cfg.get("rope_scaling") or {}
    yarn = rs.get("type") == "yarn"
    return {"d_model": m["d"], "d_ff": m["f"], "num_heads": m["h"],
            "num_layers": m["layers"], "vocab_size": m["vocab"],
            "first_dense_layers": m["lead"], "block_pattern": ("moe",),
            "num_experts": m["e"], "experts_held": m["g"], "expert_offset": m["offset"],
            "num_experts_per_tok": m["k"], "expert_d_ff": m["fe"],
            "shared_expert_d_ff": m["fs"], "moe_dropless": True,
            "norm_topk_prob": cfg["norm_topk_prob"],
            "kv_lora_rank": m["r"], "qk_nope_head_dim": m["nope"],
            "qk_rope_head_dim": m["rope"], "v_head_dim": m["v"],
            "rope_theta": cfg["rope_theta"],
            "rope_factor": rs["factor"] if yarn else 1.0,
            "rope_original_max_pos": rs["original_max_position_embeddings"] if yarn else 0,
            "yarn_beta_fast": rs["beta_fast"] if yarn else 32.0,
            "yarn_beta_slow": rs["beta_slow"] if yarn else 1.0,
            "yarn_mscale": rs["mscale"] if yarn else 1.0,
            "yarn_mscale_all_dim": rs["mscale_all_dim"] if yarn else 0.0,
            "dtype": cfg["torch_dtype"], "tie_embeddings": cfg["tie_word_embeddings"],
            "norm": "rmsnorm", "act": cfg["hidden_act"]}


def _attn_params(m: dict) -> int:
    d, h, r = m["d"], m["h"], m["r"]
    return (d * h * (m["nope"] + m["rope"]) + d * (r + m["rope"])
            + r * h * (m["nope"] + m["v"]) + h * m["v"] * d)


def _expert_params(m: dict) -> int:
    return 3 * m["d"] * m["fe"]


def layer_matmul_params(cfg: dict) -> int:
    """Weights one token multiplies through in one expert layer: attention,
    the router, the shared expert and its share k*G/E of the held experts."""
    m = dims(cfg)
    return (_attn_params(m) + m["d"] * m["e"] + 3 * m["d"] * m["fs"]
            + m["k"] * m["g"] / m["e"] * _expert_params(m))


def matmul_params(cfg: dict) -> float:
    """N of the 2*N rule: the dense layers, the expert layers and the head."""
    m = dims(cfg)
    dense = _attn_params(m) + 3 * m["d"] * m["f"]
    return (m["lead"] * dense + m["moe_layers"] * layer_matmul_params(cfg)
            + m["d"] * m["vocab"])


def _weight_bytes(cfg: dict, experts_read: float) -> int:
    """Bytes of every weight but the held experts (the router in float32),
    and of ``experts_read`` held experts in each expert layer."""
    m = dims(cfg)
    n, d = m["moe_layers"], m["d"]
    norms = (2 * m["layers"] + 1) * d + m["layers"] * m["r"]
    served = (m["layers"] * _attn_params(m) + m["lead"] * 3 * d * m["f"]
              + n * (3 * d * m["fs"] + experts_read * _expert_params(m))
              + d * m["vocab"] + norms)
    return round(served * m["bytes"] + n * d * m["e"] * 4)


def weight_bytes(cfg: dict) -> int:
    """Bytes of every weight held: all held experts of every layer."""
    return _weight_bytes(cfg, dims(cfg)["g"])


def expected_experts(cfg: dict, tokens: int) -> float:
    """Held experts a step of ``tokens`` tokens reads in one layer, under
    uniform routing over all E: each is missed by all of them with chance
    (1 - k/E)**T."""
    m = dims(cfg)
    return m["g"] * (1 - (1 - m["k"] / m["e"]) ** tokens)


def kv_bytes_per_token(cfg: dict) -> int:
    """The latent and the rotary key a token keeps, over all layers."""
    m = dims(cfg)
    return m["layers"] * (m["r"] + m["rope"]) * m["bytes"]


def attention_flops(cfg: dict, query_pos: int) -> int:
    """Absorbed decode attention of one query at 0-based ``query_pos`` over
    ``query_pos + 1`` keys in every layer: scores over the latent and the
    rotary key, and the weighted latent."""
    m = dims(cfg)
    return m["layers"] * 2 * m["h"] * (2 * m["r"] + m["rope"]) * (query_pos + 1)


def decode_flops(cfg: dict, context: Sequence[int]) -> float:
    """One decode step, lane i at length ``context[i]`` before it."""
    return sum(2 * matmul_params(cfg) + attention_flops(cfg, c) for c in context)


def decode_bytes(cfg: dict, context: Sequence[int]) -> int:
    """One decode step: the weights its tokens touch, each lane's live
    latent read, and its new one written."""
    kv = kv_bytes_per_token(cfg)
    return (_weight_bytes(cfg, expected_experts(cfg, len(context)))
            + sum(c * kv + kv for c in context))


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """A whole prompt: 2*N per token plus causal attention with keys and
    values expanded from the latent, sum over positions p < P of p + 1 keys."""
    m = dims(cfg)
    p = prompt_len
    pair = 2 * m["h"] * (m["nope"] + m["rope"] + m["v"])
    return 2 * matmul_params(cfg) * p + m["layers"] * pair * p * (p + 1) // 2


def prefill_bytes(cfg: dict, prompt_len: int) -> int:
    """The weights the prompt's tokens touch and its latent written."""
    return (_weight_bytes(cfg, expected_experts(cfg, prompt_len))
            + prompt_len * kv_bytes_per_token(cfg))
