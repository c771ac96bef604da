"""Mixture-of-experts decoder layers (Mixtral-style keys): the dense
layout's GQA attention, then a routed MLP in every layer. A float32 router
``[d, E]`` scores the token, softmax over all E experts, the top
``num_experts_per_tok`` (k) are kept and their gates renormalised to sum to
1; each kept expert is a gated MLP of width ``intermediate_size``; no token
is dropped.

The served layout is the program's ``moe`` block: ``params["blocks"]["0"]``
holds ``ln1``, ``attn`` (as in ``dense``), ``ln2`` and ``moe`` (``router``
float32 ``[L, d, E]``; ``wg``, ``wu`` ``[L, E, d, F]``; ``wd``
``[L, E, F, d]``). The file states the program's ``capacity_factor``; at
``capacity_factor * k >= E`` every expert takes all T*k claims a step can
make, so the program drops no token, as the reference assumes.

Counts: a token multiplies through its k experts; a step reads each expert
it touches once, and a step of T tokens is taken to touch the number a
uniform router touches on average, ``E * (1 - (1 - k/E)**T)``: k at T = 1,
all E as T grows.
"""

from __future__ import annotations

from typing import Sequence

from bench.layouts import dense

dims = dense.dims
kv_bytes_per_token = dense.kv_bytes_per_token
attention_flops = dense.attention_flops


def experts(cfg: dict):
    """(E, k) of ``cfg``."""
    return cfg["num_local_experts"], cfg["num_experts_per_tok"]


def shapes(cfg: dict) -> dict:
    m = dims(cfg)
    d, f, n = m["d"], m["f"], m["layers"]
    e, _ = experts(cfg)
    tree = dense.shapes(cfg)
    block = tree["blocks"]["0"]
    del block["mlp"]
    block["moe"] = {"router": ((n, d, e), "float32"),
                    "wg": (n, e, d, f), "wu": (n, e, d, f), "wd": (n, e, f, d)}
    return tree


def program_fields(cfg: dict) -> dict:
    want = dense.program_fields(cfg)
    del want["d_ff"]
    e, k = experts(cfg)
    want.update({"block_pattern": ("moe",), "num_experts": e, "num_experts_per_tok": k,
                 "expert_d_ff": cfg["intermediate_size"],
                 "capacity_factor": cfg["capacity_factor"]})
    return want


def expected_experts(cfg: dict, tokens: int) -> float:
    """Experts a step of ``tokens`` tokens reads in one layer, under uniform
    routing: each is missed by all ``tokens`` with chance (1 - k/E)**T."""
    e, k = experts(cfg)
    return e * (1 - (1 - k / e) ** tokens)


def _expert_params(cfg: dict) -> int:
    m = dims(cfg)
    return 3 * m["d"] * m["f"]


def _attn_params(cfg: dict) -> int:
    m = dims(cfg)
    return m["d"] * m["hd"] * (2 * m["h"] + 2 * m["kv"])


def layer_matmul_params(cfg: dict) -> int:
    """Weights one token multiplies through in one layer: q, k, v, o, the
    router and its k experts."""
    e, k = experts(cfg)
    return _attn_params(cfg) + dims(cfg)["d"] * e + k * _expert_params(cfg)


def matmul_params(cfg: dict) -> int:
    """N of the 2*N rule (the weights one token multiplies through): every
    layer plus the output head."""
    m = dims(cfg)
    return m["layers"] * layer_matmul_params(cfg) + m["d"] * m["vocab"]


def _weight_bytes(cfg: dict, experts_read: float) -> int:
    """Bytes of every weight but the experts (the router in float32), and
    of ``experts_read`` experts in each layer."""
    m = dims(cfg)
    e, _ = experts(cfg)
    n, d = m["layers"], m["d"]
    norms = (2 * n + 1) * d
    served = n * _attn_params(cfg) + n * experts_read * _expert_params(cfg) + d * m["vocab"] + norms
    return round(served * m["bytes"] + n * d * e * 4)


def weight_bytes(cfg: dict) -> int:
    """Bytes of every weight held: all E experts of every layer."""
    return _weight_bytes(cfg, experts(cfg)[0])


def decode_flops(cfg: dict, context: Sequence[int]) -> int:
    """One decode step, lane i at length ``context[i]`` before it."""
    return sum(2 * matmul_params(cfg) + attention_flops(cfg, c) for c in context)


def decode_bytes(cfg: dict, context: Sequence[int]) -> int:
    """One decode step: the weights its ``len(context)`` tokens touch, each
    lane's live keys and values read, and its new ones written."""
    kv = kv_bytes_per_token(cfg)
    return (_weight_bytes(cfg, expected_experts(cfg, len(context)))
            + sum(c * kv + kv for c in context))


def prefill_flops(cfg: dict, prompt_len: int) -> int:
    """A whole prompt: 2*N per token plus causal attention."""
    m = dims(cfg)
    p = prompt_len
    attn = 2 * m["layers"] * m["h"] * m["hd"] * p * (p + 1)
    return 2 * matmul_params(cfg) * p + attn


def prefill_bytes(cfg: dict, prompt_len: int) -> int:
    """The weights the prompt's tokens touch and its keys and values written."""
    return (_weight_bytes(cfg, expected_experts(cfg, prompt_len))
            + prompt_len * kv_bytes_per_token(cfg))
