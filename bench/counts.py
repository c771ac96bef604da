"""Operations and bytes a serving step needs, from a configuration's shapes.

Counted from the published shapes and the live lengths of the step, never
from ``compiled.cost_analysis()`` (which counts a 32-layer ``lax.scan``
body once). What the step *needs* is counted, not what the program moves:
the decode step reads each lane's live keys and values, not the whole
page table it gathers, so waste shows as a lower roofline share.

Each count is the configuration's layout's (``bench/layouts``): the names
here pass ``cfg`` on to it. ``cfg`` is a configuration file of
``bench/configs`` (Hugging Face keys).
"""

from __future__ import annotations

from typing import Dict, Sequence

from bench import layouts


def dims(cfg: dict) -> Dict[str, int]:
    return layouts.load(cfg).dims(cfg)


def layer_matmul_params(cfg: dict) -> int:
    return layouts.load(cfg).layer_matmul_params(cfg)


def matmul_params(cfg: dict) -> int:
    return layouts.load(cfg).matmul_params(cfg)


def weight_bytes(cfg: dict) -> int:
    return layouts.load(cfg).weight_bytes(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    return layouts.load(cfg).kv_bytes_per_token(cfg)


def attention_flops(cfg: dict, query_pos: int) -> int:
    return layouts.load(cfg).attention_flops(cfg, query_pos)


def decode_flops(cfg: dict, context: Sequence[int]) -> int:
    return layouts.load(cfg).decode_flops(cfg, context)


def decode_bytes(cfg: dict, context: Sequence[int]) -> int:
    return layouts.load(cfg).decode_bytes(cfg, context)


def prefill_flops(cfg: dict, prompt_len: int) -> int:
    return layouts.load(cfg).prefill_flops(cfg, prompt_len)


def prefill_bytes(cfg: dict, prompt_len: int) -> int:
    return layouts.load(cfg).prefill_bytes(cfg, prompt_len)


def least_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """Roofline bound: the larger of compute time and memory time."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
