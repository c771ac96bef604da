"""One module per architecture of the served layers, found by the
``layout`` key of a configuration file (``dense`` where it has none).

A layout module describes its architecture to the harness, and the harness
reads it through these names only:

- ``shapes(cfg)``: the parameter tree the program takes; a leaf is a shape,
  or a ``(shape, dtype)`` pair where it is held in another dtype than
  ``torch_dtype`` (``weights.py`` builds each leaf in its own);
- ``program_fields(cfg)``: the program's ``ModelConfig`` attributes and the
  values the file requires of them (``run.check_program``);
- the counts behind the roofline metrics (``counts.py``): ``matmul_params``,
  ``weight_bytes``, ``kv_bytes_per_token``, ``decode_flops(cfg, context)``,
  ``decode_bytes(cfg, context)``, ``prefill_flops(cfg, prompt_len)`` and
  ``prefill_bytes(cfg, prompt_len)``.
"""

from __future__ import annotations

import importlib

DEFAULT = "dense"


def name(cfg: dict) -> str:
    return cfg.get("layout", DEFAULT)


def load(cfg: dict):
    """The layout module of configuration ``cfg``."""
    return importlib.import_module(f"bench.layouts.{name(cfg)}")
