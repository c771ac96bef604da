"""Layout modules: the dense one gives the benchmark's configurations
exactly the shapes, program fields and counts the harness had before it
read them from a layout; a leaf with its own dtype is built in it; the MoE
counts read k experts for one token and tend to all of them."""

import json
import math

import jax
import pytest

from bench import counts, layouts
from bench import weights as W
from bench.layouts import dense, moe
from bench.tests import tiny

CONFIGS = tiny.REPO / "bench" / "configs"


def cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def tiny_cfg(name):
    return json.loads((tiny.DATA / f"{name}.json").read_text())


YI_SHAPES = {
    "embed": (64000, 4096),
    "final_norm": {"scale": (4096,)},
    "lm_head": (4096, 64000),
    "blocks": {"0": {
        "ln1": {"scale": (32, 4096)},
        "attn": {"wq": (32, 4096, 4096), "wk": (32, 4096, 512),
                 "wv": (32, 4096, 512), "wo": (32, 4096, 4096)},
        "ln2": {"scale": (32, 4096)},
        "mlp": {"wg": (32, 4096, 11008), "wu": (32, 4096, 11008), "wd": (32, 11008, 4096)},
    }},
}

YI_PROGRAM = {"d_model": 4096, "d_ff": 11008, "num_heads": 32, "num_kv_heads": 4,
              "num_layers": 32, "vocab_size": 64000, "resolved_head_dim": 128,
              "rope_theta": 5000000.0, "dtype": "bfloat16", "tie_embeddings": False,
              "block_pattern": ("dense",), "norm": "rmsnorm", "act": "silu"}

# (matmul_params, weight_bytes, kv_bytes_per_token, decode_flops, decode_bytes at
# contexts [0, 17, 300, 1023], prefill_flops, prefill_bytes at 256), from the
# formulas of bench/counts.py before they moved to bench/layouts/dense.py
PINNED = {
    "yi_6b": (5_798_625_280, 11_597_783_040, 65_536, 47_093_645_312, 11_685_863_424,
              2_986_143_121_408, 11_614_560_256),
    "phi3_mini": (3_722_379_264, 7_445_157_888, 393_216, 30_307_516_416, 7_973_640_192,
                  1_918_793_416_704, 7_545_821_184),
}
CONTEXT = [0, 17, 300, 1023]


def leaves(tree):
    return jax.tree_util.tree_leaves(tree, is_leaf=lambda x: isinstance(x, tuple))


def test_a_configuration_without_the_key_is_dense():
    for name in ("yi_6b", "phi3_mini"):
        assert "layout" not in cfg(name) and layouts.load(cfg(name)) is dense
    assert layouts.load(tiny_cfg("tiny_moe")) is moe


def test_yi_6b_leaf_tree_and_program_fields_are_pinned():
    yi = cfg("yi_6b")
    assert W.shapes(yi) == dense.shapes(yi) == YI_SHAPES
    assert sum(math.prod(s) for s in leaves(W.shapes(yi))) == 6_061_035_520
    assert dense.program_fields(yi) == YI_PROGRAM


@pytest.mark.parametrize("name", sorted(PINNED))
def test_dispatched_counts_are_the_dense_formulas(name):
    c = cfg(name)
    got = (counts.matmul_params(c), counts.weight_bytes(c), counts.kv_bytes_per_token(c),
           counts.decode_flops(c, CONTEXT), counts.decode_bytes(c, CONTEXT),
           counts.prefill_flops(c, 256), counts.prefill_bytes(c, 256))
    assert got == PINNED[name]
    assert counts.dims(c) == dense.dims(c)
    assert counts.layer_matmul_params(c) == dense.layer_matmul_params(c)
    assert counts.attention_flops(c, 99) == dense.attention_flops(c, 99)


def test_a_leaf_with_its_own_dtype_is_built_in_it():
    c = dict(tiny_cfg("tiny_moe"), torch_dtype="bfloat16")
    w = W.make(c, 2**31 + 3)
    block = w["blocks"]["0"]
    assert block["moe"]["router"].dtype == "float32"
    assert block["moe"]["router"].shape == (2, 64, 4)
    others = [x for x in jax.tree_util.tree_leaves(w) if x is not block["moe"]["router"]]
    assert others and all(x.dtype == "bfloat16" for x in others)


def test_moe_leaf_tree_and_program_fields():
    c = tiny_cfg("tiny_moe")
    block = W.shapes(c)["blocks"]["0"]
    assert "mlp" not in block
    assert block["moe"] == {"router": ((2, 64, 4), "float32"), "wg": (2, 4, 64, 64),
                            "wu": (2, 4, 64, 64), "wd": (2, 4, 64, 64)}
    want = moe.program_fields(c)
    assert "d_ff" not in want
    assert want["block_pattern"] == ("moe",)
    assert (want["num_experts"], want["num_experts_per_tok"], want["expert_d_ff"]) == (4, 2, 64)
    # the reference drops no token: every expert takes all T*k claims
    assert c["capacity_factor"] * c["num_experts_per_tok"] >= c["num_local_experts"]


@pytest.mark.parametrize("e,k", [(4, 2), (64, 6), (40, 8)])
def test_moe_counts_read_k_experts_for_one_token_and_tend_to_all(e, k):
    c = dict(tiny_cfg("tiny_moe"), num_local_experts=e, num_experts_per_tok=k,
             torch_dtype="bfloat16")
    d, f, n, v = 64, 64, 2, 512
    attn = d * 16 * (2 * 4 + 2 * 2)
    expert = 3 * d * f
    kv = counts.kv_bytes_per_token(c)
    assert kv == 2 * n * 2 * 16 * 2

    def weights_read(experts):
        return (n * (attn + experts * expert) + d * v + (2 * n + 1) * d) * 2 + n * d * e * 4

    assert counts.weight_bytes(c) == weights_read(e)
    # one token: exactly its k experts in each layer
    assert counts.decode_bytes(c, [10]) == weights_read(k) + 11 * kv
    assert counts.prefill_bytes(c, 1) == weights_read(k) + kv
    # every token multiplies through k experts, whatever the step's size
    assert counts.matmul_params(c) == n * (attn + d * e + k * expert) + d * v
    assert counts.decode_flops(c, [0, 0]) == 2 * counts.decode_flops(c, [0])
    # more tokens touch more experts, up to all of them
    steps = [counts.decode_bytes(c, [0] * t) - t * kv for t in (1, 2, 8, 64, 4096)]
    assert steps == sorted(steps) and steps[0] < steps[1]
    assert steps[-1] == pytest.approx(weights_read(e), rel=1e-9)
    assert moe.expected_experts(c, 10**6) == pytest.approx(e)
