"""Compile the main path's kernels and the full-width decode step for a
described TPU v5e chip, with no chip attached.

The TPU compiler refuses what interpret mode accepts: an in-kernel scatter,
a block not aligned to the tiling, a program larger than the chip's memory.
Each test lowers one program for one chip of a described ``v5e:2x2``
topology at the sizes the serving path uses. The topology is described
inside the ``topo`` fixture, never at import: only one process at a time
may load the TPU library, and under several test workers only the worker
that runs this file may try.
"""

import importlib.util
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import cmp_claim, cmp_ring, flash_attention, paged_attention
from repro.models import init_params
from repro.serving.admission import DeviceAdmissionRing
from repro.serving.paged_model import make_paged_forward

GiB = 2 ** 30
# v5e has 16 GiB of HBM; the smoke geometry must leave at least 1 GiB of it
# for what the process holds besides one forward call.
FITS_BYTES = 15 * GiB


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", Path(__file__).resolve().parents[1] / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("max_batch", [4, 8])
def test_ring_step_compiles_at_engine_sizes(one_chip, max_batch):
    ring = DeviceAdmissionRing.for_engine(max_batch)
    n = ring.capacity
    fn = jax.jit(lambda s, c, m, r: cmp_ring.cmp_ring_step(
        s, c, m, r, k=ring.claim_block, window=ring.window))
    compiled = fn.lower(_spec(one_chip, (n,)), _spec(one_chip, (n,)),
                        _spec(one_chip, (2,)), _spec(one_chip, (2,))).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n", [1024, 4096])  # one VMEM block / tiled grid
def test_claim_compiles(one_chip, n):
    fn = jax.jit(lambda s, c: cmp_claim.cmp_claim(s, c, k=8))
    compiled = fn.lower(_spec(one_chip, (n,)), _spec(one_chip, (n,))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _compile_decode_kernel(one_chip, cfg, batch, pages_shape, pps, layer):
    """The decode kernel with its schedule, as one layer of the served step
    calls it, compiled for one chip."""
    hd, bf = cfg.resolved_head_dim, jnp.bfloat16
    kv = (batch, cfg.num_kv_heads, hd)
    fn = jax.jit(lambda q, kn, vn, kp, vp, bt, cl: paged_attention.paged_attention(
        q, kn, vn, kp, vp, cl, paged_attention.schedule(bt, cl, pages_shape[-2]),
        layer))
    return fn.lower(
        _spec(one_chip, (batch, cfg.num_heads, hd), bf), _spec(one_chip, kv, bf),
        _spec(one_chip, kv, bf), _spec(one_chip, pages_shape, bf),
        _spec(one_chip, pages_shape, bf), _spec(one_chip, (batch, pps)),
        _spec(one_chip, (batch,))).compile()


def test_paged_attention_compiles_at_yi6b_widths(one_chip):
    """GQA 32/4 at head_dim 128, reading layer 5 of the whole stacked pool
    in place, as the served decode step does."""
    cfg = get_config("yi-6b")
    sm = _chip_smoke()
    pages = (cfg.num_layers, sm.NUM_PAGES, cfg.num_kv_heads, sm.PAGE_SIZE,
             cfg.resolved_head_dim)
    compiled = _compile_decode_kernel(one_chip, cfg, sm.MAX_BATCH, pages,
                                      sm.MAX_SEQ // sm.PAGE_SIZE, 5)
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_attention_compiles_at_phi3_widths(one_chip):
    """MHA 32/32 at head_dim 96 (not a multiple of the 128-lane tile), one
    layer's slice of the pool as the served step passes it, at phi3.decode's
    serving geometry: 8 lanes, 384 pages of 16, 2048 positions."""
    cfg = get_config("phi3-mini-3.8b")
    pages = (1, 384, cfg.num_kv_heads, 16, cfg.resolved_head_dim)
    compiled = _compile_decode_kernel(one_chip, cfg, 8, pages, 2048 // 16, 0)
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_at_yi6b_widths(one_chip):
    cfg = get_config("yi-6b")
    hd, S, bf = cfg.resolved_head_dim, 2048, jnp.bfloat16
    fn = jax.jit(lambda q, k, v: flash_attention.flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128))
    compiled = fn.lower(
        _spec(one_chip, (1, cfg.num_heads, S, hd), bf),
        _spec(one_chip, (1, cfg.num_kv_heads, S, hd), bf),
        _spec(one_chip, (1, cfg.num_kv_heads, S, hd), bf)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_yi6b_decode_step_fits_one_chip(one_chip, monkeypatch):
    """The engine's decode step at full width, with the pool chip_smoke.py
    serves from: params + pool + the call's outputs and temporaries fit, and
    attention is the paged decode kernel, as served on a TPU."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "on_tpu", lambda: True)  # this host is a CPU
    cfg = get_config("yi-6b")
    sm = _chip_smoke()
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    params = jax.tree_util.tree_map(
        lambda x: _spec(one_chip, x.shape, x.dtype), params)
    pool = _spec(one_chip, (cfg.num_layers, sm.NUM_PAGES, cfg.num_kv_heads,
                            sm.PAGE_SIZE, cfg.resolved_head_dim),
                 jnp.dtype(cfg.dtype))
    B = sm.MAX_BATCH
    compiled = make_paged_forward(cfg).lower(
        params, _spec(one_chip, (B, 1)), pool, pool,
        _spec(one_chip, (B, sm.MAX_SEQ // sm.PAGE_SIZE)),
        _spec(one_chip, (B,))).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"decode step: args {mem.argument_size_in_bytes / GiB:.2f} GiB, "
          f"out {mem.output_size_in_bytes / GiB:.2f} GiB, temp "
          f"{mem.temp_size_in_bytes / GiB:.2f} GiB, alias "
          f"{mem.alias_size_in_bytes / GiB:.2f} GiB")
    assert total <= FITS_BYTES, total / GiB
    assert "tpu_custom_call" in compiled.as_text()


def _mla_step(one_chip, batch, seq):
    """DeepSeek-V2-Lite's served forward (one rank of 8-way expert
    parallelism) at dsv2lite.decode's geometry: 6656 pages of 16, 2048
    positions a lane; ``batch`` lanes of ``seq`` tokens."""
    import re

    from repro.serving.kv_cache import page_widths
    cfg = get_config("deepseek-v2-lite")
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    params = jax.tree_util.tree_map(
        lambda x: _spec(one_chip, x.shape, x.dtype), params)
    (kv, dk), (_, dv) = page_widths(cfg)
    pools = [_spec(one_chip, (cfg.num_layers, 6656, kv, 16, d), jnp.bfloat16)
             for d in (dk, dv)]
    compiled = make_paged_forward(cfg).lower(
        params, _spec(one_chip, (batch, seq)), *pools,
        _spec(one_chip, (batch, 2048 // 16)), _spec(one_chip, (batch,))).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    # every op whose result is a whole pool, or one layer's slice of it
    pool_ops = re.findall(r"= bf16\[(?:27,)?6656,1,16,(?:512|128)\]\{[^}]*\} ([a-z-]+)",
                          compiled.as_text())
    return compiled, total, pool_ops


def test_mla_decode_step_fits_one_chip_and_reads_the_pool_in_place(one_chip, monkeypatch):
    """The served decode step of dsv2lite.decode (48 lanes): weights, the
    latent pool and the call's outputs fit; attention is the latent
    kernel, which reads both streams where they lie: no relayout and no
    layer slice of the pool, only the one plain copy of each stream the
    undonated forward makes and the lanes' slot updates."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "on_tpu", lambda: True)  # this host is a CPU
    compiled, total, pool_ops = _mla_step(one_chip, 48, 1)
    assert total <= FITS_BYTES, total / GiB
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "mla_decode_attention" in text
    assert sorted(set(pool_ops)) == ["copy", "dynamic-update-slice", "get-tuple-element",
                                     "parameter"]
    assert pool_ops.count("copy") == 2


def test_mla_prefill_fits_one_chip(one_chip):
    """The longest prompt of dsv2lite.decode (1536 tokens) prefilled beside
    the pool: it fits, and its pages are written in place (one plain copy
    of each undonated stream, no relayout)."""
    _, total, pool_ops = _mla_step(one_chip, 1, 1536)
    assert total <= FITS_BYTES, total / GiB
    assert pool_ops.count("copy") == 2
