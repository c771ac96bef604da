"""Roofline extraction: collective parsing on known HLO, wire-byte math, and
the while-loop cost-extrapolation calibration (in a subprocess so the main
test process keeps its single-device jax)."""

import subprocess
import sys
import textwrap

from repro.launch import roofline as R


def test_collective_parse_brace_groups():
    hlo = """
  %ar = f32[1024,64]{1,0} all-reduce(f32[1024,64] %x), replica_groups={{0,1,2,3}}, to_apply=%add
  %ag = bf16[2048,128]{1,0} all-gather(bf16[512,128] %y), replica_groups={{0,1,2,3}}, dimensions={0}
  %rs = f32[256]{0} reduce-scatter(f32[1024] %z), replica_groups={{0,1,2,3}}, to_apply=%add
  %cp = f32[64,64]{1,0} collective-permute(f32[64,64] %w), source_target_pairs={{0,1}}
"""
    wire = R.collective_wire_bytes(hlo)
    ar = 2 * 1024 * 64 * 4 * 3 / 4
    ag = 2048 * 128 * 2 * 3 / 4
    rs = 256 * 4 * 3
    cp = 64 * 64 * 4
    assert abs(wire["all-reduce"] - ar) < 1
    assert abs(wire["all-gather"] - ag) < 1
    assert abs(wire["reduce-scatter"] - rs) < 1
    assert abs(wire["collective-permute"] - cp) < 1
    assert wire["ops"] == 4


def test_collective_parse_iota_groups_and_async():
    hlo = """
  %ars = f32[100]{0} all-reduce-start(f32[100] %x), replica_groups=[16,32]<=[512], to_apply=%add
  %ard = f32[100]{0} all-reduce-done(f32[100] %ars)
"""
    wire = R.collective_wire_bytes(hlo)
    # counted once (start only), n=32 participants
    assert abs(wire["all-reduce"] - 2 * 100 * 4 * 31 / 32) < 1
    assert wire["ops"] == 1


def test_roofline_terms_dominance():
    cost = {"flops": 197e12 * 2.0, "bytes accessed": 819e9 * 0.5}
    terms = R.roofline_terms(cost, "")
    assert terms["dominant"] == "compute"
    assert abs(terms["compute_s"] - 2.0) < 1e-9
    assert abs(terms["memory_s"] - 0.5) < 1e-9


def test_model_flops():
    assert R.model_flops(1000, 10, "train") == 6e4
    assert R.model_flops(1000, 10, "decode") == 2e4


_CALIB = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import functools, jax, jax.numpy as jnp
    M, R = 128, 8
    def loss(x, ws, unroll):
        def body(x, w):
            return jnp.tanh(x @ w), ()
        x, _ = jax.lax.scan(body, x, ws, unroll=unroll)
        return jnp.sum(x)
    g = jax.grad(loss, argnums=1)
    xs = jax.ShapeDtypeStruct((M, M), jnp.float32)
    ws = jax.ShapeDtypeStruct((R, M, M), jnp.float32)
    c = {}
    for u in (1, 2):
        comp = jax.jit(functools.partial(g, unroll=u)).lower(xs, ws).compile()
        c[u] = comp.cost_analysis()["flops"]
    slope = c[2] - c[1]
    total = c[1] - slope + R * slope
    exact = 6 * M**3 * R  # fwd 2M^3 + bwd 4M^3 per layer
    ratio = total / exact
    assert 0.95 < ratio < 1.10, ratio
    print("CALIB_OK", ratio)
""")


def test_unroll_extrapolation_calibration():
    """XLA counts while bodies once; the 2-point unroll extrapolation
    reconstructs true flops to within 10% (the dry-run's cost model)."""
    r = subprocess.run([sys.executable, "-c", _CALIB], capture_output=True,
                       text=True, timeout=300)
    assert "CALIB_OK" in r.stdout, r.stdout + r.stderr
