"""Per-architecture smoke tests (reduced configs, CPU) + full-config param
counts via eval_shape (no allocation) + decode/prefill consistency."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.models import (apply, decode_step, init_cache, init_params,
                          loss_fn, prefill)
from repro.models.frontends import vision_patch_embeds
from repro.training import optimizer as O

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_train_step(arch):
    cfg = get_config(arch, smoke=True)
    params = init_params(cfg, KEY)
    B, S = 2, 16
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size, jnp.int32)
    batch = {"tokens": tokens}
    if cfg.frontend == "vision":
        batch["extra_embeds"] = vision_patch_embeds(cfg, B, 4, KEY)

    logits, aux = apply(params, tokens, cfg)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all()), "NaN/Inf in forward"

    # one full train step on CPU
    opt_cfg = O.OptConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    opt_state = O.init(params, opt_cfg)
    (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, batch, cfg)
    assert bool(jnp.isfinite(loss))
    new_params, opt_state, om = O.apply_updates(params, grads, opt_state, opt_cfg)
    assert bool(jnp.isfinite(om["grad_norm"]))
    # params actually changed
    delta = jax.tree_util.tree_reduce(
        lambda a, x: a + float(jnp.sum(jnp.abs(x[0].astype(jnp.float32)
                                               - x[1].astype(jnp.float32)))),
        jax.tree_util.tree_map(lambda a, b: (a, b), params, new_params),
        0.0)
    assert delta > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_decode_matches_full_forward(arch):
    cfg = get_config(arch, smoke=True)
    params = init_params(cfg, KEY)
    B, S = 2, 12
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size, jnp.int32)
    full_logits, _ = apply(params, tokens, cfg)
    cache = init_cache(cfg, B, S + 4)
    lg, cache = prefill(params, tokens[:, :8], cfg, cache)
    errs = [float(jnp.max(jnp.abs(lg - full_logits[:, 7])))]
    for t in range(8, S):
        lg, cache = decode_step(params, tokens[:, t:t + 1], cfg, cache)
        errs.append(float(jnp.max(jnp.abs(lg - full_logits[:, t]))))
    assert max(errs) < 5e-3, f"{arch}: decode diverges {errs}"


# published sizes (total params) the configs must land near
_EXPECTED_B = {
    "glm4_9b": (9.4, 0.25), "yi_6b": (6.1, 0.25), "phi3_mini": (3.8, 0.3),
    "command_r_35b": (35.0, 0.3), "llama4_maverick": (400.0, 0.3),
    "granite_moe": (3.3, 0.45), "xlstm_125m": (0.125, 0.45),
    "hymba_1_5b": (1.5, 0.45), "llava_next": (7.2, 0.25),
    "musicgen_large": (3.3, 0.6),
    # one rank of 8-way expert parallelism: 8 of the 64 experts held
    "deepseek_v2_lite": (3.11, 0.01),
}


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_param_count(arch):
    cfg = get_config(arch)
    p_struct = jax.eval_shape(lambda k: init_params(cfg, k),
                              jax.ShapeDtypeStruct((2,), jnp.uint32))
    n = sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(p_struct))
    expected, tol = _EXPECTED_B[arch]
    assert abs(n / 1e9 - expected) / expected < tol, (
        f"{arch}: {n/1e9:.2f}B params vs published ~{expected}B")


def test_vlm_frontend_stub_path():
    cfg = get_config("llava_next", smoke=True)
    params = init_params(cfg, KEY)
    B, S, NI = 2, 8, 4
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size, jnp.int32)
    embeds = vision_patch_embeds(cfg, B, NI, KEY)
    loss, metrics = loss_fn(params, {"tokens": tokens, "extra_embeds": embeds}, cfg)
    assert bool(jnp.isfinite(loss))
    logits, _ = apply(params, tokens, cfg, extra_embeds=embeds)
    assert logits.shape == (B, NI + S, cfg.vocab_size)


def test_sliding_window_cache_is_ring():
    """Hymba ring cache: memory is O(window), decode still exact (the CMP
    window made literal)."""
    cfg = get_config("hymba_1_5b", smoke=True)
    params = init_params(cfg, KEY)
    B, S = 1, 24
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size, jnp.int32)
    full_logits, _ = apply(params, tokens, cfg)
    cache = init_cache(cfg, B, cfg.sliding_window)  # ring of window size
    # SWA prefill must proceed in <=window chunks (single-shot prefill past
    # the ring would drop keys that intermediate positions still need —
    # standard SWA-serving constraint, noted in DESIGN.md)
    lg, cache = prefill(params, tokens[:, :cfg.sliding_window], cfg, cache)
    kv_t = cache["blocks"]["0"][0].k.shape[2]
    assert kv_t == cfg.sliding_window  # ring never grows
    err = float(jnp.max(jnp.abs(lg - full_logits[:, cfg.sliding_window - 1])))
    for t in range(cfg.sliding_window, S):
        lg, cache = decode_step(params, tokens[:, t:t + 1], cfg, cache)
        err = max(err, float(jnp.max(jnp.abs(lg - full_logits[:, t]))))
    assert err < 5e-3


def test_moe_dispatch_capacity_and_fifo():
    from repro.models.moe import assign_slots
    ids = jnp.asarray(np.array([0, 1, 0, 0, 1, 2, 0], np.int32))
    slot, keep = assign_slots(ids, num_experts=3, capacity=2)
    # expert 0 requests at positions 0,2,3,6 -> first two kept (FIFO), rest drop
    assert bool(keep[0]) and bool(keep[2]) and not bool(keep[3]) and not bool(keep[6])
    assert int(slot[0]) == 0 and int(slot[2]) == 1
    # expert 1: positions 1,4 both kept
    assert bool(keep[1]) and bool(keep[4])


def test_mlstm_state_decode_equals_scan():
    from repro.models.ssm import mlstm_scan
    B, H, S, d = 2, 2, 10, 8
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (B, H, S, d), jnp.float32)
    k = jax.random.normal(ks[1], (B, H, S, d), jnp.float32)
    v = jax.random.normal(ks[2], (B, H, S, d), jnp.float32)
    i = jax.random.normal(ks[3], (B, H, S), jnp.float32)
    f = jax.random.normal(ks[4], (B, H, S), jnp.float32) + 2.0
    h_all, _ = mlstm_scan(q, k, v, i, f)
    # step-by-step with carried state
    state = None
    outs = []
    for t in range(S):
        h_t, state = mlstm_scan(q[:, :, t:t+1], k[:, :, t:t+1], v[:, :, t:t+1],
                                i[:, :, t:t+1], f[:, :, t:t+1], state=state)
        outs.append(h_t)
    h_inc = jnp.concatenate(outs, axis=2)
    np.testing.assert_allclose(np.asarray(h_all), np.asarray(h_inc),
                               atol=1e-5, rtol=1e-5)


def test_ssd_chunked_matches_stepwise():
    from repro.models.ssm import ssd_chunked, ssd_decode_step
    B, S, H, P, N = 1, 12, 2, 4, 3
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    b = jax.random.normal(ks[1], (B, S, H, N), jnp.float32)
    c = jax.random.normal(ks[2], (B, S, H, N), jnp.float32)
    la = -jax.nn.softplus(jax.random.normal(ks[3], (B, S, H), jnp.float32))
    y_chunk, hf = ssd_chunked(x, b, c, la, chunk=4)
    state = jnp.zeros((B, H, P, N), jnp.float32)
    ys = []
    for t in range(S):
        y_t, state = ssd_decode_step(x[:, t], b[:, t], c[:, t], la[:, t], state)
        ys.append(y_t[:, None])
    y_step = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_step),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(state),
                               atol=1e-4, rtol=1e-4)
