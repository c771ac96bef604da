"""DeepSeek-V2-Lite's mechanisms at a CPU size: latent attention (MLA)
served through the paged pool, YaRN RoPE, and the dropless expert layer of
one expert-parallel rank.

Tolerances. The program runs the smoke model in float32 here, on the CPU,
where a float32 product is exact to rounding; the paged path (absorbed or
not), the full-sequence forward and the plain reference differ only in the
order of their sums, ~1e-6 in the logits. ``TOL`` (1e-4) leaves two orders
of room for that and is still far below what a bfloat16 run moves
(``test_a_bf16_run_fails_the_tolerance``)."""

import dataclasses
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.deepseek_v2_lite import CONFIG
from repro.kernels import ops as kops
from repro.models import apply, init_params
from repro.models import layers as L
from repro.models import mla
from repro.models import moe as MOE
from repro.serving import paged_model
from repro.serving.kv_cache import PagedKVPool

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))  # bench/

TOL = 1e-4
KEY = jax.random.PRNGKey(16)
PAGE = 8
TINY = Path(__file__).resolve().parents[1] / "bench" / "tests" / "data" / "tiny_mla_moe.json"


@pytest.fixture(scope="module")
def smoke():
    cfg = get_config("deepseek-v2-lite", smoke=True)
    return cfg, init_params(cfg, KEY)


def _paged_logits(cfg, params, tokens, prompt_len, kernel, monkeypatch):
    """Prefill ``tokens[:prompt_len]``, then decode the rest one by one
    through the paged pool (teacher-forced); logits at every position from
    ``prompt_len - 1`` on. (The dropless forward also returns its claims on
    held experts.)"""
    if kernel:
        monkeypatch.setattr(paged_model, "kernel_attention",
                            lambda S, c: S == 1 and c.attn_softcap == 0.0)
    pool = PagedKVPool(cfg, num_pages=16, page_size=PAGE, window=2)
    pps = 64 // PAGE
    table = jnp.arange(1, pps + 1, dtype=jnp.int32)[None]  # page 0: scratch
    fwd = paged_model.make_paged_forward(cfg)
    kp, vp = pool.k_pages, pool.v_pages
    out = []
    logits, kp, vp, _ = fwd(params, jnp.asarray(tokens[None, :prompt_len]), kp, vp,
                            table, jnp.zeros((1,), jnp.int32))
    out.append(logits[0])
    for t in range(prompt_len, len(tokens)):
        logits, kp, vp, _ = fwd(params, jnp.asarray(tokens[None, t:t + 1]), kp, vp,
                                table, jnp.full((1,), t, jnp.int32))
        out.append(logits[0])
    return jnp.stack(out)


def _reference(cfg_file, params, tokens):
    from bench.configs import mla_moe_reference as R
    return R.logits(cfg_file, params, tokens)


@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "kernel"])
def test_paged_prefill_then_decode_matches_full_forward_and_reference(smoke, monkeypatch,
                                                                     kernel):
    """Prefill 11 tokens (a page and a part), decode 9 more through the
    latent pool: the logits are the full-sequence forward's and the plain
    float32 reference's (the reference reads the program's parameter tree
    as the benchmark's weights, the same layout)."""
    cfg, params = smoke
    tokens = np.asarray(jax.random.randint(KEY, (20,), 0, cfg.vocab_size), np.int32)
    paged = _paged_logits(cfg, params, tokens, 11, kernel, monkeypatch)
    full, _ = apply(params, jnp.asarray(tokens[None]), cfg)
    ref = _reference(json.loads(TINY.read_text()), params, tokens)
    assert float(jnp.max(jnp.abs(paged - full[0, 10:]))) < TOL
    assert float(jnp.max(jnp.abs(paged - ref[10:]))) < TOL
    assert float(jnp.max(jnp.abs(full[0] - ref))) < TOL


def test_a_bf16_run_fails_the_tolerance(smoke, monkeypatch):
    """The same served path in bfloat16 misses the reference by more than
    TOL: the comparison can tell a lower precision."""
    cfg, params = smoke
    cfg16 = dataclasses.replace(cfg, dtype="bfloat16")
    p16 = jax.tree_util.tree_map_with_path(
        lambda path, a: a if "router" in jax.tree_util.keystr(path)
        else a.astype(jnp.bfloat16), params)
    tokens = np.asarray(jax.random.randint(KEY, (20,), 0, cfg.vocab_size), np.int32)
    paged = _paged_logits(cfg16, p16, tokens, 11, False, monkeypatch)
    ref = _reference(json.loads(TINY.read_text()), params, tokens)
    assert float(jnp.max(jnp.abs(paged - ref[10:]))) > TOL


def test_absorbed_attention_is_unabsorbed_attention(smoke):
    """Scores over the latent with W_UK folded into the query, and one
    expansion of the weighted latent, equal expanding keys and values."""
    cfg, params = smoke
    p = params["blocks"]["0"]["attn"]
    p = jax.tree_util.tree_map(lambda a: a[0], p)
    x = jax.random.normal(KEY, (2, 9, cfg.d_model), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(9, dtype=jnp.int32)[None], (2, 9))
    q_nope, q_rope, c, k_rope = mla.project(x, p, cfg, pos)
    k_pos = pos.at[1, 6:].set(-1)  # lane 1 holds 6 positions
    a = mla.attend(q_nope, q_rope, c, k_rope, p, cfg, pos, k_pos)
    b = mla.attend_absorbed(q_nope, q_rope, c, k_rope, p, cfg, pos, k_pos)
    assert float(jnp.max(jnp.abs(a - b))) < 1e-5


def test_latent_kernel_matches_its_oracle():
    """The kernel's latent mode (interpret mode here) against plain jnp
    over the same pages: lanes with 0, a part of a page, and several
    blocks cached; 16 query heads over one latent head of 512 + 128."""
    H, dc, dr, page, pps, B = 16, 512, 128, 16, 24, 3
    k = jax.random.split(KEY, 6)
    pages_c = jax.random.normal(k[0], (2, 96, 1, page, dc), jnp.float32)
    pages_r = jax.random.normal(k[1], (2, 96, 1, page, dr), jnp.float32)
    q = jax.random.normal(k[2], (B, H, dc + dr), jnp.float32) * 0.05
    c_new = jax.random.normal(k[3], (B, dc), jnp.float32)
    r_new = jax.random.normal(k[4], (B, dr), jnp.float32)
    lens = jnp.asarray([0, 7, 300], jnp.int32)
    tables = jax.random.permutation(k[5], 95)[:B * pps].reshape(B, pps).astype(jnp.int32) + 1
    sched = kops.paged_schedule(tables, lens, page)
    got = kops.latent_attention(q, c_new, r_new, pages_c, pages_r, lens, sched, 1,
                                sm_scale=0.11)
    for b in range(B):
        n = int(lens[b])
        c = pages_c[1, tables[b]].reshape(-1, dc)[:n]
        r = pages_r[1, tables[b]].reshape(-1, dr)[:n]
        c = jnp.concatenate([c, c_new[b][None]])
        r = jnp.concatenate([r, r_new[b][None]])
        s = (q[b, :, :dc] @ c.T + q[b, :, dc:] @ r.T) * 0.11
        want = jax.nn.softmax(s, -1) @ c
        assert float(jnp.max(jnp.abs(got[b] - want))) < 1e-4, b


def test_yarn_frequencies_at_the_published_numbers():
    """DeepSeek-V2-Lite's rope dims (64, theta 1e4, factor 40, original
    4096, beta 32 / 1): pairs 0-10 keep theta^(-2j/64), pairs 23-31 are
    divided by 40, linear in (j - 10) / 13 between; cos and sin unscaled;
    the softmax scale 192^-0.5 (0.1 * 0.707 * ln 40 + 1)^2."""
    freqs, scale = L.rope_tables(CONFIG, 64)
    plain = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    want = plain / 40 * ramp + plain * (1 - ramp)
    np.testing.assert_allclose(np.asarray(freqs), want, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(freqs)[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(freqs)[23:], plain[23:] / 40, rtol=1e-6)
    assert scale == 1.0
    m = 0.1 * 0.707 * math.log(40) + 1
    assert mla.softmax_scale(CONFIG) == pytest.approx(192 ** -0.5 * m * m)
    assert mla.softmax_scale(CONFIG) == pytest.approx(0.11472, abs=5e-6)


def _expert_params(E, D, F, key, shared=True):
    k = jax.random.split(key, 7)
    p = {"router": jax.random.normal(k[0], (D, E)) * 0.5,
         "wg": jax.random.normal(k[1], (E, D, F)) * 0.1,
         "wu": jax.random.normal(k[2], (E, D, F)) * 0.1,
         "wd": jax.random.normal(k[3], (E, F, D)) * 0.1}
    if shared:
        p["shared"] = {"wg": jax.random.normal(k[4], (D, 2 * F)) * 0.1,
                       "wu": jax.random.normal(k[5], (D, 2 * F)) * 0.1,
                       "wd": jax.random.normal(k[6], (2 * F, D)) * 0.1}
    return p


@pytest.mark.parametrize("ranks", [2, 8])
def test_the_ranks_shares_sum_to_the_uncut_layer(ranks):
    """Each rank of an 8-expert layer holds 8 / ranks experts; the ranks'
    routed parts, with the shared expert counted once, add up to the uncut
    layer's output, and their claims to every claim."""
    E, D, F, k = 8, 32, 16, 3
    p = _expert_params(E, D, F, KEY)
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (2, 7, D))
    whole, _, n_whole = MOE.held_moe_block(x, p, num_experts=E, top_k=k,
                                           norm_topk_prob=False)
    G = E // ranks
    routed = {key: p[key] for key in ("router", "wg", "wu", "wd")}
    parts, claims = [], 0
    for rank in range(ranks):
        held = dict(routed, **{w: p[w][rank * G:(rank + 1) * G] for w in ("wg", "wu", "wd")})
        y, _, n = MOE.held_moe_block(x, held, num_experts=E, top_k=k,
                                     expert_offset=rank * G, norm_topk_prob=False)
        parts.append(y)
        claims += int(n)
    shared = L.swiglu(x, p["shared"])
    np.testing.assert_allclose(np.asarray(sum(parts) + shared), np.asarray(whole),
                               atol=1e-5)
    assert claims == int(n_whole) == 2 * 7 * k


def test_dropless_routing_under_a_skewed_router():
    """A router that sends every token to the same experts: the held-expert
    layer computes every claim (equal to running each expert over every
    token, weighted by its gate where kept), where the capacity layer drops
    most of them."""
    E, D, F, k, T = 8, 32, 16, 2, 40
    p = _expert_params(E, D, F, KEY, shared=False)
    p["router"] = p["router"].at[:, :2].add(1.0)  # experts 0 and 1 win
    x = jnp.abs(jax.random.normal(jax.random.fold_in(KEY, 2), (1, T, D))) + 0.5
    y, _, n = MOE.held_moe_block(x, p, num_experts=E, top_k=k, norm_topk_prob=True)
    probs = jax.nn.softmax(x[0] @ p["router"], -1)
    top, ids = jax.lax.top_k(probs, k)
    top = top / top.sum(-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[jnp.arange(T)[:, None], ids].set(top)
    outs = jnp.stack([L.swiglu(x[0], {"wg": p["wg"][e], "wu": p["wu"][e], "wd": p["wd"][e]})
                      for e in range(E)], 1)                      # [T, E, D]
    want = jnp.einsum("te,ted->td", gates, outs)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want), atol=1e-5)
    assert int(n) == T * k and bool((ids < 2).all())
    capped, _ = MOE.moe_block(x, p, num_experts=E, top_k=k, capacity_factor=1.0,
                              min_capacity=1)
    assert float(jnp.max(jnp.abs(capped[0] - want))) > 1e-2


def test_the_engine_serves_it_and_counts_routed_claims_only_when_traced(smoke):
    """``Fabric.open(arch="deepseek-v2-lite")`` serves the smoke model
    through the engine; with a flight recorder attached each forward
    emits its claims on held experts (none dropped), without one nothing
    is emitted; one forward serves both."""
    from repro.fabric import Fabric, FabricConfig
    from repro.obs import ObsConfig
    cfg, params = smoke
    prompts = [[5, 17, 200, 3, 9], [9, 9, 42, 7, 7, 7, 1, 2, 3]]
    outs = []
    for obs in (None, ObsConfig(trace_rate=1.0)):
        config = FabricConfig(arch="deepseek-v2-lite", smoke=True, max_batch=2,
                              page_size=PAGE, num_pages=24, kv_window=2, max_seq=64,
                              obs=obs)
        with Fabric.open(config, params=params) as fab:
            uids = fab.submit_many(prompts, max_new_tokens=6)
            done = fab.drain(max_steps=100)
            outs.append([done[u].output for u in uids])
            events = [e for e in (fab.obs.events() if fab.obs else [])
                      if e[1] == "expert_route"]
            assert {e._decode_attn for e in fab.engines} == {"mla_gather"}
        if obs is None:
            assert not events
        else:
            decodes = [e for e in events if e[2] == "decode"]
            prefills = [e for e in events if e[2] == "prefill"]
            assert len(prefills) == 2 and decodes
            assert all(e[6][1] == 0 and 0 < e[6][0] <= 2 * cfg.num_experts_per_tok
                       * cfg.num_layers for e in decodes)
    assert outs[0] == outs[1]
