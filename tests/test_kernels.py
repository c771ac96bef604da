"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ref import ref_claim, ref_flash_attention, ref_paged_attention

KEY = jax.random.PRNGKey(7)


@pytest.mark.parametrize("B,H,KV,S,hd", [
    (1, 4, 4, 128, 32),    # MHA
    (2, 8, 2, 256, 64),    # GQA 4:1
    (1, 16, 1, 192, 64),   # MQA, ragged S
    (2, 4, 2, 100, 16),    # non-multiple of block
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, H, KV, S, hd, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, S, hd), dtype)
    k = jax.random.normal(ks[1], (B, KV, S, hd), dtype)
    v = jax.random.normal(ks[2], (B, KV, S, hd), dtype)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    ref = ref_flash_attention(q, k, v, causal=True)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [32, 128])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(KEY, 3)
    B, H, KV, S, hd = 2, 4, 2, 256, 32
    q = jax.random.normal(ks[0], (B, H, S, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, KV, S, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, KV, S, hd), jnp.float32)
    out = flash_attention(q, k, v, causal=True, sliding_window=window,
                          block_q=64, block_k=64, interpret=True)
    ref = ref_flash_attention(q, k, v, causal=True, sliding_window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_noncausal():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 4, 64, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 64, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 64, 32), jnp.float32)
    out = flash_attention(q, k, v, causal=False, block_q=32, block_k=32,
                          interpret=True)
    ref = ref_flash_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def _decode_case(H, KV, hd, page, pps, dtype, seed=0):
    """Six lanes, one per length case, each with its own pages: cached
    lengths 0 (an empty lane whose table points at stale pages, filled with
    NaN), 1, a page less one token (the new token ends the page), a whole
    page (the new token opens the next), mid-block, and the whole table (the
    new token takes its last position). Returns the kernel's inputs and
    ``pool`` with each new token written where the model writes it."""
    cached = [0, 1, page - 1, page, 3 * page + page // 2, pps * page - 1]
    B = len(cached)
    P = B * pps + 2
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, H, hd), dtype)
    kv_new = jax.random.normal(ks[1], (2, B, KV, hd), dtype)
    pool = jax.random.normal(ks[2], (2, P, KV, page, hd), dtype)
    ids = np.asarray(jax.random.permutation(ks[3], P))[:B * pps].reshape(B, pps)
    bt = jnp.asarray(ids, jnp.int32)
    cl = jnp.asarray(cached, jnp.int32)
    rows = np.asarray(bt)[np.arange(B), np.asarray(cl) // page]
    slots = np.asarray(cl) % page
    written = pool.at[:, rows, :, slots].set(jnp.moveaxis(kv_new, 1, 0))
    stale = jnp.zeros(P, bool).at[bt[0]].set(True)
    poisoned = jnp.where(stale[None, :, None, None, None], jnp.nan, pool)
    return q, kv_new, poisoned, written, bt, cl


@pytest.mark.parametrize("H,KV,hd,page,pps", [
    (16, 2, 128, 8, 40),        # GQA, a group of 8; up to 3 blocks of 16 pages
    (4, 4, 96, 16, 20),         # MHA at head_dim 96 (phi3-mini's); 8-page blocks
    (8, 2, 32, 8, 6),           # GQA 4; one block holds the whole table
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_sweep(H, KV, hd, page, pps, dtype):
    """The kernel, the oracle over the pool with each new token written,
    and the model step's gathered attention over the pool as received plus
    the new token agree."""
    import dataclasses

    from repro.configs import get_config
    from repro.serving.paged_model import _gathered_attention
    q, (kn, vn), poisoned, written, bt, cl = _decode_case(H, KV, hd, page,
                                                          pps, dtype)
    sched = ops.paged_schedule(bt, cl, page)
    out = ops.paged_attention(q, kn, vn, poisoned[:1], poisoned[1:], cl, sched,
                              sm_scale=1.0 / (hd ** 0.5))
    out = np.asarray(out, np.float32)
    ref = ref_paged_attention(q, written[0], written[1], bt, cl + 1)
    cfg = dataclasses.replace(get_config("phi3_mini", smoke=True),
                              num_heads=H, num_kv_heads=KV, head_dim=hd)
    attend = _gathered_attention(cfg, poisoned[:1], poisoned[1:], bt, cl)
    gathered = attend(q[:, None], kn[:, None], vn[:, None], None, 0)[:, 0]
    tol = 2e-5 if dtype == jnp.float32 else 4e-2
    live = slice(1, None)   # the empty lane's gathers read its stale pages
    for want in (ref, gathered):
        np.testing.assert_allclose(out[live], np.asarray(want, np.float32)[live],
                                   atol=tol, rtol=tol)
    # The empty lane attends to its new token alone, and read no page.
    rep = H // KV
    np.testing.assert_array_equal(out[0], np.tile(np.asarray(vn[0], np.float32),
                                                  (rep, 1)))


def test_paged_schedule_reads_each_live_page_once():
    """The grid visits only live blocks, and an input whose page is dead
    at a step repeats the page it held before, so the pipeline fetches each
    live page once and no page outside the live ones."""
    from repro.kernels.paged_attention import schedule
    page, pps, ppb = 16, 24, 8                 # 128 positions, 8 pages a block
    bt = jnp.arange(3 * pps, dtype=jnp.int32).reshape(3, pps) + 100
    cl = jnp.asarray([0, 13 * 16 - 3, 5 * 16], jnp.int32)   # 0, 13, 5 pages
    steps, lane, blk, pages = schedule(bt, cl, page)
    steps = int(steps)
    assert steps == 1 + 2 + 1
    assert np.asarray(lane)[:steps].tolist() == [0, 1, 1, 2]
    assert np.asarray(blk)[:steps].tolist() == [0, 0, 1, 0]
    held = np.asarray(pages).reshape(-1, ppb)[:steps]
    live = set(np.asarray(bt[1, :13]).tolist()) | set(np.asarray(bt[2, :5]).tolist())
    assert set(held.ravel().tolist()) <= live
    fetched = 0
    for t in range(steps):
        prev = held[t - 1] if t else [None] * ppb
        fetched += sum(int(a != b) for a, b in zip(held[t], prev))
    assert fetched == len(live)
    # inputs that are never live hold a live page too: no stale page of the
    # empty lane 0 is fetched even at the pipeline's first step
    steps, _, _, pages = schedule(bt, jnp.asarray([0, 9, 7], jnp.int32), page)
    held = set(np.asarray(pages).reshape(-1, ppb)[:int(steps)].ravel().tolist())
    assert held == {int(bt[1, 0]), int(bt[2, 0])}


@pytest.mark.parametrize("n,k", [(16, 1), (64, 5), (128, 16)])
def test_claim_kernel_sweep(n, k):
    rng = np.random.default_rng(n * 1000 + k)
    state = jnp.asarray(rng.choice([0, 1, 2], size=n).astype(np.int32))
    cycle = jnp.asarray(rng.permutation(n).astype(np.int32))
    ns, ids = ops.claim(state, cycle, k=k)
    rs, rids, _ = ref_claim(state, cycle, k)
    assert np.array_equal(np.asarray(ns), np.asarray(rs))
    assert np.array_equal(np.asarray(ids), np.asarray(rids))


def test_claim_kernel_empty_pool():
    state = jnp.full((32,), 2, jnp.int32)  # everything CLAIMED
    cycle = jnp.arange(32, dtype=jnp.int32)
    ns, ids = ops.claim(state, cycle, k=4)
    assert np.all(np.asarray(ids) == 32)  # all invalid
    assert np.array_equal(np.asarray(ns), np.asarray(state))


def test_model_ref_matches_pallas_attention():
    """The model's self_attention with impl='pallas' equals impl='ref'."""
    from repro.models.layers import self_attention
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 128, 8, 32), jnp.float32)  # [B,S,H,hd]
    k = jax.random.normal(ks[1], (2, 128, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, 128, 2, 32), jnp.float32)
    a = self_attention(q, k, v, impl="ref")
    b = self_attention(q, k, v, impl="pallas")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


# ---------------------------------------------------------------------------
# tiled claim kernel: pools spanning multiple grid blocks (DESIGN.md §6)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,k,block_n", [
    (300, 7, 128),    # 3 blocks, ragged tail
    (257, 4, 64),     # 5 blocks, tail of 1
    (1024, 16, 256),  # exact multiple
    (129, 3, 128),    # 2 blocks, minimal spill
])
def test_claim_kernel_tiled_matches_ref(n, k, block_n):
    rng = np.random.default_rng(n * 7 + k)
    state = jnp.asarray(rng.choice([0, 1, 2], size=n).astype(np.int32))
    cycle = jnp.asarray(rng.permutation(n).astype(np.int32))
    ns, ids = ops.claim(state, cycle, k=k, block_n=block_n)
    rs, rids, _ = ref_claim(state, cycle, k)
    assert np.array_equal(np.asarray(ns), np.asarray(rs))
    assert np.array_equal(np.asarray(ids), np.asarray(rids))


@pytest.mark.parametrize("n,k", [(384, 5), (500, 9)])
def test_claim_kernel_tiled_matches_fused(n, k):
    """Tiled grid path == single-block fused path (interpret mode) on the
    same input: the cross-block merge is exact, not approximate."""
    rng = np.random.default_rng(n + k)
    state = jnp.asarray(rng.choice([0, 1, 2], size=n).astype(np.int32))
    cycle = jnp.asarray(rng.permutation(n).astype(np.int32))
    ns_t, ids_t = ops.claim(state, cycle, k=k, block_n=128)   # 3-4 blocks
    ns_f, ids_f = ops.claim(state, cycle, k=k, block_n=n)     # single block
    assert np.array_equal(np.asarray(ns_t), np.asarray(ns_f))
    assert np.array_equal(np.asarray(ids_t), np.asarray(ids_f))


def test_claim_kernel_tiled_sparse_and_empty_blocks():
    """Blocks with zero AVAILABLE slots must not contribute candidates."""
    n, k, bn = 512, 6, 128
    state = np.zeros(n, np.int32)
    state[130] = 1   # block 1
    state[400] = 1   # block 3
    cycle = np.arange(n, dtype=np.int32)
    ns, ids = ops.claim(jnp.asarray(state), jnp.asarray(cycle), k=k, block_n=bn)
    got = np.asarray(ids)
    assert got[0] == 130 and got[1] == 400
    assert np.all(got[2:] == n)  # only two claimable slots exist
    assert np.asarray(ns)[130] == 2 and np.asarray(ns)[400] == 2


def test_claim_kernel_tiled_ties_break_by_lowest_id():
    """Equal cycles across different blocks: lowest slot id wins, exactly as
    lax.top_k and the fused cascade break ties."""
    n, bn = 256, 64
    state = np.ones(n, np.int32)
    cycle = np.full(n, 5, np.int32)  # all tied
    ns, ids = ops.claim(jnp.asarray(state), jnp.asarray(cycle), k=4, block_n=bn)
    assert np.asarray(ids).tolist() == [0, 1, 2, 3]


def test_slotpool_claim_dispatches_to_tiled_kernel():
    """slotpool.claim goes through kernels/ops.py for pools larger than one
    block and still claims the earliest cycles with a correct boundary."""
    from repro.core import slotpool as sp
    pool = sp.make(3000)  # > default block (2048) => tiled path
    pool, _, _ = sp.produce(pool, 12)
    pool, ids, valid = sp.claim(pool, 5)
    assert np.asarray(ids).tolist() == [0, 1, 2, 3, 4]
    assert bool(np.asarray(valid).all())
    assert int(pool.deque_cycle) == 5  # monotone max-publish of claimed cycles


# ---------------------------------------------------------------------------
# fused admission-ring step (kernels/cmp_ring.py) vs ref.ref_ring_step
# ---------------------------------------------------------------------------


def _ring_trajectory(step_fn, n, k, window, reqs):
    state = jnp.zeros((n,), jnp.int32)
    cycle = jnp.zeros((n,), jnp.int32)
    meta = jnp.zeros((2,), jnp.int32)
    outs = []
    for push_n, want in reqs:
        req = jnp.asarray([push_n, want], jnp.int32)
        state, cycle, meta, claimed = step_fn(state, cycle, meta, req)
        outs.append((np.asarray(state), np.asarray(cycle),
                     np.asarray(meta), np.asarray(claimed)))
    return outs


@pytest.mark.parametrize("n,k", [(16, 4), (32, 8), (64, 4)])
def test_ring_kernel_matches_oracle(n, k):
    """The Pallas ring kernel (interpret mode) and the jit'd oracle are
    bit-identical over random reachable trajectories — every array, every
    step: reclaim recycling, contiguous-prefix accept, ascending-cycle
    claim order and the monotone frontier."""
    from repro.kernels.cmp_ring import cmp_ring_step
    from repro.kernels.ref import ref_ring_step

    rng = np.random.default_rng(n * 31 + k)
    window = n // 4
    reqs = [(int(rng.integers(0, n)), int(rng.integers(0, k + 1)))
            for _ in range(8)]

    def pallas_step(s, c, m, r):
        return cmp_ring_step(s, c, m, r, k=k, window=window, interpret=True)

    def oracle_step(s, c, m, r):
        return ref_ring_step(s, c, m, r, k=k, window=window)

    got = _ring_trajectory(pallas_step, n, k, window, reqs)
    want = _ring_trajectory(oracle_step, n, k, window, reqs)
    for step, (g, w) in enumerate(zip(got, want)):
        for name, a, b in zip(("state", "cycle", "meta", "claimed"), g, w):
            assert (a == b).all(), (step, name, a, b)


def test_ring_kernel_recycles_and_rejects():
    """Deterministic ring-protocol checks through the public ops wrapper
    (oracle path): a full ring accepts only the contiguous FREE prefix,
    claimed slots recycle once the frontier moves a window past them, and
    claim order is always ascending cycle."""
    n, k, window = 16, 4, 4
    s = jnp.zeros((n,), jnp.int32)
    c = jnp.zeros((n,), jnp.int32)
    m = jnp.zeros((2,), jnp.int32)

    # fill the ring completely; second push must be rejected wholesale
    s, c, m, cl = ops.ring_step(s, c, m, jnp.asarray([n, 0], jnp.int32),
                                k=k, window=window, use_pallas=False)
    assert int(m[0]) == n and int((cl >= 0).sum()) == 0
    s, c, m, cl = ops.ring_step(s, c, m, jnp.asarray([5, 0], jnp.int32),
                                k=k, window=window, use_pallas=False)
    assert int(m[0]) == n, "push into a full ring must reject"

    # claim in k-chunks: ascending cycles 1..n, frontier follows the max
    seen = []
    for _ in range(n // k):
        s, c, m, cl = ops.ring_step(s, c, m, jnp.asarray([0, k], jnp.int32),
                                    k=k, window=window, use_pallas=False)
        seen += [int(x) for x in np.asarray(cl) if x >= 0]
    assert seen == list(range(1, n + 1))
    assert int(m[1]) == n

    # frontier is n: slots with cycle < n - window recycle, so a fresh push
    # accepts exactly those freed slots and no more
    s, c, m, cl = ops.ring_step(s, c, m, jnp.asarray([n, 0], jnp.int32),
                                k=k, window=window, use_pallas=False)
    accepted = int(m[0]) - n
    assert accepted == n - window - 1, accepted
