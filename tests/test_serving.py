"""Serving engine: paged decode correctness, FIFO admission, preemption
recovery via the CMP window, page-pool accounting."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import decode_step, init_cache, init_params, prefill
from repro.serving.engine import Engine

KEY = jax.random.PRNGKey(0)


def _ref_generate(cfg, params, prompt, n):
    cache = init_cache(cfg, 1, 256)
    lg, cache = prefill(params, jnp.asarray([prompt], jnp.int32), cfg, cache)
    out = [int(jnp.argmax(lg[0]))]
    for _ in range(n - 1):
        lg, cache = decode_step(params, jnp.asarray([[out[-1]]], jnp.int32), cfg, cache)
        out.append(int(jnp.argmax(lg[0])))
    return out


@pytest.fixture(scope="module")
def dense_model():
    cfg = get_config("yi_6b", smoke=True)
    return cfg, init_params(cfg, KEY)


def test_engine_matches_reference(dense_model):
    cfg, params = dense_model
    eng = Engine(cfg, params, max_batch=2, page_size=8, num_pages=32,
                 window=2, max_seq=64)
    prompts = [[5, 17, 200, 3], [9, 9, 42], [100, 2, 7, 7, 1], [11] * 9]
    uids = [eng.submit(p, max_new_tokens=5) for p in prompts]
    done = eng.run_until_idle()
    for p, u in zip(prompts, uids):
        assert done[u].output == _ref_generate(cfg, params, p, 5)


def test_engine_moe(dense_model):
    cfg = get_config("granite_moe", smoke=True)
    params = init_params(cfg, KEY)
    eng = Engine(cfg, params, max_batch=2, page_size=8, num_pages=16,
                 window=2, max_seq=32)
    u = eng.submit([3, 1, 4, 1, 5], max_new_tokens=4)
    done = eng.run_until_idle()
    assert done[u].output == _ref_generate(cfg, params, [3, 1, 4, 1, 5], 4)


def test_fifo_admission_order(dense_model):
    cfg, params = dense_model
    eng = Engine(cfg, params, max_batch=1, page_size=8, num_pages=32,
                 window=1, max_seq=32)
    uids = [eng.submit([i + 1, i + 2], max_new_tokens=2) for i in range(5)]
    completion_order = []
    seen = set()
    for _ in range(200):
        eng.step()
        for u in eng.completed:
            if u not in seen:
                seen.add(u)
                completion_order.append(u)
        if len(seen) == 5:
            break
    assert completion_order == uids  # strict FIFO service with max_batch=1


def test_preemption_recovers_and_completes(dense_model):
    """Pool too small for all requests: engine preempts, pages recycle after
    the window, everything still completes with correct outputs."""
    cfg, params = dense_model
    eng = Engine(cfg, params, max_batch=3, page_size=4, num_pages=10,
                 window=2, max_seq=24)
    prompts = [[5, 17, 200, 3], [9, 9, 42], [100, 2, 7, 7, 1]]
    uids = [eng.submit(p, max_new_tokens=6) for p in prompts]
    done = eng.run_until_idle(max_steps=400)
    assert set(done) >= set(uids), "not all requests completed"
    for p, u in zip(prompts, uids):
        assert done[u].output == _ref_generate(cfg, params, p, 6)


def test_pages_recycle_after_window(dense_model):
    cfg, params = dense_model
    eng = Engine(cfg, params, max_batch=2, page_size=8, num_pages=16,
                 window=3, max_seq=32)
    eng.submit([1, 2, 3], max_new_tokens=3)
    eng.run_until_idle()
    used_after_done = eng.pool.free_pages()
    for _ in range(eng.pool.window + 2):
        eng.step()
    # all pages except the reserved scratch page are FREE again
    assert eng.pool.free_pages() == eng.pool.num_pages - 1
    assert eng.pool.free_pages() >= used_after_done


def test_engine_rejects_ssm_archs():
    cfg = get_config("xlstm_125m", smoke=True)
    params = init_params(cfg, KEY)
    with pytest.raises(AssertionError):
        Engine(cfg, params)


def test_concurrent_submitters_strict_fifo(dense_model):
    """The admission queue is the paper's queue: multiple submitter threads,
    strict global FIFO service order (max_batch=1 makes order observable)."""
    import threading
    import time

    cfg, params = dense_model
    eng = Engine(cfg, params, max_batch=1, page_size=8, num_pages=32,
                 window=2, max_seq=32)
    submitted = []
    lock = threading.Lock()

    def submitter(tid):
        for i in range(3):
            with lock:  # serialize just the uid recording, not the queue
                uid = eng.submit([tid * 10 + i + 1, 2, 3], max_new_tokens=2)
                submitted.append(uid)
            time.sleep(0.001)

    ts = [threading.Thread(target=submitter, args=(t,)) for t in range(3)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    completion = []
    seen = set()
    for _ in range(400):
        eng.step()
        for u in eng.completed:
            if u not in seen:
                seen.add(u)
                completion.append(u)
        if len(seen) == len(submitted):
            break
    # service order == global arrival order across submitter threads
    assert completion == submitted


def test_class_aware_preemption_evicts_lowest_class_first(dense_model):
    """Under pool exhaustion the engine preempts the lowest class first, and
    the preempted request re-enters *its own* class queue at its original
    cycle (served before anything younger in that class)."""
    from repro.sched import QueueClass

    cfg, params = dense_model
    eng = Engine(cfg, params, max_batch=2, page_size=4, num_pages=7,
                 window=2, max_seq=24,
                 classes=[QueueClass("background", priority=0),
                          QueueClass("interactive", priority=2)],
                 policy="strict")
    # Fill both lanes with background work (3 pages each incl. growth room).
    bg = [eng.submit([1, 2, 3, 4, 5], max_new_tokens=8, qclass="background")
          for _ in range(2)]
    eng.step()
    assert all(r is not None for r in eng.active)
    # Interactive arrival under a dry pool must evict a background lane...
    hi = eng.submit([9, 9, 9, 9], max_new_tokens=2, qclass="interactive")
    eng.step()
    admitted = {r.uid for r in eng.active if r is not None} | set(eng.completed)
    assert hi in admitted, "interactive was not admitted"
    done = eng.run_until_idle(max_steps=400)
    assert set(done) >= {hi, *bg}
    # ...and the victim was a background request, never the interactive one.
    assert done[hi].preemptions == 0
    assert sum(done[u].preemptions for u in bg) >= 1
    # outputs stay correct through evict -> requeue -> re-prefill
    assert done[hi].output == _ref_generate(cfg, params, [9, 9, 9, 9], 2)
    snap = eng.class_stats()
    assert snap["background"]["requeued"] >= 1
    assert snap["interactive"]["requeued"] == 0


def test_preempted_request_keeps_class_fifo_seat(dense_model):
    """Same-class preemption: the victim is the *youngest* class cycle, and
    on requeue it is re-served before every later submission of its class —
    FIFO position by original cycle, not by preemption time."""
    from repro.sched import QueueClass

    cfg, params = dense_model
    eng = Engine(cfg, params, max_batch=1, page_size=4, num_pages=4,
                 window=1, max_seq=16,
                 classes=[QueueClass("default", priority=0)])
    uids = [eng.submit([i + 1, i + 2], max_new_tokens=2) for i in range(4)]
    completion = []
    seen = set()
    for _ in range(300):
        eng.step()
        for u in eng.completed:
            if u not in seen:
                seen.add(u)
                completion.append(u)
        if len(seen) == 4:
            break
    # strict within-class FIFO end to end, preemptions or not
    assert completion == uids


def test_priority_inversion_never_happens(dense_model):
    """A lower class arriving later can never evict a higher-class lane."""
    from repro.sched import QueueClass

    cfg, params = dense_model
    eng = Engine(cfg, params, max_batch=2, page_size=4, num_pages=7,
                 window=2, max_seq=24,
                 classes=[QueueClass("lo", priority=0),
                          QueueClass("hi", priority=1)])
    hi = [eng.submit([5, 6, 7, 8], max_new_tokens=6, qclass="hi")
          for _ in range(2)]
    eng.step()
    lo = eng.submit([1, 2, 3], max_new_tokens=2, qclass="lo")
    done = eng.run_until_idle(max_steps=400)
    assert set(done) >= {lo, *hi}
    for u in hi:
        assert done[u].preemptions == 0, "higher class was evicted by lower"


def test_growth_starved_lane_self_evicts_not_corrupts(dense_model):
    """max_batch=1: when page growth fails (the previous request's retired
    pages are still inside the protection window) and there is nobody less
    entitled to evict, the growing lane preempts *itself* (clean requeue at
    its cycle seat) instead of decoding into the scratch page — outputs must
    still match the reference exactly."""
    cfg, params = dense_model
    # 3 usable pages (1 reserved scratch). Request A completes holding 2
    # pages, which stay window-protected for W=2 steps; request B admits on
    # the 1 remaining page, then its first growth finds the pool dry with
    # itself as the only (least-entitled) lane.
    eng = Engine(cfg, params, max_batch=1, page_size=4, num_pages=4,
                 window=2, max_seq=12)
    prompts = [[5, 17, 200, 3], [9, 9, 42, 7]]
    uids = [eng.submit(p, max_new_tokens=4) for p in prompts]
    done = eng.run_until_idle(max_steps=400)
    assert set(done) >= set(uids)
    assert done[uids[1]].preemptions >= 1, \
        "starved lane was never self-evicted"
    for p, u in zip(prompts, uids):
        assert done[u].output == _ref_generate(cfg, params, p, 4)


def test_admission_window_backpressure_on_engine(dense_model):
    """A class with a finite admit_window rejects the overflow (submit
    returns None) instead of growing without bound, and recovers once the
    backlog drains."""
    from repro.sched import QueueClass

    cfg, params = dense_model
    eng = Engine(cfg, params, max_batch=2, page_size=8, num_pages=32,
                 window=2, max_seq=32,
                 classes=[QueueClass("default", admit_window=4)])
    uids = [eng.submit([i + 1, 2], max_new_tokens=2) for i in range(6)]
    assert sum(u is not None for u in uids) == 4
    assert uids[4] is None and uids[5] is None
    done = eng.run_until_idle(max_steps=200)
    assert set(done) == {u for u in uids if u is not None}
    assert eng.pending == 0
    assert eng.submit([7, 7], max_new_tokens=2) is not None  # window freed


def test_overload_burst_drains_pending_counter(dense_model):
    """Batched admission under a pool too small for the burst: every request
    still completes AND the pending counter drains to exactly zero (the
    park-at-backlog path must not double-count)."""
    cfg, params = dense_model
    eng = Engine(cfg, params, max_batch=3, page_size=4, num_pages=8,
                 window=2, max_seq=16)
    uids = eng.submit_many([[i + 1, i + 2] for i in range(7)],
                           max_new_tokens=3)
    done = eng.run_until_idle(max_steps=300)
    assert set(done) >= set(uids)
    assert eng.pending == 0
    assert all(r is None for r in eng.active)
    # idle detection must actually fire (pending leak would burn max_steps)
    before = eng.step_count
    eng.run_until_idle(max_steps=50)
    assert eng.step_count == before + 1  # one probe step, then idle exit


def test_engine_replica_group_serves_and_recovers(dense_model):
    """DESIGN.md §9 end to end: 2 engine replicas (partitioned lane+page
    budgets, shared compiled forward) serve a 2-class wave; a mid-wave
    exact-seat checkpoint restores into a fresh group and every admitted
    request is served exactly once across the crash."""
    from repro.sched import QueueClass
    from repro.serving.engine import EngineReplicaGroup

    cfg, params = dense_model

    def classes():
        return [QueueClass("hi", priority=1, weight=4.0, num_shards=2,
                           window=64, reclaim_period=32),
                QueueClass("lo", priority=0, weight=1.0, num_shards=2,
                           window=64, reclaim_period=32)]

    grp = EngineReplicaGroup(cfg, params, num_replicas=2, max_batch=4,
                             page_size=8, num_pages=32, window=2, max_seq=64,
                             classes=classes())
    uids = [grp.submit([i + 1, 2, 3], max_new_tokens=3, qclass="hi")
            for i in range(3)]
    uids += grp.submit_many([[9, 9 + i] for i in range(3)],
                            max_new_tokens=3, qclass="lo")
    done = grp.run_until_idle(max_steps=200)
    assert all(u in done for u in uids)
    assert grp.idle()
    # each replica really owns a partitioned budget
    assert [e.max_batch for e in grp.engines] == [2, 2]
    assert sum(e.pool.num_pages for e in grp.engines) == 32

    # ---- checkpoint mid-wave, crash the group, restore, finish ----
    grp2 = EngineReplicaGroup(cfg, params, num_replicas=2, max_batch=4,
                              page_size=8, num_pages=32, window=2,
                              max_seq=64, classes=classes(),
                              forward_fn=grp._fwd)
    wave = []
    for i in range(4):
        wave.append(grp2.submit([5 + i, 1], max_new_tokens=3, qclass="hi"))
        wave.append(grp2.submit([7 + i, 2], max_new_tokens=3, qclass="lo"))
    grp2.step()
    grp2.step()
    import json
    state = json.loads(json.dumps(grp2.sched_state()))
    done_before = dict(grp2.completed)
    del grp2  # crash: laned requests and staged claims die with the group
    grp3 = EngineReplicaGroup.from_sched_state(
        cfg, params, state, max_batch=4, page_size=8, num_pages=32,
        max_seq=64, forward_fn=grp._fwd)
    done_after = grp3.run_until_idle(max_steps=300)
    assert not (set(done_before) & set(done_after)), "served twice"
    assert set(done_before) | set(done_after) >= set(wave), "lost a tenant"
    # uid continuity: new submissions never collide with pre-crash uids
    assert grp3.submit([3, 3], max_new_tokens=2, qclass="hi") not in wave


# ---------------------------------------------------------------------------
# device-resident admission (serving/admission.py, DESIGN.md §12)
# ---------------------------------------------------------------------------


def test_device_admission_ring_fifo_and_lookahead():
    from repro.serving.admission import DeviceAdmissionRing

    ring = DeviceAdmissionRing(k=4, claim_block=16)
    entries = [("q", i) for i in range(40)]
    out = []
    i = 0
    while len(out) < 40:
        push, i = entries[i:i + 8], min(i + 8, 40)
        claimed, rejected = ring.step(push, 4)
        assert not rejected
        out.extend(claimed)
    assert out == entries, "ring admission reordered the FIFO"
    # look-ahead actually amortized: far fewer kernel calls than steps
    assert ring.stats["kernel_calls"] < ring.stats["steps"]
    assert ring.pending == 0


def test_device_admission_ring_flush_is_exact_and_reusable():
    from repro.serving.admission import DeviceAdmissionRing

    ring = DeviceAdmissionRing(k=2, claim_block=8)
    entries = [("q", i) for i in range(20)]
    claimed, _ = ring.step(entries, 2)
    assert claimed == entries[:2]
    # flush returns the rest: claim-buffered first, then unclaimed, in
    # exact cycle (submission) order
    assert ring.flush() == entries[2:]
    assert ring.pending == 0 and ring.flush() == []
    # ring survives the flush: cycles stay monotone, admission continues
    more = [("q", i) for i in range(20, 30)]
    claimed, rejected = ring.step(more, 2)
    assert not rejected
    while len(claimed) < 10:
        got, rejected = ring.step([], 2)
        assert got and not rejected
        claimed.extend(got)
    assert claimed == more


def test_device_admission_ring_rejects_past_capacity():
    from repro.serving.admission import DeviceAdmissionRing

    ring = DeviceAdmissionRing(k=2, claim_block=2, capacity=8, window=2)
    entries = [("q", i) for i in range(12)]
    claimed, rejected = ring.step(entries, 0)
    assert claimed == []
    # contiguous-prefix accept: whatever fits stays FIFO, the suffix comes
    # back for the host to requeue — nothing is dropped
    assert claimed == [] and entries == entries[:12 - len(rejected)] + rejected
    assert ring.pending + len(rejected) == 12


def test_engine_device_admission_matches_host(dense_model):
    """The ISSUE 6 exactness bar: admission routed through the device ring
    serves the same requests to the same outputs as the host path."""
    cfg, params = dense_model
    outs = {}
    for device_admission in (False, True):
        eng = Engine(cfg, params, max_batch=2, page_size=8, num_pages=32,
                     window=2, max_seq=64, device_admission=device_admission)
        prompts = [[5, 17, 200, 3], [9, 9, 42], [100, 2, 7], [11] * 5]
        uids = [eng.submit(p, max_new_tokens=4) for p in prompts]
        done = eng.run_until_idle()
        outs[device_admission] = [done[u].output for u in uids]
        if device_admission:
            assert eng._dev_admit.stats["kernel_calls"] > 0, \
                "ring path never exercised"
            assert eng.ring_pending == 0
    assert outs[True] == outs[False]


def _serve_through_fabric(cfg, params, prompts):
    from repro.fabric import Fabric, FabricConfig
    config = FabricConfig(arch=cfg.name, smoke=True, max_batch=3, page_size=8,
                          num_pages=48, kv_window=2, max_seq=64)
    with Fabric.open(config, params=params, model_cfg=cfg) as fab:
        uids = fab.submit_many(prompts, max_new_tokens=9)
        done = fab.drain(max_steps=200)
        attn = {e._decode_attn for e in fab.engines}
    return [done[u].output for u in uids], attn


@pytest.mark.parametrize("arch", [
    "phi3_mini",   # MHA, head_dim 16: each layer's pool slice
    "gqa_hd128",   # a group of 4 at head_dim 128: the pool read in place
])
def test_decode_kernel_serves_the_gather_paths_tokens(monkeypatch, arch):
    """The paged decode kernel (interpret mode here) serves token for token
    what the whole-table gather serves: prompts that end on, before and
    after page boundaries, lanes refilled as requests finish."""
    import dataclasses

    from repro.serving import paged_model
    cfg = get_config("phi3_mini", smoke=True)
    if arch == "gqa_hd128":
        cfg = dataclasses.replace(cfg, name="gqa-hd128-smoke", num_heads=8,
                                  num_kv_heads=2, head_dim=128)
    params = init_params(cfg, KEY)
    prompts = [[5, 17, 200, 3, 9, 9, 42, 1], [9, 9, 42], [100, 2, 7, 7, 1],
               [11] * 15, [3, 1, 4, 1, 5, 9, 2, 6, 5]]
    gathered, attn = _serve_through_fabric(cfg, params, prompts)
    assert attn == {"gather"}
    monkeypatch.setattr(paged_model, "kernel_attention",
                        lambda S, c: S == 1 and c.attn_softcap == 0.0)
    served, attn = _serve_through_fabric(cfg, params, prompts)
    assert attn == {"kernel"}
    assert served == gathered
    assert len({tuple(o) for o in served}) == len(prompts)


def test_decode_kernel_dispatch_follows_the_call_shape(monkeypatch):
    """Only a decode call (one token per lane) without a logit softcap takes
    the kernel, and only on a TPU; prefill and softcapped models gather."""
    import dataclasses

    from repro.kernels import ops
    from repro.serving import paged_model
    cfg = get_config("yi_6b", smoke=True)
    assert not paged_model.kernel_attention(1, cfg)        # this host: CPU
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    assert paged_model.kernel_attention(1, cfg)
    assert not paged_model.kernel_attention(7, cfg)        # prefill
    assert not paged_model.kernel_attention(
        1, dataclasses.replace(cfg, attn_softcap=30.0))
