"""The real wire transport (DESIGN.md §15): binary framing, the per-host
worker processes, prefetch credit, batched reseat frames, chaos
(drop/delay/RTT) invariance over real localhost sockets, and the RTT
telemetry export path."""

import json
import os
import random
import subprocess
import sys
import time

import pytest

from repro.fabric import ClassSpec, Fabric, FabricConfig, FabricConfigError
from repro.net import (FrameDecoder, FrameError, KIND_REQ, KIND_RESP,
                       MAX_FRAME, WireTransport, pack_frame, unpack_frames)
from repro.sched import SimHostTransport, make_transport

# ---------------------------------------------------------------------------
# framing
# ---------------------------------------------------------------------------


def test_frame_pack_unpack_roundtrip():
    bodies = [{"op": "fetch", "id": 1}, {"envs": "[]", "t": []}, {}]
    data = b"".join(pack_frame(KIND_REQ if i % 2 == 0 else KIND_RESP, b)
                    for i, b in enumerate(bodies))
    out = unpack_frames(data)
    assert [b for _, b in out] == bodies
    assert [k for k, _ in out] == [KIND_REQ, KIND_RESP, KIND_REQ]


def test_frame_decoder_survives_arbitrary_chunking():
    """A TCP stream can split/coalesce frames anywhere; the incremental
    decoder must reassemble exactly the sent frame sequence."""
    rng = random.Random(0)
    bodies = [{"op": "publish", "n": i, "blob": "x" * rng.randrange(200)}
              for i in range(50)]
    data = b"".join(pack_frame(KIND_REQ, b) for b in bodies)
    for _ in range(20):
        dec = FrameDecoder()
        got = []
        i = 0
        while i < len(data):
            j = min(len(data), i + rng.randrange(1, 64))
            got.extend(dec.feed(data[i:j]))
            i = j
        assert [b for _, b in got] == bodies
        assert dec.pending == 0


def test_frame_decoder_rejects_garbage():
    with pytest.raises(FrameError, match="unknown frame kind"):
        list(FrameDecoder().feed(b"\x00\x00\x00\x02\x7f{}"))
    with pytest.raises(FrameError, match="exceeds"):
        list(FrameDecoder().feed(
            (MAX_FRAME + 1).to_bytes(4, "big") + bytes([KIND_REQ])))
    with pytest.raises(FrameError, match="undecodable frame body"):
        list(FrameDecoder().feed(pack_frame(KIND_REQ, {})[:-2] + b"!!"))
    with pytest.raises(FrameError, match="trailing"):
        unpack_frames(pack_frame(KIND_REQ, {}) + b"\x00")


# ---------------------------------------------------------------------------
# sched-only fabrics over real worker processes
# ---------------------------------------------------------------------------


def _fab(**kw):
    base = dict(classes=(ClassSpec("hi", priority=1, weight=4.0),
                         ClassSpec("lo", priority=0, weight=1.0)),
                shards_per_class=4, replicas=4, max_replicas=4,
                queue_window=4096, drain_k=6)
    base.update(kw)
    return Fabric.open(FabricConfig(**base))


def _wave(fab, per_class):
    for name in ("hi", "lo"):
        fab.submit_many([(name, i) for i in range(per_class)], qclass=name)


def _drain_streams(fab, per_class, max_rounds=50000):
    streams = {"hi": [], "lo": []}
    rounds = 0
    while sum(map(len, streams.values())) < 2 * per_class:
        rounds += 1
        assert rounds < max_rounds, "fabric did not drain"
        for v, env in fab.step():
            streams[v.name].append(env.seq)
    return streams


def _assert_exact(streams, per_class, shards=4):
    for name, seqs in streams.items():
        assert sorted(seqs) == list(range(per_class)), \
            f"{name}: lost/duplicated seats ({len(seqs)} of {per_class})"
        for s in range(shards):
            run = [q for q in seqs if q % shards == s]
            assert run == sorted(run), f"{name} run {s} reordered"


def test_wire_lossless_delivers_identically_to_local():
    """Over real sockets and worker processes, a clean wire is invisible:
    the same per-class delivery streams as the in-process transport."""
    per_class = 80
    fab_l = _fab()
    _wave(fab_l, per_class)
    local = _drain_streams(fab_l, per_class)
    fab_w = _fab(transport="wire", hosts=2)
    try:
        _wave(fab_w, per_class)
        wire = _drain_streams(fab_w, per_class)
        ts = fab_w.stats_view().transport
    finally:
        fab_w.close(final_checkpoint=False)
    assert wire == local
    _assert_exact(wire, per_class)
    assert ts["kind"] == "wire" and ts["remote_bytes"] > 0


def test_wire_chaos_preserves_exact_order():
    """Dropped requests, parked fetch batches and injected RTT cost
    latency, never exactness — the ack-before-state-change rule means a
    timed-out request changed nothing and its retry is the recovery."""
    per_class = 90
    fab = _fab(transport="wire", hosts=2, replicas=3,
               transport_drop=0.25, transport_delay=0.2,
               transport_rtt_ms=0.3, transport_seed=17)
    try:
        _wave(fab, per_class)
        streams = _drain_streams(fab, per_class)
        ts = fab.stats_view().transport
    finally:
        fab.close(final_checkpoint=False)
    _assert_exact(streams, per_class)
    assert ts["drops"] > 0 or ts["delayed"] > 0, "chaos never fired"


def test_wire_credit_one_is_synchronous_and_exact():
    """credit=1 disables pipelining (the bench baseline) but changes no
    semantics."""
    per_class = 40
    fab = _fab(transport="wire", hosts=2, transport_credit=1)
    try:
        _wave(fab, per_class)
        streams = _drain_streams(fab, per_class)
        assert fab.stats_view().transport["credit"] == 1
    finally:
        fab.close(final_checkpoint=False)
    _assert_exact(streams, per_class)


def test_wire_fail_host_recovers_and_batches_reseat():
    """Losing a host mid-wave reseats its replicas' seats onto survivors
    (one batched reseat frame per surviving host) and the wave still
    drains exactly once; the dead host's worker process stays up as the
    durable substrate for the shards homed on it."""
    per_class = 60
    fab = _fab(transport="wire", hosts=2, replicas=4)
    try:
        _wave(fab, per_class)
        streams = {"hi": [], "lo": []}
        for _ in range(3):  # partial drain: leave staged + unreached seats
            for v, env in fab.step():
                streams[v.name].append(env.seq)
        assert sum(map(len, streams.values())) < 2 * per_class
        fab.fail_host(1)
        rounds = 0
        while sum(map(len, streams.values())) < 2 * per_class:
            rounds += 1
            assert rounds < 50000, "fabric did not drain after fail_host"
            for v, env in fab.step():
                streams[v.name].append(env.seq)
        ts = fab.stats_view().transport
    finally:
        fab.close(final_checkpoint=False)
    _assert_exact(streams, per_class)
    assert ts["dead_hosts"] == [1]


def test_wire_snapshot_roundtrips_to_local():
    """The frontier checkpoint format is the wire format: a snapshot taken
    over the wire transport restores on the local transport and delivers
    the remaining seats exactly."""
    per_class = 50
    fab = _fab(transport="wire", hosts=2)
    try:
        _wave(fab, per_class)
        done = {"hi": [], "lo": []}
        for _ in range(2):  # partial drain: the snapshot is a live frontier
            for v, env in fab.step():
                done[v.name].append(env.seq)
        assert sum(map(len, done.values())) < 2 * per_class
        snap = fab.snapshot()
    finally:
        fab.close(final_checkpoint=False)
    fab2 = Fabric.from_snapshot(json.loads(json.dumps(snap)))
    try:
        assert fab2.transport.kind == "wire"  # restored onto a fresh fleet
        streams = {n: list(s) for n, s in done.items()}
        rounds = 0
        while sum(map(len, streams.values())) < 2 * per_class:
            rounds += 1
            assert rounds < 50000, "restored fabric did not drain"
            for v, env in fab2.step():
                streams[v.name].append(env.seq)
    finally:
        fab2.close(final_checkpoint=False)
    _assert_exact(streams, per_class)


def test_wire_steals_route_through_claim_frames():
    """A starved replica steals a seat via one claim CAS against the
    seat's home worker; the transport counts it."""
    fab = _fab(transport="wire", hosts=2, replicas=4, drain_k=4)
    try:
        _wave(fab, 40)
        streams = _drain_streams(fab, 40)
        view = fab.stats_view()
        steals = sum(rs["steals"] for rs in view.replicas.values())
        ts = view.transport
    finally:
        fab.close(final_checkpoint=False)
    _assert_exact(streams, 40)
    if steals:  # steals are load-dependent; when they happen, they're RPC
        assert ts["remote_claims"] >= 0


def test_wire_rejects_reorder_and_add_host():
    with pytest.raises(FabricConfigError, match="reorder"):
        FabricConfig(transport="wire", hosts=2, replicas=2,
                     shards_per_class=2, transport_reorder=True)
    with pytest.raises(AssertionError):
        make_transport("wire", 2, reorder=True)
    tr = WireTransport(2)
    with pytest.raises(NotImplementedError):
        tr.add_host()
    tr.close()


def test_wire_close_is_idempotent_and_kills_workers():
    fab = _fab(transport="wire", hosts=2)
    procs = list(fab.transport._procs)
    fab.close(final_checkpoint=False)
    fab.close(final_checkpoint=False)
    for p in procs:
        assert p.poll() is not None, "worker process survived close()"


# ---------------------------------------------------------------------------
# sim RTT knob (the sim-at-RTT baseline) + config fields
# ---------------------------------------------------------------------------


def test_sim_rtt_knob_sleeps_per_op():
    tr = SimHostTransport(2, rtt=0.01)
    assert tr.spec()["rtt_ms"] == pytest.approx(10.0)
    with pytest.raises(AssertionError):
        SimHostTransport(2, rtt=-0.1)
    assert make_transport("sim", 2, rtt_ms=2.5).rtt == pytest.approx(0.0025)
    # end-to-end: an rtt'd sim fabric still drains exactly, just slower
    t0 = time.perf_counter()
    fab = _fab(transport="sim", hosts=2, transport_rtt_ms=1.0)
    assert fab.transport.rtt == pytest.approx(0.001)
    _wave(fab, 12)
    streams = _drain_streams(fab, 12)
    _assert_exact(streams, 12)
    assert time.perf_counter() - t0 > 0.001  # the injected RTT was paid


def test_config_roundtrips_new_transport_fields():
    cfg = FabricConfig(transport="wire", hosts=2, replicas=2,
                       shards_per_class=2, transport_rtt_ms=0.5,
                       transport_credit=8)
    back = FabricConfig.from_json(json.loads(json.dumps(cfg.to_json())))
    assert back == cfg
    assert back.transport_rtt_ms == 0.5 and back.transport_credit == 8
    with pytest.raises(FabricConfigError, match="transport_rtt_ms"):
        FabricConfig(transport="sim", hosts=2, replicas=2,
                     shards_per_class=2, transport_rtt_ms=-1.0)
    with pytest.raises(FabricConfigError, match="transport_credit"):
        FabricConfig(transport="wire", hosts=2, replicas=2,
                     shards_per_class=2, transport_credit=0)
    with pytest.raises(FabricConfigError, match="rtt"):
        FabricConfig(transport_rtt_ms=3.0)  # local transport has no wire


# ---------------------------------------------------------------------------
# RTT telemetry export
# ---------------------------------------------------------------------------


def test_rtt_percentiles_export_to_stats_and_prometheus():
    from repro.obs import ObsConfig, prometheus_text
    fab = _fab(transport="wire", hosts=2, obs=ObsConfig(trace_rate=0.0))
    try:
        _wave(fab, 40)
        _drain_streams(fab, 40)
        view = fab.stats_view()
    finally:
        fab.close(final_checkpoint=False)
    rtt = view.transport.get("rtt_ms")
    assert rtt, "no per-host RTT percentiles in the transport section"
    for host, pct in rtt.items():
        assert set(pct) >= {"p50", "p99", "count"} and pct["count"] > 0
        assert pct["p99"] >= pct["p50"] >= 0.0
    text = prometheus_text(view)
    assert 'repro_transport_rtt_ms{host="' in text
    assert 'quantile="p99"' in text
    assert 'repro_transport_rtt_count{host="' in text
    for line in text.splitlines():
        if line and not line.startswith("#"):
            assert line.startswith("repro_")
            float(line.rsplit(" ", 1)[1])


def test_wire_worker_imports_no_jax():
    """Wire-transport host workers run ``repro.net.server`` in their own
    processes; importing it must not load JAX, or a worker could try to
    take the accelerator that the serving process holds."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = ("import repro.net.server; import sys; assert not any("
            "m == 'jax' or m.startswith('jax.') for m in sys.modules)")
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
