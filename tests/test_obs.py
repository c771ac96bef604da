"""Observability plane (DESIGN.md §13): flight recorder, gauges, exporters,
and the MetricsHub wired through the Fabric session."""

import json

import pytest

from repro.obs import (CONTROL_EVENTS, LIFECYCLE_STAGES, PRODUCER_RID,
                       FlightRecorder, MetricsHub, ObsConfig,
                       format_class_lines, perfetto_trace, prometheus_text,
                       sample_stride, stage_breakdown)
from repro.sched import QueueClass


# ---------------------------------------------------------------------------
# recorder
# ---------------------------------------------------------------------------


def test_sample_stride_maps_rate_to_every_n():
    assert sample_stride(1.0) == 1
    assert sample_stride(0.5) == 2
    assert sample_stride(0.01) == 100
    assert sample_stride(0.0) == 0  # lifecycle tracing off


def test_recorder_sampling_is_deterministic_in_seq():
    rec = FlightRecorder(ObsConfig(ring_capacity=16, trace_rate=0.25))
    picked = [seq for seq in range(40) if rec.sampled(seq)]
    assert picked == [0, 4, 8, 12, 16, 20, 24, 28, 32, 36]
    off = FlightRecorder(ObsConfig(ring_capacity=16, trace_rate=0.0))
    assert not any(off.sampled(seq) for seq in range(40))


def test_recorder_ring_wraps_and_counts():
    rec = FlightRecorder(ObsConfig(ring_capacity=4, trace_rate=1.0),
                         host=1, rid=3)
    for seq in range(10):
        rec.emit("submit", "cls", seq)
    evs = rec.events()
    assert len(evs) == 4  # bounded ring: only the newest survive
    assert [e[3] for e in evs] == [6, 7, 8, 9]  # append order preserved
    snap = rec.snapshot()
    assert snap["dropped"] == 6
    assert snap["counts"]["submit"] == 10  # counts are totals, not retained
    assert snap["rid"] == 3 and snap["host"] == 1


def test_obs_config_validation():
    ObsConfig().validate()
    with pytest.raises(ValueError):
        ObsConfig(trace_rate=1.5).validate()
    with pytest.raises(ValueError):
        ObsConfig(ring_capacity=0).validate()
    with pytest.raises(ValueError):
        ObsConfig(sample_every_n_steps=0).validate()


# ---------------------------------------------------------------------------
# class-level emit sites
# ---------------------------------------------------------------------------


def _traced_class(**kw):
    qc = QueueClass("t", num_shards=2, **kw)
    qc._obs = FlightRecorder(ObsConfig(ring_capacity=1024, trace_rate=1.0))
    return qc


def test_queue_class_emits_producer_and_drain_stages():
    qc = _traced_class()
    qc.submit_many(list(range(8)))
    qc.submit(99)
    qc.drain(9)
    stages = {e[1] for e in qc._obs.events()}
    assert {"submit", "window_admit", "shard_enqueue",
            "drain", "seat"} <= stages
    # one submit event per envelope at trace_rate=1.0
    assert qc._obs.snapshot()["counts"]["submit"] == 9


def test_queue_class_emits_requeue_event():
    qc = _traced_class()
    qc.submit(0)
    [env] = qc.drain(1)
    qc.requeue(env)
    assert any(e[1] == "requeue" and e[3] == env.seq
               for e in qc._obs.events())


def test_partial_sampling_traces_the_stride_subset():
    qc = _traced_class()
    qc._obs = FlightRecorder(ObsConfig(ring_capacity=1024, trace_rate=0.25))
    qc.submit_many(list(range(20)))
    qc.drain(20)
    submit_seqs = sorted(e[3] for e in qc._obs.events()
                         if e[1] == "submit")
    assert submit_seqs == [0, 4, 8, 12, 16]
    drain_seqs = sorted(e[3] for e in qc._obs.events() if e[1] == "drain")
    assert drain_seqs == [0, 4, 8, 12, 16]


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def _lifecycle_events():
    qc = _traced_class()
    qc.submit_many(list(range(6)))
    qc.drain(6)
    return qc._obs.events()


def test_perfetto_trace_structure(tmp_path):
    path = str(tmp_path / "trace.json")
    trace = perfetto_trace(_lifecycle_events(), path=path)
    reloaded = json.load(open(path))
    assert reloaded == trace
    assert trace["displayTimeUnit"] == "ms"
    slices = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert slices, "no complete slices emitted"
    for ev in slices:
        assert ev["dur"] >= 0.0 and ev["ts"] >= 0.0
        assert set(ev) >= {"name", "cat", "pid", "tid", "args"}
        assert ev["name"] in LIFECYCLE_STAGES


def test_perfetto_control_events_are_instants():
    rec = FlightRecorder(ObsConfig(ring_capacity=16, trace_rate=1.0))
    rec.emit("steal", "t", -1, arg={"shard": 1})
    trace = perfetto_trace(rec.events())
    [inst] = trace["traceEvents"]
    assert inst["ph"] == "i" and inst["name"] == "steal"
    assert inst["name"] in CONTROL_EVENTS


def test_stage_breakdown_covers_adjacent_pairs():
    bd = stage_breakdown(_lifecycle_events())
    assert set(bd) == {"submit->window_admit",
                       "window_admit->shard_enqueue",
                       "shard_enqueue->drain", "drain->seat"}
    for row in bd.values():
        assert row["n"] == 6
        assert row["p99_ms"] >= row["p50_ms"] >= 0.0


def _parse_prometheus(text):
    """Minimal exposition-format parser: returns {metric: type} and sample
    count, raising on format violations (non-contiguous families,
    duplicate samples, malformed lines)."""
    types, samples, seen = {}, 0, set()
    current = None
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# TYPE "):
            _, _, name, typ = line.split(" ")
            assert name not in types, f"family {name} split into two groups"
            types[name] = typ
            current = name
        elif line.startswith("#"):
            continue
        else:
            ident, value = line.rsplit(" ", 1)
            float(value)
            assert ident.split("{")[0] == current, f"stray sample {ident}"
            assert ident not in seen, f"duplicate sample {ident}"
            seen.add(ident)
            samples += 1
    return types, samples


def test_prometheus_text_is_well_formed():
    from repro.fabric import Fabric, FabricConfig
    fab = Fabric.open(FabricConfig(replicas=2, obs=ObsConfig(trace_rate=1.0)))
    fab.submit_many(list(range(30)))
    fab.drain()
    hub = fab.obs
    hub.sample(fab.replica_set, fab.engines)
    gauges = hub.window()[-1][1]
    text = prometheus_text(fab.stats_view(), gauges=gauges)
    types, samples = _parse_prometheus(text)
    assert samples > 20
    assert types["repro_class_submitted"] == "counter"
    assert types["repro_class_pending"] == "gauge"
    assert types["repro_obs_events_total"] == "counter"
    assert "repro_obs_events_dropped" in types


def test_format_class_lines_handles_missing_latency():
    from repro.fabric import Fabric, FabricConfig
    fab = Fabric.open(FabricConfig())
    lines = format_class_lines(fab.stats_view())
    assert len(lines) == 1 and "p50_ms=-" in lines[0]
    fab.submit_many(list(range(4)))
    fab.drain()
    [line] = format_class_lines(fab.stats_view())
    assert "submitted=4" in line and "delivered=4" in line


# ---------------------------------------------------------------------------
# hub + fabric wiring
# ---------------------------------------------------------------------------


def test_hub_attach_traces_scheduler_fabric_end_to_end():
    from repro.fabric import Fabric, FabricConfig
    cfg = FabricConfig(replicas=2,
                       obs=ObsConfig(trace_rate=1.0, sample_every_n_steps=1))
    fab = Fabric.open(cfg)
    fab.submit_many(list(range(40)))
    deliveries = fab.drain()
    assert len(deliveries) == 40
    hub = fab.obs
    evs = hub.events()
    assert {"submit", "window_admit", "shard_enqueue",
            "drain", "seat"} <= {e[1] for e in evs}
    # merged stream is time-sorted across all rings
    assert all(a[0] <= b[0] for a, b in zip(evs, evs[1:]))
    snap = fab.stats_view().obs
    assert snap["trace_rate"] == 1.0
    assert sum(snap["events_total"].values()) >= 5 * 40
    assert snap["window"]["samples"] >= 1  # cadenced gauge sweeps ran
    gauges = snap["gauges"]
    assert "default" in gauges["classes"]
    occ = gauges["classes"]["default"]
    assert occ["occupancy_frac_max"] >= 0.0
    assert gauges["pending"] == 0


def test_hub_survives_resize_reattach():
    from repro.fabric import Fabric, FabricConfig
    cfg = FabricConfig(replicas=1, max_replicas=3,
                       obs=ObsConfig(trace_rate=1.0))
    fab = Fabric.open(cfg)
    fab.submit_many(list(range(10)))
    fab.drain()
    before = len(fab.obs.events())
    fab.resize(3)
    fab.submit_many(list(range(10, 30)))
    fab.drain()
    evs = fab.obs.events()
    assert len(evs) > before  # new replicas' views re-attached and emitting
    seat_seqs = sorted(e[3] for e in evs if e[1] == "seat")
    assert seat_seqs == list(range(30))  # no envelope lost to the resize


def test_hub_rolling_window_evicts_by_age():
    hub = MetricsHub(ObsConfig(metrics_window_s=1e-7))
    from repro.fabric import Fabric, FabricConfig
    fab = Fabric.open(FabricConfig())
    for _ in range(5):
        hub.sample(fab.replica_set, [])
    # span 0s: every sweep but the newest is already outside the window
    assert len(hub.window()) == 1
    assert hub.snapshot()["window"]["taken"] == 5


def test_hub_rtt_histograms():
    hub = MetricsHub(ObsConfig())
    for ms in (1.0, 2.0, 3.0, 4.0):
        hub.record_rtt(1, ms / 1e3)
    snap = hub.snapshot()["rtt_ms"]
    assert snap[1]["count"] == 4
    assert snap[1]["p50"] == pytest.approx(2.5)


def test_transport_rtt_reaches_hub():
    """Remote publishes (the steal-victim move) report RTT through the
    attached hub; home-aligned local ops do not."""
    from repro.sched import (HostAddr, QueueClass, ReplicaSet, Scheduler,
                             SimHostTransport)
    qc = QueueClass("t", num_shards=2)
    transport = SimHostTransport(2)
    rs = ReplicaSet(Scheduler([qc]), 2, transport=transport)
    hub = MetricsHub(ObsConfig())
    hub.attach(rs)
    qc.submit_many(list(range(4)))
    envs = [env for _, env in rs.replicas[0].drain(4)]
    # shard 1's home is host 1; publishing from host 0 is a remote op
    transport.publish("t", 1, envs[:1], HostAddr(0, 0))
    assert hub.snapshot()["rtt_ms"].get(0, {}).get("count", 0) >= 1


def test_device_admission_ring_control_events():
    from repro.serving.admission import DeviceAdmissionRing
    ring = DeviceAdmissionRing(k=2, claim_block=4)
    ring._obs = FlightRecorder(ObsConfig(ring_capacity=64, trace_rate=1.0))
    claimed, rejected = ring.step(["a", "b", "c"], want=2)
    assert claimed == ["a", "b"] and rejected == []
    leftover = ring.flush()
    assert leftover == ["c"]
    stages = [e[1] for e in ring._obs.events()]
    assert "claim_block" in stages and "flush" in stages


def test_fabric_config_obs_json_round_trip():
    from repro.fabric import FabricConfig, FabricConfigError
    cfg = FabricConfig(obs=ObsConfig(trace_rate=0.5, ring_capacity=128))
    again = FabricConfig.from_json(cfg.to_json())
    assert again == cfg
    assert isinstance(again.obs, ObsConfig)
    with pytest.raises(FabricConfigError):
        FabricConfig(obs=ObsConfig(trace_rate=7.0))
