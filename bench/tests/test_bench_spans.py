"""The program-span reduction (``bench/spans.py``) and its metric readers, on
synthetic events, on a trace recorded around a tiny fabric on the CPU, and
through a whole traced run; and the guard that the benchmark's existing
reduction and readers read what they read before."""

import importlib
import json
from pathlib import Path

import pytest

from bench import run, spans, trace
from bench.loop import Step
from bench.tests import tiny
from bench.tests.test_bench_metrics import record, track

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"
SAMPLE = TESTDATA / "trace_sample.xplane.pb"
BENCH = json.loads((Path(__file__).resolve().parents[2] / "BENCHMARK.json").read_text())


def read(name, rec):
    return importlib.import_module(f"bench.metrics.{name}").read(rec)


def span(a, b, name, thread="python", **args):
    return (a, b, name, {"t_mono_ns": int((a - 100.0) * 1e9), **args}, thread)


def synthetic():
    """A window of 10 s holding two fabric steps (the first with one
    prefill), a collector pause on another thread, and device ops."""
    host = [(0.0, 10.0, "bench.window")]
    ops = [(0.5, 1.5, "prefill"), (2.5, 3.5, "decode"), (5.0, 6.0, "decode"),
           (-1.0, 0.2, "before")]
    program = sorted([
        span(0.0, 4.0, "fabric.step"), span(0.1, 3.8, "engine.step", rid=0),
        span(0.2, 2.0, "engine.admit"), span(0.4, 1.8, "engine.prefill", prompt_len=64),
        span(2.2, 3.6, "engine.decode"),
        span(4.5, 6.5, "fabric.step"), span(4.6, 6.4, "engine.step", rid=0),
        span(4.9, 6.1, "engine.decode"),
        span(7.0, 8.0, "host.gc", thread="worker", generation=2),
        span(-2.0, -1.0, "fabric.step"),                     # before the window
        span(9.5, 11.0, "fabric.step"),                      # cut by its end
    ], key=lambda s: (s[0], -s[1]))
    return {"host": host, "device": {"/device:TPU:0": ops}}, program


def test_spans_are_nested_clipped_and_timed_on_the_device():
    events, program = synthetic()
    red = spans.reduce(events, program)
    out = red["spans"]
    assert [s["name"] for s in out][:4] == ["fabric.step", "engine.step", "engine.admit",
                                            "engine.prefill"]
    names = [s["name"] for s in out]
    assert names.count("fabric.step") == 3 and len(out) == len(program) - 1
    chain, k = [], names.index("engine.prefill")
    while k is not None:
        chain.append(out[k]["name"])
        k = out[k]["parent"]
    assert chain == ["engine.prefill", "engine.admit", "engine.step", "fabric.step"]
    prefill = out[names.index("engine.prefill")]
    assert prefill["busy_s"] == pytest.approx(1.0) and prefill["args"] == {"prompt_len": 64}
    assert out[names.index("host.gc")]["parent"] is None
    last = out[-1]
    assert last["name"] == "fabric.step" and last["end"] == pytest.approx(10.0)
    assert red["mono_offset_s"] == pytest.approx(100.0)


def test_idle_is_charged_to_the_innermost_open_span_and_sums_to_the_window():
    events, program = synthetic()
    idle = spans.reduce(events, program)["idle_by_span"]
    busy = trace.reduce(events)["busy_s"]
    assert busy == pytest.approx(0.2 + 1.0 + 1.0 + 1.0)
    assert sum(idle.values()) == pytest.approx(10.0 - busy)
    assert idle["engine.prefill"] == pytest.approx(0.4)        # 0.4-0.5 and 1.5-1.8
    assert idle["engine.admit"] == pytest.approx(0.2 + 0.2)    # 0.2-0.4 and 1.8-2.0
    assert idle["engine.decode"] == pytest.approx(0.4 + 0.2)    # in each step
    assert idle["host.gc"] == pytest.approx(1.0)
    assert idle["outside"] == pytest.approx(0.5 + 0.5 + 1.5)    # 4-4.5, 6.5-7, 8-9.5


def test_span_metric_readers():
    events, program = synthetic()
    rec = {"spans": spans.reduce(events, program)["spans"]}
    assert read("prefill_device_ms", rec) == pytest.approx(1000.0)
    assert read("first_token_hold_ms", rec) == pytest.approx(1000 * (4.0 - 1.8))
    # step idle: 4.0 - 2.2 busy, 2.0 - 1.0, 0.5 - 0.0: the median of three by rank
    assert read("step_host_idle_ms", rec) == pytest.approx(1000.0)


@pytest.mark.parametrize("name", spans.METRICS)
def test_span_readers_read_nothing_without_spans(name):
    assert read(name, {}) is None
    assert read(name, {"spans": []}) is None
    events, _ = synthetic()
    assert read(name, {"spans": spans.reduce(events, [])["spans"]}) is None


def test_a_trace_without_program_spans_charges_all_idle_outside():
    ev = trace.load(str(SAMPLE))
    assert spans.load(str(SAMPLE)) == []
    red = spans.reduce(ev, [])
    r = trace.reduce(ev)
    assert list(red["idle_by_span"]) == ["outside"]
    assert red["idle_by_span"]["outside"] == pytest.approx(r["window_s"] - r["busy_s"])
    assert red["mono_offset_s"] is None


def test_existing_trace_reduction_is_unchanged():
    """Every key ``trace.reduce`` returned before the program had spans, on
    the recorded TPU trace, read back exactly."""
    want = json.loads((TESTDATA / "trace_sample.reduce.json").read_text())
    got = json.loads(json.dumps(trace.reduce(trace.load(str(SAMPLE)))))
    assert got == want


def _rec_with_every_input():
    steps = [Step(0.0, 0.05, 0, 4, [100] * 4, []), Step(0.05, 0.2, 1, 5, [100] * 4 + [256], [256]),
             Step(0.2, 0.25, 0, 5, [101] * 5, [])]
    tracks = [track(i, due=0.01 * i, submit=0.01 * i, times=[0.2, 0.25, 0.3]) for i in range(5)]
    tr = {"busy_s": 0.2, "window_s": 0.3, "step_device_s": [0.04, 0.12, 0.04],
          "device_ops": [], "idle_gaps": []}
    return record(tracks, steps, trace=tr, waits=[0.001, 0.002])


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
                                  if m["name"] != "setup_s"])
def test_existing_readers_ignore_the_spans_key(name):
    rec = _rec_with_every_input()
    before = read(name, rec)
    events, program = synthetic()
    rec["spans"] = spans.reduce(events, program)["spans"]
    assert read(name, rec) == before


def test_recorded_program_spans_reduce_against_a_device(tmp_path):
    """Spans recorded around a tiny fabric on the CPU, against stand-in
    device ops (a CPU trace has no device plane): the loader finds every
    phase and the readers read them."""
    import jax

    from repro.fabric import Fabric, FabricConfig
    from repro.obs import ObsConfig

    fab = Fabric.open(FabricConfig(arch="yi_6b", smoke=True, max_batch=2, page_size=8,
                                   num_pages=32, kv_window=2, max_seq=64,
                                   obs=ObsConfig(trace_rate=1.0)))
    fab.submit([1, 2, 3], max_new_tokens=2)
    fab.drain(max_steps=50)
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(3):
            fab.submit([4, 5, 6 + i], max_new_tokens=3)
        fab.drain(max_steps=50)
    jax.profiler.stop_trace()
    fab.close()
    path = trace.find(str(tmp_path))
    events, program = trace.load(path), spans.load(path)
    assert events["device"] == {}
    assert {"fabric.step", "engine.step", "engine.prefill", "engine.decode"} \
        <= {s[2] for s in program}
    lo, hi = events["host"][0][:2]
    w = hi - lo
    events["device"] = {"/device:TPU:0": [(lo + w * k / 1000, lo + w * (k + 0.5) / 1000, "op")
                                          for k in range(1000)]}
    red = spans.reduce(events, program)
    assert sum(red["idle_by_span"].values()) == pytest.approx((hi - lo) / 2, rel=1e-6)
    rec = {"spans": red["spans"]}
    assert all(read(name, rec) > 0 for name in spans.METRICS)
    assert sum(1 for s in red["spans"] if s["name"] == "engine.prefill") == 3


def test_traced_run_prints_the_span_line(capsys, monkeypatch, tmp_path):
    """The traced run of a tiny cell with the span reduction, against
    stand-in device ops: run.py's line, then the span line, whose idle split
    sums to the window's idle time."""
    monkeypatch.setattr(run, "enable_compile_cache", lambda root: "off in tests")
    load = trace.load

    def with_device(path):
        ev = load(path)
        lo, hi = next((a, b) for a, b, n in ev["host"] if n == "bench.window")
        n = int((hi - lo) * 1000)
        ev["device"] = {"/device:TPU:0": [(lo + k * 1e-3, lo + k * 1e-3 + 4e-4, "op")
                                          for k in range(n)]}
        return ev

    monkeypatch.setattr(trace, "load", with_device)
    root = tiny.make_root(tmp_path, limit=1e-4)
    rc = spans.traced_run(["--workload", "tiny.open", "--seed", "3", "--seconds", "2",
                           "--out", str(tmp_path / "red.json")], root=root, require_tpu=False)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    line, summary = json.loads(out[-2]), json.loads(out[-1])
    assert line["correct"] is True
    assert set(summary["metrics"]) == set(spans.METRICS)
    idle = line["device"]["window_s"] - line["device"]["busy_s"]
    assert summary["idle_s"] == pytest.approx(idle, rel=1e-9)
    assert json.loads((tmp_path / "red.json").read_text())["spans"]
    assert trace.load is with_device  # put back after the run
