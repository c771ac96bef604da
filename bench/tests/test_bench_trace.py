"""The trace reduction, on synthetic events and on a small trace recorded
on a TPU v5e (``python -m bench.trace --record``, kept in testdata/)."""

from pathlib import Path

import pytest

from bench import trace

SAMPLE = Path(__file__).resolve().parents[1] / "testdata" / "trace_sample.xplane.pb"


def synthetic():
    host = [(0.0, 10.0, "bench.window"),
            (0.0, 2.0, "bench.step"), (2.0, 3.0, "bench.wait"),
            (3.0, 6.0, "bench.step"), (6.0, 6.5, "bench.submit"), (6.5, 10.0, "bench.wait")]
    ops = [(0.5, 1.5, "fusion.1"), (1.0, 1.8, "fusion.2"),   # overlap: union 0.5-1.8
           (3.5, 5.0, "fusion.1"), (5.5, 6.2, "copy"), (-1.0, 0.2, "before")]
    return {"host": host, "device": {"/device:TPU:0": ops}}


def test_busy_is_the_union_inside_the_window():
    r = trace.reduce(synthetic())
    assert r["window_s"] == pytest.approx(10.0)
    assert r["busy_s"] == pytest.approx(0.2 + 1.3 + 1.5 + 0.7)


def test_step_device_time_runs_to_the_next_step():
    r = trace.reduce(synthetic())
    assert r["step_device_s"] == pytest.approx([0.2 + 1.3, 1.5 + 0.7])


def test_top_ops_and_idle_gaps_named_by_host_phase():
    r = trace.reduce(synthetic())
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(1.0 + 1.5)]
    gaps = r["idle_gaps"]
    assert gaps[0] == ["bench.wait", pytest.approx(10.0 - 6.2)]
    assert ["bench.wait", pytest.approx(3.5 - 1.8)] in gaps
    assert sum(g for _, g in gaps) == pytest.approx(10.0 - r["busy_s"])


def test_no_window_or_no_device_reads_nothing():
    ev = synthetic()
    assert trace.reduce({"host": ev["host"][1:], "device": ev["device"]}) is None
    assert trace.reduce({"host": ev["host"], "device": {}}) is None


def test_recorded_tpu_trace():
    ev = trace.load(str(SAMPLE))
    assert list(ev["device"]) == ["/device:TPU:0"]
    r = trace.reduce(ev)
    names = [n for *_, n in ev["host"]]
    assert names.count("bench.step") == 4 and names.count("bench.window") == 1
    assert 0 < r["busy_s"] < r["window_s"]
    # the i-th step runs the program i + 1 times: 1 + 2 + 3 + 4 matmuls
    ops = ev["device"]["/device:TPU:0"]
    assert sum(1 for *_, n in ops if n.startswith("%convolution_tanh_fusion")) == 10
    # steps split the busy time from the first step to the window's end
    assert len(r["step_device_s"]) == 4
    assert 0 < sum(r["step_device_s"]) <= r["busy_s"] + 1e-9
    assert r["device_ops"] and r["idle_gaps"]
    assert sum(g for _, g in r["idle_gaps"]) <= r["window_s"] - r["busy_s"] + 1e-9
