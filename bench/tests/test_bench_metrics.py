"""Each metric reader on a synthetic run record."""

import importlib
import json
from pathlib import Path

import pytest

from bench import counts, generator
from bench.loop import Step, Track

ROOT = Path(__file__).resolve().parents[2]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
YI = json.loads((ROOT / "bench" / "configs" / "yi_6b.json").read_text())


def read(name, rec):
    return importlib.import_module(f"bench.metrics.{name}").read(rec)


def track(i, due, submit, times, prompt=64, max_new=None, preemptions=0, done=True):
    req = generator.Request(i, [1] * prompt, max_new or len(times), due)
    return Track(req, due, submit, uid=i, token_times=list(times),
                 done=times[-1] if done and times else None,
                 output=[1] * len(times) if done else None, preemptions=preemptions)


def record(tracks, steps=(), trace=None, loop="open", seconds=10.0, waits=None, end=None):
    return {"seconds": seconds, "end": seconds + 5.0 if end is None else end,
            "loop": loop, "tracks": tracks, "steps": list(steps),
            "window_steps": list(steps), "cfg": YI, "peaks": PEAKS, "max_batch": 4,
            "trace": trace, "queue_waits": waits}


def test_ttft_p95_ranks_unserved_requests_above_served_ones():
    served = [track(i, due=1.0, submit=1.0, times=[1.0 + 0.01 * (i + 1), 2.0]) for i in range(19)]
    rec = record(served + [track(19, due=2.0, submit=2.0, times=[2.5, 3.0])])
    assert read("ttft_tail_p95_ms", rec) == pytest.approx(190.0)  # 19th of 20 by rank
    rec = record(served + [track(19, due=2.0, submit=2.0, times=[], done=False),
                           track(20, due=2.0, submit=2.0, times=[], done=False)])
    # the p95 request never got a token: it counts until the drain's end
    assert read("ttft_tail_p95_ms", rec) == pytest.approx(13000.0)
    assert read("ttft_tail_p95_ms", record(served, loop="closed")) is None


def test_ttft_p50_is_the_median_request_and_ranks_unserved_ones_last():
    served = [track(i, due=1.0, submit=1.0, times=[1.0 + 0.01 * (i + 1), 2.0]) for i in range(9)]
    assert read("ttft_p50_ms", record(served)) == pytest.approx(50.0)  # 5th of 9
    unserved = [track(9 + i, due=2.0, submit=2.0, times=[], done=False) for i in range(10)]
    assert read("ttft_p50_ms", record(served + unserved)) == pytest.approx(13000.0)
    assert read("ttft_p50_ms", record(served, loop="closed")) is None


def test_itl_counts_gaps_ending_inside_the_window_only():
    rec = record([track(0, 0.0, 0.0, [1.0, 1.1, 1.3, 10.5])], seconds=10.0)
    assert read("itl_p95_ms", rec) == pytest.approx(200.0)


def test_output_tokens_per_second_counts_tokens_inside_the_window():
    rec = record([track(0, 0.0, 0.0, [1.0, 2.0, 11.0]), track(1, 0.0, 0.0, [3.0])], seconds=10.0)
    assert read("output_tok_s", rec) == pytest.approx(0.3)


def test_generator_lateness_and_queue_wait():
    tracks = [track(i, due=float(i), submit=i + 0.001 * i, times=[i + 1.0]) for i in range(10)]
    assert read("gen_late_p95_ms", record(tracks)) == pytest.approx(9.0)
    assert read("queue_wait_p95_ms", record(tracks, waits=[0.001 * i for i in range(1, 21)])) \
        == pytest.approx(19.0)
    assert read("queue_wait_p95_ms", record(tracks)) is None


def test_lane_occupancy_kv_and_preemptions():
    steps = [Step(0, 1, 0, 4, [10] * 4, [], 0.5), Step(1, 2, 1, 2, [10, 64], [64], 0.25),
             Step(2, 3, 0, 0, [], [], None)]
    rec = record([track(0, 0, 0, [1.0], preemptions=2), track(1, 0, 0, [2.0])], steps)
    assert read("lane_occupancy", rec) == pytest.approx(0.75)
    assert read("kv_used_frac", rec) == pytest.approx(0.375)
    assert read("preempt_per_req", rec) == pytest.approx(1.0)


def test_trace_metrics_from_decode_only_and_prefill_steps():
    ctx = [100] * 4
    steps = [Step(0, 0.05, 0, 4, ctx, []), Step(0.05, 0.1, 0, 4, ctx, []),
             Step(0.1, 0.2, 2, 4, ctx[:2] + [64, 64], [64, 64])]
    trace = {"busy_s": 0.15, "window_s": 0.2, "step_device_s": [0.04, 0.04, 0.1]}
    rec = record([], steps, trace)
    assert read("prefill_ms", rec) == pytest.approx(30.0)  # (100 - 40) / 2
    least = counts.least_seconds(counts.decode_flops(YI, ctx), counts.decode_bytes(YI, ctx), PEAKS)
    assert read("decode_hbm_roofline", rec) == pytest.approx(100 * least / 0.04)
    assert 0 < read("decode_hbm_roofline", rec) < 100
    assert read("device_idle_frac", rec) == pytest.approx(0.25)
    flops = (2 * counts.decode_flops(YI, ctx) + counts.decode_flops(YI, ctx[:2] + [64, 64])
             + 2 * counts.prefill_flops(YI, 64))
    assert read("mfu", rec) == pytest.approx(100 * flops / 0.2 / PEAKS["bf16_flops_per_s"])


@pytest.mark.parametrize("name", ["prefill_ms", "decode_hbm_roofline", "device_idle_frac"])
def test_trace_readers_return_nothing_without_a_trace(name):
    assert read(name, record([], [Step(0, 1, 0, 1, [1], [])])) is None


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_a_refusal_at_submit_is_unserved(loop):
    from bench import check
    served = [track(i, due=1.0, submit=1.0, times=[1.1, 1.2]) for i in range(3)]
    refused = track(3, due=1.0, submit=1.0, times=[], done=False)
    refused.uid = None
    assert check.admission(served, loop, 0)["unserved"] == 0
    assert check.admission(served + [refused], loop, 0)["unserved"] == 1
