"""The traffic generator: determinism, rates and sizes per seed."""

import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from bench import generator

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"


def load(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["chat", "burst", "backlog_mid", "backlog_long"])
def test_same_seed_same_requests(name):
    t = load(name)
    a = generator.generate(t, 2**31 + 7, 30, 64000)
    b = generator.generate(t, 2**31 + 7, 30, 64000)
    assert [(r.due, r.max_new, r.prompt.tolist()) for r in a] == \
           [(r.due, r.max_new, r.prompt.tolist()) for r in b]
    c = generator.generate(t, 2**31 + 8, 30, 64000)
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in c]


@pytest.mark.parametrize("name", ["chat", "burst"])
def test_open_loop_count_rate_and_window(name):
    t = load(name)
    reqs = generator.generate(t, 5, 30, 64000)
    assert len(reqs) == round(t["arrivals"]["rate_per_s"] * 30)
    due = [r.due for r in reqs]
    assert due == sorted(due) and 0 < due[0] and due[-1] < 30
    # the mean gap is the rate's, within the quantile midpoints' shortfall
    assert due[-1] / len(due) == pytest.approx(1 / t["arrivals"]["rate_per_s"], rel=0.1)


@pytest.mark.parametrize("name", ["chat", "burst", "backlog_mid", "backlog_long"])
def test_every_seed_gets_the_same_sizes_in_another_order(name):
    t = load(name)
    # open loop: the whole window; closed loop: any whole number of blocks
    n = None if t["loop"] == "open" else 4 * generator.BLOCK
    runs = [generator.generate(t, s, 30, 64000)[:n] for s in (1, 2)]
    sizes = [(sorted(len(r.prompt) for r in rs), sorted(r.max_new for r in rs)) for rs in runs]
    assert sizes[0] == sizes[1]
    assert [r.max_new for r in runs[0]] != [r.max_new for r in runs[1]]
    if t["loop"] == "open":
        gaps = [sorted(np.diff([0.0] + [r.due for r in rs])) for rs in runs]
        np.testing.assert_allclose(gaps[0], gaps[1], rtol=1e-9, atol=1e-9)


def test_gamma_arrivals_are_burstier_than_poisson():
    cv = {}
    for name in ("chat", "burst"):
        due = np.array([r.due for r in generator.generate(load(name), 3, 40, 64000)])
        gaps = np.diff(due)
        cv[name] = gaps.std() / gaps.mean()
    assert 0.8 < cv["chat"] < 1.2
    assert cv["burst"] > 1.5


@pytest.mark.parametrize("name", ["chat", "burst", "backlog_mid", "backlog_long"])
def test_lengths_stay_in_the_traffic_bounds(name):
    t = load(name)
    reqs = generator.generate(t, 9, 30, 64000)
    prompts = {len(r.prompt) for r in reqs}
    assert prompts <= set(generator.length_values(t["prompt_len"]))
    outs = [r.max_new for r in reqs]
    vals = generator.length_values(t["output_len"])
    assert min(vals) <= min(outs) and max(outs) <= max(vals)
    assert all(0 < tok < 64000 for r in reqs[:50] for tok in r.prompt)


def test_chat_lengths_are_heavy_tailed_around_their_medians():
    reqs = generator.generate(load("chat"), 1, 40, 64000)
    prompts = np.array([len(r.prompt) for r in reqs])
    assert np.mean(prompts <= 256) == pytest.approx(0.5, abs=0.02)
    assert np.mean(prompts == 1024) == pytest.approx(0.19, abs=0.03)
    assert 56 <= np.median([r.max_new for r in reqs]) <= 72
    assert max(r.max_new for r in reqs) == 256


def test_a_block_holds_the_same_gaps_and_sizes_for_every_seed():
    t = load("chat")
    k, m = t["arrivals"]["block"], t["prompt_len"]["block"]
    runs = [generator.generate(t, s, 30, 64000) for s in (1, 2**31 + 5)]
    gaps = [np.diff([0.0] + [r.due for r in rs]) for rs in runs]
    assert not np.allclose(gaps[0], gaps[1])
    for i in range(0, len(gaps[0]) - k + 1, k):
        np.testing.assert_allclose(sorted(gaps[0][i:i + k]), sorted(gaps[1][i:i + k]))
    for i in range(0, len(runs[0]) - m + 1, m):
        assert (sorted(len(r.prompt) for r in runs[0][i:i + m])
                == sorted(len(r.prompt) for r in runs[1][i:i + m]))
