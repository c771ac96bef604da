"""Profiler spans of the served step (DESIGN.md §13): their names and
nesting in a recorded trace, the ``t_mono_ns`` anchor that maps flight
recorder events onto the trace's clock, the ``host.gc`` callback, and that
a fabric without an obs hub builds no span at all."""

import gc
import glob
import os
import statistics

import pytest

from repro.fabric import Fabric, FabricConfig
from repro.obs import SPAN_NAMES, GcSpans, ObsConfig

PROMPTS = [[3, 1, 4], [1, 5, 9, 2, 6], [5, 3, 5], [8, 9, 7, 9, 3], [2, 3, 8]]


@pytest.fixture(scope="module")
def model():
    import jax
    from repro.configs import get_config
    from repro.models import init_params
    cfg = get_config("yi_6b", smoke=True)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _open(model, obs):
    mcfg, params = model
    return Fabric.open(FabricConfig(arch="yi_6b", max_batch=2, page_size=8,
                                    num_pages=32, kv_window=2, max_seq=64,
                                    obs=obs),
                       params=params, model_cfg=mcfg)


def _serve(fab, prompts):
    for p in prompts:
        fab.submit(p, max_new_tokens=3)
    fab.drain(max_steps=200)


def _program_spans(trace_dir):
    """(start s, end s, name, stats, thread) of every ``fabric.*``,
    ``engine.*`` and ``host.*`` event of the trace, by start."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("fabric.", "engine.", "host.")):
                    out.append((e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9, e.name,
                                {k: v for k, v in e.stats}, line.name))
    return sorted(out, key=lambda s: (s[0], -s[1]))


@pytest.fixture(scope="module")
def traced(model, tmp_path_factory):
    """A served fabric under the profiler (every prompt length compiled
    before the trace starts), with one forced collection inside it."""
    import jax
    fab = _open(model, ObsConfig(trace_rate=1.0, sample_every_n_steps=2))
    _serve(fab, PROMPTS[:2])
    trace_dir = str(tmp_path_factory.mktemp("spans"))
    jax.profiler.start_trace(trace_dir)
    try:
        _serve(fab, PROMPTS)
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    events = fab.obs.events()
    fab.close()
    return _program_spans(trace_dir), events


def _parent(spans, i):
    """The innermost span of the same thread that contains span ``i``."""
    a, b, _, _, line = spans[i]
    best = None
    for j, (a2, b2, _, _, line2) in enumerate(spans):
        if j != i and line2 == line and a2 <= a and b <= b2:
            if best is None or a2 >= spans[best][0]:
                best = j
    return best


def test_span_names_args_and_clock_stat(traced):
    spans, _ = traced
    names = {s[2] for s in spans}
    assert names == set(SPAN_NAMES)
    assert all("t_mono_ns" in s[3] for s in spans)
    assert {s[3]["prompt_len"] for s in spans if s[2] == "engine.prefill"} \
        == {3, 5}
    assert {s[3]["rid"] for s in spans if s[2] == "engine.step"} == {0}
    # this host has no TPU: every decode step gathered
    assert {s[3]["attn"] for s in spans if s[2] == "engine.decode"} \
        == {"gather"}
    # the fabric served before the trace: every forward call donated its pools
    assert {s[3]["donated"] for s in spans
            if s[2] in ("engine.decode", "engine.prefill")} == {1}
    assert all(s[3]["generation"] in (0, 1, 2)
               for s in spans if s[2] == "host.gc")


@pytest.mark.parametrize("child,chain", [
    ("engine.prefill", ["engine.admit", "engine.step", "fabric.step"]),
    ("engine.drain", ["engine.admit", "engine.step", "fabric.step"]),
    ("engine.decode", ["engine.step", "fabric.step"]),
    ("engine.bookkeep", ["engine.step", "fabric.step"]),
    ("engine.grow_pages", ["engine.step", "fabric.step"]),
    ("fabric.obs_sample", ["fabric.step"]),
])
def test_spans_nest_inside_the_step(traced, child, chain):
    spans, _ = traced
    found = [i for i, s in enumerate(spans) if s[2] == child]
    assert found
    for i in found:
        up = []
        j = _parent(spans, i)
        while j is not None and len(up) < len(chain):
            up.append(spans[j][2])
            j = _parent(spans, j)
        assert up == chain, (child, up)


def test_page_grabs_nest_in_admission_or_page_growth(traced):
    spans, _ = traced
    grabs = [i for i, s in enumerate(spans) if s[2] == "engine.alloc"]
    assert grabs
    assert {spans[_parent(spans, i)][2] for i in grabs} <= \
        {"engine.admit", "engine.grow_pages"}


def test_lane_prefill_events_map_into_their_prefill_spans(traced):
    spans, events = traced
    offset = statistics.median(a - s["t_mono_ns"] * 1e-9
                               for a, _, _, s, _ in spans)
    prefills = [(a, b) for a, b, n, _, _ in spans if n == "engine.prefill"]
    lane = [t + offset for t, stage, *_ in events if stage == "lane_prefill"
            and prefills[0][0] <= t + offset]
    assert len(prefills) == len(PROMPTS) == len(lane)
    for t, (a, b) in zip(sorted(lane), prefills):
        assert a <= t <= b


def test_a_forced_collection_is_a_gc_span(traced):
    spans, _ = traced
    full = [s for s in spans if s[2] == "host.gc" and s[3]["generation"] == 2]
    assert full and all(b > a for a, b, *_ in full)


def test_no_hub_builds_no_span_and_installs_no_gc_callback(model, monkeypatch):
    import jax

    def refuse(*a, **kw):
        raise AssertionError("a TraceAnnotation was built")

    before = list(gc.callbacks)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    fab = _open(model, None)
    _serve(fab, PROMPTS[:3])
    assert len(fab.completed) == 3
    assert all(cb in before for cb in gc.callbacks)  # nothing installed
    gc.collect()
    fab.close()


def test_the_gc_callback_lives_from_attach_to_close(model):
    before = list(gc.callbacks)
    fab = _open(model, ObsConfig(trace_rate=0.0))
    mine = [cb for cb in gc.callbacks if cb not in before]
    assert len(mine) == 1 and isinstance(mine[0], GcSpans)
    fab.step()
    fab.close()
    assert mine[0] not in gc.callbacks
    fab.close()  # idempotent
