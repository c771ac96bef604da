"""The program's own spans in a profiler trace, on the device's clock: each
span's device-busy time, and the window's device-idle time charged to the
innermost span open at each idle instant.

With a flight recorder attached (``ObsConfig``, as in every traced run),
the program wraps each phase of ``Fabric.step`` in a profiler annotation
named ``fabric.*`` or ``engine.*``, and each garbage-collector pause in
``host.gc`` (DESIGN.md §13). Each carries ``t_mono_ns``, the flight
recorder's clock at its start, so ``mono_offset_s`` (the median of trace
time less ``t_mono_ns``) maps the recorder's events into the window. A
program without these spans gives an empty ``spans`` list, and every
reader of it returns None.

    python -m bench.spans <file.xplane.pb>
    python -m bench.spans --workload <cell> --seed <n> --seconds <s> [--out <file.json>]

The first prints the reduction of a kept trace (its window is the
``bench.window`` annotation of ``bench/run.py``'s traced run). The second
makes ``bench/run.py``'s traced run of the cell, unchanged, reduces the
same trace file for the spans too, prints run.py's line and then one line
with the span metrics and ``idle_by_span``; ``--out`` keeps the whole
reduction.
"""

from __future__ import annotations

import importlib
import json
import statistics
import sys
from typing import Dict, List, Optional

from bench import trace

PREFIXES = ("fabric.", "engine.", "host.")
#: the metric readers (``bench/metrics``) of this reduction's ``spans``
METRICS = ("prefill_device_ms", "first_token_hold_ms", "step_host_idle_ms")
OUTSIDE = "outside"


def load(path: str) -> List[tuple]:
    """(start s, end s, name, args, thread) of the program's spans in the
    trace, by start; at equal starts the longer (outer) span first."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    out.append((e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                                e.name, {k: v for k, v in e.stats}, line.name))
    return sorted(out, key=lambda s: (s[0], -s[1]))


def nest(spans: List[tuple]) -> List[Optional[int]]:
    """Index of each span's innermost enclosing span on its own thread."""
    stacks: Dict[str, List[int]] = {}
    parents: List[Optional[int]] = []
    for i, (_, end, _, _, thread) in enumerate(spans):
        stack = stacks.setdefault(thread, [])
        while stack and spans[stack[-1]][1] < end:
            stack.pop()
        parents.append(stack[-1] if stack else None)
        stack.append(i)
    return parents


def reduce(events: dict, spans: List[tuple], top: int = 10) -> Optional[dict]:
    """The window's spans and the split of its device-idle time.

    ``events`` is ``trace.load``'s (the ``bench.window`` annotation and the
    device ops), ``spans`` is :func:`load`'s. Returns ``spans`` (name,
    start and end in seconds after the window opened, clipped to it, args,
    ``parent`` index, device ``busy_s`` inside), ``idle_by_span`` (idle
    seconds of the first chip by innermost open span, ``outside`` where
    none is open; they sum to the window's idle time), ``idle_top`` (the
    ``top`` longest idle stretches inside one span: name, idle seconds,
    start) and ``mono_offset_s``."""
    windows = [(a, b) for a, b, n in events["host"] if n == "bench.window"]
    if not windows or not events["device"]:
        return None
    lo, hi = windows[0]
    ops = next(iter(events["device"].values()))
    busy = trace.Busy(trace.union([(a, b) for a, b, _ in ops if b > lo and a < hi]))
    anchors = [a - args["t_mono_ns"] * 1e-9 for a, _, _, args, _ in spans if "t_mono_ns" in args]
    parents = nest(spans)
    keep = [i for i, s in enumerate(spans) if s[1] > lo and s[0] < hi]
    index = {i: k for k, i in enumerate(keep)}
    out = []
    for i in keep:
        a, b, name, args, _ = spans[i]
        a, b = max(a, lo), min(b, hi)
        out.append({"name": name, "start": a - lo, "end": b - lo,
                    "args": {k: v for k, v in args.items() if k != "t_mono_ns"},
                    "parent": index.get(parents[i]), "busy_s": busy.between(a, b)})
    # sweep the span edges; between two edges the innermost open span (the
    # latest started, whatever its thread) is fixed
    edges = sorted([(s["start"], 1, k) for k, s in enumerate(out)] +
                   [(s["end"], 0, k) for k, s in enumerate(out)] + [(hi - lo, 0, -1)])
    idle: Dict[str, float] = {}
    stretches = []
    open_: set = set()
    prev = 0.0
    for t, starts, k in edges:
        if t > prev:
            name = out[max(open_)]["name"] if open_ else OUTSIDE
            gap = (t - prev) - busy.between(lo + prev, lo + t)
            idle[name] = idle.get(name, 0.0) + gap
            stretches.append([name, gap, prev])
            prev = t
        if starts:
            open_.add(k)
        else:
            open_.discard(k)
    return {"spans": out,
            "idle_by_span": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
            "idle_top": sorted(stretches, key=lambda g: -g[1])[:top],
            "mono_offset_s": statistics.median(anchors) - lo if anchors else None}


def totals(spans: List[dict]) -> Dict[str, list]:
    """Per span name: count, seconds inside, device-busy seconds inside."""
    out: Dict[str, list] = {}
    for s in spans:
        n = out.setdefault(s["name"], [0, 0.0, 0.0])
        n[0] += 1
        n[1] += s["end"] - s["start"]
        n[2] += s["busy_s"]
    return out


def summary(red: dict) -> dict:
    rec = {"spans": red["spans"]}
    metrics = {}
    for name in METRICS:
        value = importlib.import_module(f"bench.metrics.{name}").read(rec)
        if value is not None:
            metrics[name] = value
    return {"metrics": metrics, "idle_by_span": red["idle_by_span"],
            "idle_s": sum(red["idle_by_span"].values()), "idle_top": red["idle_top"],
            "span_totals": totals(red["spans"]), "mono_offset_s": red["mono_offset_s"]}


def traced_run(argv: List[str], **run_kw) -> int:
    """``bench/run.py``'s traced run of one cell (``run_kw`` go to
    ``run.run``), with the same trace file reduced for the program's spans
    before run.py deletes it."""
    import argparse

    from bench import run

    p = argparse.ArgumentParser()
    p.add_argument("--out")
    args, rest = p.parse_known_args(argv)
    got = {}
    load_trace = trace.load

    def load_both(path):
        events = load_trace(path)
        got["red"] = reduce(events, load(path))
        return events

    trace.load = load_both
    try:
        rc = run.run(rest + ["--trace", "1"], **run_kw)
    finally:
        trace.load = load_trace
    red = got.get("red")
    if rc != 0 or red is None:
        return rc or 1
    if args.out:
        with open(args.out, "w") as f:
            json.dump(red, f)
    print(json.dumps(summary(red)), flush=True)
    return 0


if __name__ == "__main__":
    if sys.argv[1].startswith("--"):
        sys.exit(traced_run(sys.argv[1:]))
    path = sys.argv[1]
    print(json.dumps(summary(reduce(trace.load(path), load(path))), indent=1))
