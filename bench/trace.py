"""Reduction of a profiler trace to device busy time, per-step device time,
the top device operations and the longest idle gaps.

The traced run wraps its window in a ``bench.window`` annotation and each
host phase in ``bench.step``, ``bench.submit`` or ``bench.wait``
(``loop.py``). Device operations are the events of the ``XLA Ops`` line of
each ``/device:TPU:<n>`` plane; host annotations are events of the
``/host:CPU`` plane. Both are in the trace's one nanosecond clock.

    python -m bench.trace <file.xplane.pb>      # print the reduction
    python -m bench.trace --record <dir>        # record a small trace
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]

HOST_PHASES = ("bench.step", "bench.submit", "bench.wait")


def load(path: str) -> dict:
    """Device ops per chip and the harness's host annotations, in seconds."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device: Dict[str, List[tuple]] = {}
    host: List[tuple] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "SparseCore" not in plane.name:
            lines = {line.name: line for line in plane.lines}
            line = lines.get("XLA Ops")
            if line is None:
                continue
            device[plane.name] = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
                                  for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name))
    host.sort()
    return {"device": device, "host": host}


def find(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    return files[-1] if files else None


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[list] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


class Busy:
    """Sorted, disjoint busy intervals with prefix sums, so the busy time
    inside any [lo, hi) costs two bisections."""

    def __init__(self, merged: List[Interval]):
        self.starts = [a for a, _ in merged]
        self.ends = [b for _, b in merged]
        self.cum = [0.0]
        for a, b in merged:
            self.cum.append(self.cum[-1] + (b - a))

    def upto(self, t: float) -> float:
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0.0
        return self.cum[i - 1] + min(t, self.ends[i - 1]) - self.starts[i - 1]

    def between(self, lo: float, hi: float) -> float:
        return max(0.0, self.upto(hi) - self.upto(lo))


def reduce(events: dict, top: int = 10) -> Optional[dict]:
    """``busy_s`` (averaged over chips) and ``window_s`` of the window,
    device-busy seconds of each step (from its start to the next step's),
    the ``top`` device ops by summed duration and the ``top`` longest idle
    gaps, each named by the host phase at its midpoint."""
    host = events["host"]
    windows = [(a, b) for a, b, n in host if n == "bench.window"]
    if not windows or not events["device"]:
        return None
    lo, hi = windows[0]
    chips = list(events["device"].values())
    merged = [union([(a, b) for a, b, _ in ops if b > lo and a < hi]) for ops in chips]
    busy = sum(Busy(m).between(lo, hi) for m in merged) / len(merged)
    if busy <= 0:
        return None
    steps = [(a, b) for a, b, n in host if n == "bench.step" and lo <= a < hi]
    bounds = [a for a, _ in steps[1:]] + [hi]
    first = Busy(merged[0])
    step_busy = [first.between(a, end) for (a, _), end in zip(steps, bounds)]
    totals: Dict[str, float] = {}
    for a, b, name in chips[0]:
        if b > lo and a < hi:
            totals[name] = totals.get(name, 0.0) + (min(b, hi) - max(a, lo))
    phases = [(a, b, n) for a, b, n in host if n in HOST_PHASES]
    starts = [a for a, _, _ in phases]
    gaps = []
    prev = lo
    for a, b in merged[0] + [(hi, hi)]:
        a, b = max(a, lo), min(b, hi)
        if a > prev:
            mid = (prev + a) / 2
            i = bisect.bisect_right(starts, mid) - 1
            label = phases[i][2] if i >= 0 and mid < phases[i][1] else "host"
            gaps.append([label, a - prev])
        prev = max(prev, b)
    return {
        "busy_s": busy, "window_s": hi - lo,
        "step_device_s": step_busy,
        "device_ops": sorted(([k, v] for k, v in totals.items()), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(gaps, key=lambda g: -g[1])[:top],
    }


def record_sample(out_dir: str) -> str:
    """Record a small trace in the traced run's shape: a window of a few
    steps of device work with host waits between them (for the tests)."""
    import time

    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    f(x).block_until_ready()
    jax.profiler.start_trace(out_dir)
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(4):
            with jax.profiler.TraceAnnotation("bench.submit"):
                y = x + i
            with jax.profiler.TraceAnnotation("bench.step"):
                for _ in range(i + 1):
                    y = f(y)
                y.block_until_ready()
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    return find(out_dir)


if __name__ == "__main__":
    if sys.argv[1] == "--record":
        print(record_sample(sys.argv[2]))
    else:
        print(json.dumps(reduce(load(sys.argv[1])), indent=1))
