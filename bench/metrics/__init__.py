"""One reader per metric, found by the metric's name in ``BENCHMARK.json``.

Each module has ``read(rec) -> float | None``: the metric from the run
record that ``run.py`` builds (``tracks`` and ``steps`` of ``loop.py``,
the trace reduction of ``trace.py`` under ``trace``, the flight
recorder's ``queue_waits``). A reader that finds nothing to read returns
None, and the run leaves the metric out of its line.
"""

import math


def percentile(values, q: float):
    """Nearest-rank percentile (q in 0..100) of a non-empty list, else None."""
    xs = sorted(values)
    if not xs:
        return None
    return xs[max(0, math.ceil(q / 100 * len(xs)) - 1)]


def in_window(rec):
    """Tracks due inside the window."""
    return [t for t in rec["tracks"] if t.due < rec["seconds"]]


def decode_only(rec):
    """(step, device seconds) of the traced window's steps that laned no
    request and decoded at least one token."""
    tr = rec.get("trace")
    if not tr:
        return []
    return [(s, d) for s, d in zip(rec["window_steps"], tr["step_device_s"])
            if s.prefills == 0 and s.decodes > 0 and d > 0]


def ttfts(rec):
    """Seconds from due time to first token of every request due in the
    window (open loop; None in a closed loop). A request refused at submit,
    or with no first token by the end of the drain, ranks above every served
    request with its time to the drain's end (a lower bound); the run is
    then not correct."""
    if rec["loop"] != "open":
        return None
    served = [t.token_times[0] - t.due for t in in_window(rec) if t.token_times]
    top = max(served, default=0.0)
    return served + [max(rec["end"] - t.due, top) for t in in_window(rec) if not t.token_times]
