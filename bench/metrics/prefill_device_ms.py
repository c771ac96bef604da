"""Device time of a prefill, timed inside the program: the mean, over the
traced window's ``engine.prefill`` spans (one per request laned, from its
block-table write to its first token's host read), of the device-busy time
inside the span (``spans`` of ``bench/spans.py``)."""


def read(rec):
    busy = [s["busy_s"] for s in rec.get("spans") or () if s["name"] == "engine.prefill"]
    return 1000 * sum(busy) / len(busy) if busy else None
