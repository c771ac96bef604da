"""95th percentile of fabric admission wait: the flight recorder's
``submit`` to ``seat`` time per request submitted in the window
(``ObsConfig(trace_rate=1.0)``, traced runs)."""

from bench.metrics import percentile


def read(rec):
    waits = rec.get("queue_waits")
    p = percentile(waits or [], 95)
    return None if p is None else 1000 * p
