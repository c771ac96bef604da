"""95th percentile of how late the load generator submitted a request:
submit time minus due time, over the requests due in the window (open
loop). A starved generator shows here, not as a slow server."""

from bench.metrics import in_window, percentile


def read(rec):
    if rec["loop"] != "open":
        return None
    p = percentile([t.submit - t.due for t in in_window(rec)], 95)
    return None if p is None else 1000 * p
