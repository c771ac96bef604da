"""95th percentile of the gap between two consecutive output tokens of one
request, over every gap that ends inside the window."""

from bench.metrics import percentile


def read(rec):
    end = rec["seconds"]
    gaps = [b - a for t in rec["tracks"]
            for a, b in zip(t.token_times, t.token_times[1:]) if b <= end]
    p = percentile(gaps, 95)
    return None if p is None else 1000 * p
