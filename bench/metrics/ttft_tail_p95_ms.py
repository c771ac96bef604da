"""95th percentile of time to first token over every request due in the
window (``ttfts``): the tail, which a host stall of a second or two moves
by several times, so it is read per layer, beside ``ttft_p50_ms``."""

from bench.metrics import percentile, ttfts


def read(rec):
    p = percentile(ttfts(rec) or [], 95)
    return None if p is None else 1000 * p
