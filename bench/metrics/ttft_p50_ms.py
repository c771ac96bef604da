"""Median time to first token over every request due in the window, from
its due time to the return of the step that produced its first token
(``ttfts``: an unserved request ranks above every served one). Open loop
only."""

from bench.metrics import percentile, ttfts


def read(rec):
    p = percentile(ttfts(rec) or [], 50)
    return None if p is None else 1000 * p
