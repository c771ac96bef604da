"""Mean share of the KV pool's pages not free after each step of the
window (``PagedKVPool.free_pages``, read in traced runs)."""


def read(rec):
    vals = [s.kv_used for s in rec["window_steps"] if s.kv_used is not None]
    return sum(vals) / len(vals) if vals else None
