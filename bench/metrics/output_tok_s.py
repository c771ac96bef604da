"""Output tokens emitted inside the window, over the window's seconds."""


def read(rec):
    end = rec["seconds"]
    n = sum(1 for t in rec["tracks"] for s in t.token_times if s <= end)
    return n / end if n else None
