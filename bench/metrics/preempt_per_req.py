"""Preemptions per request completed in the window
(``Request.preemptions``)."""


def read(rec):
    done = [t for t in rec["tracks"] if t.done is not None and t.done <= rec["seconds"]]
    return sum(t.preemptions for t in done) / len(done) if done else None
