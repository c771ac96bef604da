"""Mean share of the engine's lanes that decoded a token, over the
window's steps."""


def read(rec):
    steps = [s for s in rec["window_steps"] if s.decodes]
    if not steps:
        return None
    return sum(s.decodes for s in steps) / (len(steps) * rec["max_batch"])
