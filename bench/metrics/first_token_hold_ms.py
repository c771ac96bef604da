"""How long a first token waits on the host before any client can see it:
the median, over the traced window's ``engine.prefill`` spans, of the end
of the enclosing ``fabric.step`` less the end of the prefill, which ends
just after the first token's host read (``spans`` of ``bench/spans.py``)."""

from bench.metrics import percentile


def read(rec):
    spans = rec.get("spans") or []
    holds = []
    for s in spans:
        if s["name"] != "engine.prefill":
            continue
        up = s["parent"]
        while up is not None and spans[up]["name"] != "fabric.step":
            up = spans[up]["parent"]
        if up is not None:
            holds.append(spans[up]["end"] - s["end"])
    p = percentile(holds, 50)
    return None if p is None else 1000 * p
