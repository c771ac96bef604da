"""Device-idle time inside one ``Fabric.step``: the median, over the traced
window's ``fabric.step`` spans, of the span's length less the device-busy
time inside it (``spans`` of ``bench/spans.py``)."""

from bench.metrics import percentile


def read(rec):
    idle = [s["end"] - s["start"] - s["busy_s"] for s in rec.get("spans") or ()
            if s["name"] == "fabric.step"]
    p = percentile(idle, 50)
    return None if p is None else 1000 * p
