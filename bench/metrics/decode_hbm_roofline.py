"""Share of its roofline the decode step reaches, in %: over the traced
window's decode-only steps, the least time the chip could take for what
each step needs (``bench/counts.py``: weights, each lane's live keys and
values read, the new ones written; the larger of bytes over peak
bandwidth and operations over peak rate) over the device time the step
took."""

from bench import counts
from bench.metrics import decode_only


def read(rec):
    steps = decode_only(rec)
    if not steps or not rec.get("peaks"):
        return None
    cfg, peaks = rec["cfg"], rec["peaks"]
    least = sum(counts.least_seconds(counts.decode_flops(cfg, s.context),
                                     counts.decode_bytes(cfg, s.context), peaks)
                for s, _ in steps)
    return 100 * least / sum(d for _, d in steps)
