"""Device time per admitted request: in each traced step that laned
requests, its device-busy time less the median of the decode-only steps,
over the requests it laned; the mean over those steps."""

from bench.metrics import decode_only, percentile


def read(rec):
    base = percentile([d for _, d in decode_only(rec)], 50)
    tr = rec.get("trace")
    if base is None or not tr:
        return None
    per = [(d - base) / s.prefills
           for s, d in zip(rec["window_steps"], tr["step_device_s"]) if s.prefills]
    return 1000 * sum(per) / len(per) if per else None
