"""Model FLOP/s utilisation of the whole step, in %: the model operations
of every step in the window (2*N per prompt and output token processed,
plus attention over the live context, ``bench/counts.py``) over the
summed host wall time of those steps, over the chip's peak bf16 rate."""

from bench import counts


def read(rec):
    steps = rec["window_steps"]
    if not steps or not rec.get("peaks"):
        return None
    cfg = rec["cfg"]
    flops = sum(counts.decode_flops(cfg, s.context)
                + sum(counts.prefill_flops(cfg, p) for p in s.prompts) for s in steps)
    wall = sum(s.t1 - s.t0 for s in steps)
    return 100 * flops / wall / rec["peaks"]["bf16_flops_per_s"]
