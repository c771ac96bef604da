"""Share of its roofline the latent (MLA) decode kernel reaches, in %: the
least time the chip could take for the attention every decoding step of
the traced window needs (``bench/counts.py``: per lane at context c,
``attention_flops(c)`` operations and ``c * kv_bytes_per_token`` bytes of
cached latent read; the larger of operations over peak rate and bytes over
peak bandwidth, per step), over the device time of the kernel's ops
(``mla_decode_attention``) in the trace's device operations. The trace's
reduction keeps only its ten longest ops, so a kernel outside them reads
as absent: None, as where no such op ran (another attention) or the run
was not traced."""

from bench import counts

KERNEL = "mla_decode_attention"


def read(rec):
    tr = rec.get("trace")
    if not tr or not rec.get("peaks"):
        return None
    kernel_s = sum(seconds for name, seconds in tr["device_ops"] if KERNEL in name)
    if kernel_s <= 0:
        return None
    cfg, peaks = rec["cfg"], rec["peaks"]
    least = sum(counts.least_seconds(
        sum(counts.attention_flops(cfg, c) for c in s.context),
        sum(c * counts.kv_bytes_per_token(cfg) for c in s.context), peaks)
        for s in rec["window_steps"] if s.decodes > 0)
    return 100 * least / kernel_s
