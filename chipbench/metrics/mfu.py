"""Needed model FLOPs of every token processed in the traced window (decode
tokens at their live lengths, prompt tokens causally, logits of emitted
rows only) over window x chips x the chip's peak, in %."""
from chipbench import peaks, work


def read(rec):
    if not rec.trace:
        return None
    f = sum(work.decode_flops(rec.arch, s.lanes, s.kv)
            for s in rec.window_steps())
    f += sum(work.prefill_flops(rec.arch, a.prompt)
             for a in rec.window_admits())
    peak = peaks.peaks(rec.device_kind)["flops"]
    return 100.0 * f / (rec.trace["window_s"] * rec.chips * peak)
