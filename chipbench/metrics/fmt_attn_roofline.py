"""Roofline share of the certified flash-decode kernel, in %: the least time
of decode attention over each lane's live positions (KV at the attention
scope's format width) over the device time of ``flash_decode`` inside the
decode program (profiler trace)."""
from chipbench import peaks, work


def read(rec):
    t = rec.trace["kernels"].get("decode_step/flash_decode") \
        if rec.trace else None
    if not t or not rec.fmt_map:
        return None
    peak = peaks.peaks(rec.device_kind)
    need = sum(work.roofline_s(work.decode_attention(rec.arch, rec.fmt_map,
                                                     s.lanes, s.kv), peak)
               for s in rec.window_steps())
    return 100.0 * need / t
