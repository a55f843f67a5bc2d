"""Roofline share of the format GEMM kernel in decode, in %: the least time
of the decode steps' layer weight products (needed work at each scope's
format width, active lanes only) over the device time of
``quant_matmul_format`` inside the decode program (profiler trace)."""
from chipbench import peaks, work


def read(rec):
    t = rec.trace["kernels"].get("decode_step/quant_matmul_format") \
        if rec.trace else None
    if not t or not rec.fmt_map:
        return None
    peak = peaks.peaks(rec.device_kind)
    need = sum(work.roofline_s(work.decode_gemms(rec.arch, rec.fmt_map,
                                                 s.lanes), peak)
               for s in rec.window_steps())
    return 100.0 * need / t
