"""Roofline share of the format GEMM kernel in decode, for a decoder of
latent attention and routed experts, in %: the least time of every decode
product that runs on it (projections, dense MLP, router, shared experts,
and the routed experts' grouped products, each expert's weights once) at
each scope's format width, active lanes only, over the device time of
``quant_matmul_format`` inside the decode program (profiler trace)."""
from chipbench import peaks, work
from chipbench import work_mla_moe


def read(rec):
    t = rec.trace["kernels"].get("decode_step/quant_matmul_format") \
        if rec.trace else None
    if not t or not rec.fmt_map or not rec.arch.get("n_experts"):
        return None
    peak = peaks.peaks(rec.device_kind)
    need = sum(work.roofline_s(work_mla_moe.decode_gemms(
        rec.arch, rec.fmt_map, s.lanes), peak) for s in rec.window_steps())
    return 100.0 * need / t
