"""Needed model FLOPs of every token processed in the traced window (decode
tokens at their live lengths, prompt tokens causally, logits of emitted
rows only), for a decoder of latent attention and routed experts, over
window x chips x the chip's peak, in %. It is ``mfu`` under the work model
of ``chipbench/work_mla_moe.py``."""
from chipbench import peaks, work_mla_moe as work


def read(rec):
    if not rec.trace or not rec.arch.get("mla") or not rec.arch.get(
            "n_experts"):
        return None
    f = sum(work.decode_flops(rec.arch, s.lanes, s.kv)
            for s in rec.window_steps())
    f += sum(work.prefill_flops(rec.arch, a.prompt)
             for a in rec.window_admits())
    peak = peaks.peaks(rec.device_kind)["flops"]
    return 100.0 * f / (rec.trace["window_s"] * rec.chips * peak)
