"""Needed FLOPs of the prompts prefilled in the traced window, for a
decoder of latent attention and routed experts, over the prefill
program's device time there x the chip's peak, in %. It is
``prefill_mfu`` under the work model of ``chipbench/work_mla_moe.py``."""
from chipbench import peaks, work_mla_moe as work


def read(rec):
    if not rec.trace or not rec.arch.get("mla") or not rec.arch.get(
            "n_experts"):
        return None
    p = rec.trace["programs"].get("prefill_step")
    a = rec.window_admits()
    if not p or not a:
        return None
    f = sum(work.prefill_flops(rec.arch, x.prompt) for x in a)
    return 100.0 * f / (p["s"] * peaks.peaks(rec.device_kind)["flops"])
