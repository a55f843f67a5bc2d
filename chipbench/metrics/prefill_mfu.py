"""Needed FLOPs of the prompts prefilled in the traced window over the
prefill program's device time there x the chip's peak, in %."""
from chipbench import peaks, work


def read(rec):
    p = rec.trace["programs"].get("prefill_step") if rec.trace else None
    a = rec.window_admits()
    if not p or not a:
        return None
    f = sum(work.prefill_flops(rec.arch, x.prompt) for x in a)
    return 100.0 * f / (p["s"] * peaks.peaks(rec.device_kind)["flops"])
