"""Share of the traced window in which no operation ran on the device, in %
(profiler trace, averaged over the chips used)."""


def read(rec):
    if not rec.trace:
        return None
    return 100.0 * (1.0 - rec.trace["busy_s"] / rec.trace["window_s"])
