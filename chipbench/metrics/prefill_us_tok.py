"""Device time of the prefill program in the traced window per real prompt
token prefilled there, in microseconds (profiler trace; page padding is
not a prompt token)."""


def read(rec):
    p = rec.trace["programs"].get("prefill_step") if rec.trace else None
    toks = sum(a.prompt for a in rec.window_admits())
    return 1e6 * p["s"] / toks if p and toks else None
