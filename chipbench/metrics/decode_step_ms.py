"""Device time per run of the decode program in the traced window, in ms
(profiler trace)."""


def read(rec):
    p = rec.trace["programs"].get("decode_step") if rec.trace else None
    return 1e3 * p["s"] / p["n"] if p and p["n"] else None
