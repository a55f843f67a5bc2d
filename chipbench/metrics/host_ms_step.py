"""Mean host time per engine step in the window, in ms, spent outside the
decode call (to its result in hand) and outside admission: the engine's
own loop, scheduling and bookkeeping."""


def read(rec):
    s = rec.window_steps()
    if not s:
        return None
    return 1e3 * sum(x.t1 - x.t0 - x.decode_s - x.admit_s for x in s) / len(s)
