"""Mean host time of one admission in the window, in ms: from the prefill
call to the first token in hand (prefill, insert, the token's transfer),
while every decoding lane waits."""


def read(rec):
    a = rec.window_admits()
    return 1e3 * sum(x.t1 - x.t0 for x in a) / len(a) if a else None
