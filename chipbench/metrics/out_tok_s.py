"""Output tokens emitted in the window over the window's seconds (host
clock; every token of every request, the whole window)."""


def read(rec):
    n = sum(rec.t_open <= t <= rec.t_close
            for r in rec.reqs.values() for t in r.times)
    return n / (rec.t_close - rec.t_open)
