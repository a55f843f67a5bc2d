"""Seconds from the start of the process to the window's opening: imports,
weights, engine, loading or compiling every program, warm-up and the
steady state."""


def read(rec):
    return rec.setup_s
