"""Fixture metric: decode steps the engine ran in the window."""


def read(rec):
    c0, c1 = rec.counters
    return c1["decode_steps"] - c0["decode_steps"]
