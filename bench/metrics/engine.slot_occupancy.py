"""Engine scheduler: mean share of decode slots occupied per decode
step over the window, from the engine's own counters
(``occupancy_sum`` / ``decode_steps``, deltas across the window)."""


def read(rec):
    c0, c1 = rec.counters
    steps = c1.get("decode_steps", 0) - c0.get("decode_steps", 0)
    if steps <= 0:
        return None
    return 100.0 * (c1["occupancy_sum"] - c0["occupancy_sum"]) / steps
