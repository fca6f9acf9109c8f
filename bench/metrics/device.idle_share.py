"""Device: share of the traced stretch in which no operation ran on the
chip (1 - union of device op intervals / traced window), averaged over
the chips used."""


def read(rec):
    s = rec.trace
    if s is None or s.devices == 0 or s.window_s <= 0:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
