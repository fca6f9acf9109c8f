"""Device: ``peak_bytes_in_use`` of the fullest chip after the window,
in GB (1e9 bytes)."""


def read(rec):
    if not rec.memory_peak_bytes:
        return None
    return rec.memory_peak_bytes / 1e9
