"""Device: share of the traced stretch's device-idle time that lies
outside every ``decode_sync``, ``decode_fetch`` and ``admit`` stage,
the phases in which the host waits on the device or on its copy back.
What remains is idle time spent on host work, in the engine's other
phases or above the engine.  None where the trace has no device or the
program times no ``decode_sync``."""
from bench.stats import union_length
from bench.trace import idle_gaps

WAITING = ("decode_sync", "decode_fetch", "admit")


def read(rec):
    s = rec.trace
    if s is None or s.devices == 0:
        return None
    if not any(st[0] == "decode_sync" for st in rec.stages):
        return None
    waits = [(max(t0, s.start), min(t1, s.end))
             for name, t0, t1, *_ in rec.stages
             if name in WAITING and t1 > s.start and t0 < s.end]
    idle = host = 0.0
    for dev in {d for *_, d in s.ops}:
        gaps = idle_gaps(s, dev)
        idle += union_length(gaps)
        # idle time outside every wait: |gaps or waits| - |waits|
        host += union_length(gaps + waits) - union_length(waits)
    return 100.0 * host / idle if idle > 0 else None
