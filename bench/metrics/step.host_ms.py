"""Model step: host time per decode step that the device does not
cover.  Over each pair of consecutive decode steps in the window with
no ``admit`` or ``prefill_chunk`` stage between them: the step period,
from one ``decode_step`` stage's start to the next one's, less the
first step's ``decode_sync`` stage, in which the host waits on the
device.  The mean over the pairs, in ms; None where the program times
no ``decode_sync``.  A pair in which the harness started or stopped the
profiler is left out: that pause, seconds long, is not the program's."""
from bisect import bisect_left


def _between(times, a, b) -> bool:
    """Whether any of the sorted ``times`` lies in ``[a, b)``."""
    k = bisect_left(times, a)
    return k < len(times) and times[k] < b


def read(rec):
    steps = sorted(rec.stages_in(("decode_step",), rec.t0, rec.close),
                   key=lambda s: s[1])
    sync = {attrs.get("step"): t1 - t0 for _, t0, t1, _, attrs
            in rec.stages_in(("decode_sync",), rec.t0, rec.close)}
    admits = sorted(t0 for name, t0, *_ in rec.stages
                    if name in ("admit", "prefill_chunk"))
    profiler = ([] if rec.trace is None
                else sorted((rec.trace.start, rec.trace.end)))
    host = []
    for (_, a0, _, _, a), (_, b0, _, _, b) in zip(steps, steps[1:]):
        n = a.get("step")
        if (n not in sync or b.get("step") != n + 1
                or _between(admits, a0, b0) or _between(profiler, a0, b0)):
            continue
        host.append(b0 - a0 - sync[n])
    return 1e3 * sum(host) / len(host) if host else None
