"""Model step: mean wall duration of the engine's ``decode_step``
stage over the window.  The stage ends when the step's tokens reach the
host, so it holds the device time of the step and the host work around
its dispatch."""


def read(rec):
    steps = rec.stages_in(("decode_step",), rec.t0, rec.close)
    if not steps:
        return None
    return 1e3 * sum(t1 - t0 for _, t0, t1, _, _ in steps) / len(steps)
