"""Facade and router: median wait of every request due in the window,
from when it was due (open loop) or sent (closed loop) to the start of
the engine's first ``admit`` or ``prefill_chunk`` stage that holds it.
A request the engine has not begun by the close enters with its wait
so far, as time to first token does."""
from bench import stats


def read(rec):
    due = rec.attempted()
    if not due:
        return None
    begun = {}
    for _, t0, _, rids, _ in rec.stages_in(("admit", "prefill_chunk"),
                                           rec.t0, rec.close):
        for rid in rids:
            begun[rid] = min(t0, begun.get(rid, t0))
    return stats.percentile(stats.censored_waits(
        [r.due for r in due], [begun.get(r.rid) for r in due], rec.close),
        50)
