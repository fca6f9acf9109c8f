"""Engine scheduler: wall time of the engine's ``admit`` and
``prefill_chunk`` stages in the window, in ms per 1000 prompt tokens
the callers sent for the requests those stages served.  Padding is not
counted as work, so padding waste shows as a higher number."""


def read(rec):
    stages = rec.stages_in(("admit", "prefill_chunk"), rec.t0, rec.close)
    rids = {rid for _, _, _, ids, _ in stages for rid in ids}
    tokens = sum(rec.requests[rid].prompt_len for rid in rids
                 if rid in rec.requests)
    if not tokens:
        return None
    return 1e3 * sum(t1 - t0 for _, t0, t1, _, _ in stages) / (
        tokens / 1e3)
