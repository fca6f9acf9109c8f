"""Model step: the algorithm's FLOPs of every token the window served,
per second of window, over the chip's peak bf16 rate.  A request's
prompt counts once, when its first token is stamped in the window; every
later token counts one decode at its context.  Only the prompt's own
tokens count, never padding."""
from bench import flops


def read(rec):
    d = flops.dims(rec.config)
    total = 0
    for r in rec.requests.values():
        for k, t in enumerate(r.stamps):
            if not rec.t0 <= t <= rec.close:
                continue
            total += (flops.prefill_flops(d, r.prompt_len) if k == 0
                      else flops.decode_flops(d, r.prompt_len + k))
    if total == 0:
        return None
    return 100.0 * total / rec.seconds / rec.peaks["bf16_flops_per_s"]
