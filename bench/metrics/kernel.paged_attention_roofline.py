"""Kernels: the paged decode attention kernel's share of its roofline
in the traced stretch.  For each decode step whose stage lies wholly in
the trace, the least time the chip could take for the attention the
step needs (live tokens' keys and values per KV head, the query and the
output, at the published peaks) is summed, and divided by the summed
device time of the kernel's events inside those steps."""
from bench import flops

KERNEL = "paged_attention_pallas"    # the Pallas kernel's op name


def read(rec):
    s = rec.trace
    if s is None:
        return None
    d = flops.dims(rec.config)
    need = busy = 0.0
    for _, t0, t1, rids, _ in rec.stages_in(("decode_step",), s.start,
                                            s.end):
        ctx = []
        for rid in rids:
            r = rec.requests.get(rid)
            if r is not None:
                ctx.append(r.prompt_len + sum(1 for t in r.stamps if t < t0))
        f, b = flops.paged_attention_cost(d, ctx)
        k = sum(e - a for name, a, e, _ in s.ops
                if name == KERNEL and t0 <= a and e <= t1)
        if k > 0:
            need += flops.least_time_s(f, b, rec.peaks)
            busy += k
    return None if busy == 0 else 100.0 * need / busy
