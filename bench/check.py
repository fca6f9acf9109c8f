"""The comparison that decides ``correct``.

Two kinds of number, each held against a limit from the cell's
``bench/limits/<workload>.json``:

* ``bad_streams`` (exact, limit 0): requests whose streamed tokens
  differ from what the engine and the handle finally hold, or that
  streamed more than their ``max_new``, or that completed short of it.
* ``served_gap_std``: once the window has closed and the program's state
  is freed, a sample of the finished requests, drawn from the seed and
  always holding the longest, is run through the plain float32
  reference, prompt and served tokens together.  The number is the
  widest gap by which a served token's reference logit lies below the
  reference's best at that position, in units of that row's standard
  deviation.  It is valid because all traffic is greedy.
"""
from __future__ import annotations

import sys
from typing import Dict, List

import numpy as np

from bench import mix
from bench.cells import CellError


def streams(requests, handles, engine) -> Dict:
    """Count broken streams; list the requests served in full."""
    bad, finished = 0, []
    for rid, req in requests.items():
        h = handles.get(rid)
        n = len(req.tokens)
        if n > req.max_new:
            bad += 1
            continue
        if h is not None and h.done and h.admitted and not h.dropped:
            if n != req.max_new or list(h.tokens) != req.tokens:
                bad += 1
                continue
        if n == req.max_new:
            done = engine.done.get(rid)
            if done is None or list(np.asarray(done.output)) != req.tokens:
                bad += 1
                continue
            finished.append(rid)
    return {"bad": bad, "finished": finished}


def sample(record, finished: List[int], seed: int, min_requests: int,
           min_tokens: int) -> List[int]:
    """The longest finished request, then others in a seeded order until
    both minimums are met."""
    if not finished:
        return []
    reqs = record.requests
    longest = max(finished, key=lambda r: (reqs[r].padded_len
                                           + len(reqs[r].tokens), r))
    rest = [r for r in sorted(finished) if r != longest]
    order = np.random.default_rng([seed, 2]).permutation(len(rest))
    out = [longest]
    for i in order:
        if (len(out) >= min_requests
                and sum(len(reqs[r].tokens) for r in out) >= min_tokens):
            break
        out.append(rest[i])
    return out


def compare(cell, params, record, stream_check: Dict, seed: int,
            control: bool = False) -> Dict:
    """Every number compared, with its limit.  ``control``: the int8
    reference takes the served tokens' place (see ``bench/control.py``)."""
    limits = cell.limits
    for key in ("served_gap_std", "bad_streams"):
        if key not in limits:
            raise CellError(f"bench/limits/{cell.workload}.json lacks "
                            f"{key!r}")
    want = cell.traffic.get("check", {})
    picked = sample(record, stream_check["finished"], seed,
                    int(want.get("min_requests", 4)),
                    int(want.get("min_tokens", 512)))
    worst, agree, total, unlike = None, 0, 0, 0
    vocab = cell.config["vocab_size"]
    for rid in picked:
        r = record.requests[rid]
        prompt = mix.prompt_tokens(seed, rid, r.prompt_len, vocab)
        # the context as the configuration states it, not as the
        # program reports it; a program that pads otherwise reads wrong
        ctx = cell.reference.context(cell.config, cell.traffic["pool"],
                                     prompt)
        unlike += len(ctx) != r.padded_len
        seq = np.concatenate([ctx, np.asarray(r.tokens[:-1], np.int32)])
        gap, best = cell.reference.score(params, cell.config, seq,
                                         len(ctx) - 1,
                                         np.asarray(r.tokens, np.int32),
                                         control=control)
        worst = max(float(gap.max()), worst or 0.0)
        agree += int((best == np.asarray(r.tokens)).sum())
        total += len(r.tokens)
    print(f"bench: reference over {len(picked)} of "
          f"{len(stream_check['finished'])} finished requests, {total} "
          f"served tokens; greedy agreement {agree}/{total}; "
          f"{unlike} admitted at another context length than the "
          f"configuration's prompt_padding states",
          file=sys.stderr, flush=True)
    return {"served_gap_std": {"value": worst,
                               "limit": limits["served_gap_std"]},
            "bad_streams": {"value": stream_check["bad"],
                            "limit": limits["bad_streams"]}}
