"""The one general traffic generator: a traffic file's parameters in,
a seeded request stream out.

Every seed gets the same work in the same order: one schedule of
lengths and arrival gaps per traffic file, like a trace replayed, and
the seed draws the prompt tokens (and the harness the weights).  The
order is not the seed's because it decides the work: a batch runs to
its longest output, so which requests arrive together moves the median
time to first token by some 40% from seed to seed, where two runs of
one seed agree within 3%.  The schedule is drawn block-stratified: each
block of ``block`` consecutive requests takes the distribution's values
at the ``block`` mid-quantiles ``(i + 0.5) / block``, permuted by a
fixed key, so any window that spans a few blocks sees nearly the mix's
own lengths and gaps.  Prompt tokens are drawn per request from
``(seed, index)``, so a stream is cheap to extend.

Traffic file keys (all lengths in tokens):

* ``loop``: ``"open"`` (Poisson arrivals at ``rate_per_s``, each request
  timed from when it was due) or ``"closed"`` (``clients`` callers, each
  sending its next request when the last one completes);
* ``prompt`` / ``output``: ``{"dist": "lognormal", "median", "sigma",
  "min", "max"}`` or ``{"dist": "uniform", "min", "max"}``;
* ``block``: stratification block (default 32).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List

import numpy as np

_NORMAL = NormalDist()
SCHEDULE_KEY = 0          # the schedule's permutations; not the seed


@dataclass(frozen=True)
class Item:
    index: int
    prompt_len: int
    max_new: int
    due_s: float            # open loop: offset from the window start


def quantiles(spec: Dict, n: int) -> np.ndarray:
    """The distribution's values at ``n`` mid-quantiles, clipped and
    rounded to whole tokens."""
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(p)) for p in q])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        vals = spec["min"] + q * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    vals = np.clip(np.rint(vals), spec["min"], spec["max"])
    return vals.astype(np.int64)


def _stratified(base: np.ndarray, n: int, rng) -> np.ndarray:
    blocks = -(-n // len(base))
    return np.concatenate([rng.permutation(base)
                           for _ in range(blocks)])[:n]


def stream(traffic: Dict, n: int) -> List[Item]:
    """The first ``n`` requests of the mix's schedule, the same for
    every seed."""
    block = int(traffic.get("block", 32))

    def rng(k):            # one stream each, so that a longer draw
        return np.random.default_rng([SCHEDULE_KEY, 0, k])   # extends
    prompt = _stratified(quantiles(traffic["prompt"], block), n, rng(0))
    output = _stratified(quantiles(traffic["output"], block), n, rng(1))
    due = np.zeros(n)
    if traffic["loop"] == "open":
        rate = float(traffic["rate_per_s"])
        q = (np.arange(block) + 0.5) / block
        gaps = _stratified(-np.log1p(-q) / rate, n, rng(2))
        due = np.cumsum(gaps)
    elif traffic["loop"] != "closed":
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    return [Item(i, int(prompt[i]), int(output[i]), float(due[i]))
            for i in range(n)]


def prompt_tokens(seed: int, index: int, length: int,
                  vocab: int) -> np.ndarray:
    """Prompt ``index`` of ``seed``: ids in ``[1, vocab)`` (0 is the
    program's pad token)."""
    rng = np.random.default_rng([seed, 1, index])
    return rng.integers(1, vocab, length).astype(np.int32)


def stream_length(traffic: Dict, seconds: float) -> int:
    """Requests to draw at first; ``stream`` of a longer length extends
    it, for a closed loop that runs through them."""
    block = int(traffic.get("block", 32))
    if traffic["loop"] == "open":
        need = float(traffic["rate_per_s"]) * seconds * 1.5
    else:
        need = int(traffic["clients"]) * 8
    return block * max(2, math.ceil(need / block) + 1)
