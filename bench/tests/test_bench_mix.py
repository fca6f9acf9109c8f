"""The traffic generator repeats for a seed, differs across seeds in its
prompt tokens, and gives every seed the same schedule of work."""
import json

import numpy as np

from bench import mix
from bench.tests.helpers import REPO

BIG = 2 ** 31 + 987654321


def traffic(name):
    return json.loads((REPO / f"bench/traffic/{name}.json").read_text())


def test_same_seed_same_stream():
    t = traffic("chat")
    assert mix.stream(t, 200) == mix.stream(t, 200)
    a = mix.prompt_tokens(BIG, 3, 50, 1000)
    assert (a == mix.prompt_tokens(BIG, 3, 50, 1000)).all()
    assert a.min() >= 1 and a.max() < 1000


def test_seeds_differ_in_order_and_tokens_not_in_work():
    """Seeds differ in their prompt tokens only: the schedule of lengths
    and arrivals is one for every seed, and each block of 32 holds the
    mix's mid-quantiles in a fixed order."""
    t = traffic("chat")
    a = mix.stream(t, 256)
    q = sorted(mix.quantiles(t["prompt"], 32))
    for lo in range(0, 256, 32):
        assert sorted(x.prompt_len for x in a[lo:lo + 32]) == q
    first = [x.prompt_len for x in a[:32]]
    assert first != sorted(first) and first != [x.prompt_len
                                                for x in a[32:64]]
    assert not (mix.prompt_tokens(1, 0, 64, 1000)
                == mix.prompt_tokens(2, 0, 64, 1000)).all()


def test_lengths_follow_the_file():
    for name in ("chat", "decode-long-32slots"):
        t = traffic(name)
        s = mix.stream(t, 320)
        p = np.array([x.prompt_len for x in s])
        o = np.array([x.max_new for x in s])
        assert p.min() >= t["prompt"]["min"] and p.max() <= t["prompt"]["max"]
        assert o.min() >= t["output"]["min"] and o.max() <= t["output"]["max"]
        assert p.max() <= t["pool"].get("max_prompt_len",
                                         t["pool"]["prompt_len"])
        assert o.max() <= t["pool"]["max_new"]
    chat = mix.stream(traffic("chat"), 3200)
    assert abs(np.median([x.prompt_len for x in chat]) - 256) < 16
    rate = 3200 / chat[-1].due_s
    assert abs(rate - traffic("chat")["rate_per_s"]) < 0.05 * rate


def test_a_longer_draw_extends_a_shorter_one():
    t = traffic("decode-long-32slots")
    assert mix.stream(t, 64) == mix.stream(t, 128)[:64]
