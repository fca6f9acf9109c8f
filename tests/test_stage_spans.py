"""Stage spans of the engine and the executor on a tiny config: each
decode step's phases tile it in order and carry its index,
``decode_step`` runs from the dispatch to the fetch, a chunked prompt's
last chunk holds the copy of its first token to the host, an unhooked
engine stamps nothing and serves the same tokens, and the executor's
tracer relay chains to the hook it finds and puts it back."""
import time

import jax
import numpy as np
import pytest

from repro.models import transformer as T
from repro.runtime import serve
from repro.runtime.serve import ContinuousBatchingEngine, Request
from repro.serving import FleetSpec, PoolSpec

from conftest import tiny_dense

PROMPT_LEN, MAX_LEN, BLOCK, CHUNK = 8, 48, 4, 8
PHASES = ("decode_prepare", "decode_dispatch", "decode_sync",
          "decode_fetch", "decode_bookkeep")


@pytest.fixture(scope="module")
def model():
    cfg = tiny_dense()
    return cfg, T.model_init(jax.random.PRNGKey(0), cfg)


class Recording:
    """A stage hook that keeps every call."""

    def __init__(self):
        self.stages = []

    def __call__(self, stage, t0, t1, rids, attrs):
        self.stages.append((stage, t0, t1, list(rids), dict(attrs)))

    def named(self, stage):
        return [s for s in self.stages if s[0] == stage]


def serve_all(model, hook=None, wrap=None):
    """Two bucket prompts and one of three chunks into two slots, so the
    chunked one is admitted between decode steps; returns the engine and
    every output."""
    cfg, params = model
    eng = ContinuousBatchingEngine(params, cfg, max_slots=2,
                                   prompt_len=PROMPT_LEN, max_len=MAX_LEN,
                                   block_size=BLOCK, prefill_chunk=CHUNK)
    if wrap is not None:
        wrap(eng)
    eng.on_stage = hook
    rng = np.random.default_rng(11)
    for rid, (n, max_new) in enumerate([(5, 6), (7, 4),
                                        (3 * CHUNK - 2, 5)]):
        eng.submit(Request(rid, rng.integers(0, 256, n).astype(np.int32),
                           max_new=max_new))
    for _ in range(500):
        if not eng.pending:
            break
        eng.step()
    assert not eng.pending, "engine failed to make progress"
    return eng, {rid: r.output for rid, r in eng.done.items()}


def test_decode_phases_tile_each_step_in_order(model):
    rec = Recording()
    eng, _ = serve_all(model, rec)
    phases = {}
    for stage, t0, t1, rids, attrs in rec.stages:
        if stage in PHASES:
            assert rids == []                  # batch-wide phases
            phases.setdefault(attrs["step"], []).append((stage, t0, t1))
    # every step's phases, emitted in order, each under its step index
    assert sorted(phases) == list(range(eng.decode_steps))
    steps = rec.named("decode_step")
    assert [s[4]["step"] for s in steps] == list(range(eng.decode_steps))
    for _, t0, t1, rids, attrs in steps:
        ph = phases[attrs["step"]]
        assert [p[0] for p in ph] == list(PHASES)
        for (_, a0, a1), (_, b0, b1) in zip(ph, ph[1:]):
            assert a0 <= a1 == b0 <= b1        # starts where the last ended
        # decode_step spans the dispatch to the fetch exactly
        assert t0 == ph[1][1] and t1 == ph[3][2]
        assert attrs["tokens"] == len(rids) > 0


def test_last_chunk_holds_the_first_token_fetch(model):
    """A chunked prompt's chunk stages run from its first dispatch to
    its first token on the host, and the token reaches its stream
    after that."""
    fetched, emitted = [], []

    class Token:
        """The last chunk's device token, stamped when copied out."""

        def __init__(self, arr):
            self.arr = arr

        def __array__(self, dtype=None, copy=None):
            fetched.append(time.perf_counter())
            return np.asarray(self.arr)

    def wrap(eng):
        step = eng._chunk_step

        def chunk_step(*a):
            firsts, caches = step(*a)
            return Token(firsts), caches
        eng._chunk_step = chunk_step
        eng.on_token = lambda rid, tok: (
            emitted.append(time.perf_counter()) if rid == 2 else None)

    rec = Recording()
    serve_all(model, rec, wrap)
    chunks = rec.named("prefill_chunk")
    assert [c[4]["chunk"] for c in chunks] == [0, 1, 2]
    assert all(c[3] == [2] for c in chunks)
    for a, b in zip(chunks, chunks[1:]):
        assert a[2] == b[1]
    assert len(fetched) == 1
    assert chunks[-1][1] <= fetched[0] <= chunks[-1][2] <= emitted[0]


def test_unhooked_engine_stamps_nothing_and_serves_the_same(model,
                                                           monkeypatch):
    made, annotated, synced = [], [], []

    class CountingSpan(serve.StageSpan):
        def __init__(self, hook, stage, *a):
            made.append(stage)
            super().__init__(hook, stage, *a)
    annotation = jax.profiler.TraceAnnotation

    def counting_annotation(name):
        annotated.append(name)
        return annotation(name)

    def wrap(eng):
        decode = eng._decode

        def watched(*a):
            nxt, caches = decode(*a)
            ready = nxt.block_until_ready

            class Watched:
                def block_until_ready(self):
                    synced.append(1)
                    return ready()

                def __array__(self, dtype=None, copy=None):
                    return np.asarray(nxt)
            return Watched(), caches
        eng._decode = watched
    monkeypatch.setattr(serve, "StageSpan", CountingSpan)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        counting_annotation)
    _, hooked = serve_all(model, Recording(), wrap)
    assert {"decode_sync", "prefill_chunk", "admit_plan"} <= set(made)
    assert "engine.decode_sync" in annotated and synced
    for seen in (made, annotated, synced):
        seen.clear()
    eng, plain = serve_all(model, None, wrap)
    assert made == [] and annotated == [] and synced == []
    assert eng.decode_steps > 0 and eng.decode_s > 0 and eng.admit_s > 0
    assert sorted(plain) == sorted(hooked) == [0, 1, 2]
    for rid in plain:
        np.testing.assert_array_equal(plain[rid], hooked[rid])


def test_tracer_relay_chains_to_the_hook_it_finds(model):
    client = FleetSpec(
        pools=[PoolSpec("lm", ("tpu_v5e_bf16",), capacity=1,
                        max_window=4, max_wait_s=0.0, max_slots=3,
                        prompt_len=PROMPT_LEN, max_new=6,
                        max_prompt_len=4 * PROMPT_LEN, backend="engine")],
        workload="transformer", seq_len=PROMPT_LEN).build(model=model)
    client.enable_tracing()
    engine = client.engines["lm"]
    rec = Recording()
    engine.on_stage = rec
    rng = np.random.default_rng(7)
    handles = [client.submit(rng.integers(0, 256, n).astype(np.int32),
                             max_new=3) for n in (2 * PROMPT_LEN + 3, 5)]
    client.drain()
    assert engine.on_stage is rec               # put back, not cleared
    got = {s[0] for s in rec.stages}
    assert {"batch", "admit_plan", "admit", "prefill_chunk",
            "decode_step", *PHASES} <= got
    # the subscriber saw every stage the tracer did, and the batch the
    # tracer holds as ``serve`` was not forwarded to it
    traced = {sp.stage for sp in client.tracer.spans
              if sp.stage not in ("request", "queue", "serve")}
    assert traced and traced <= got and "batch" not in traced
    held = {rid for s in rec.named("batch") for rid in s[3]}
    assert held == {h.rid for h in handles}
    assert all(sp.rid is None for sp in client.tracer.spans
               if sp.stage in PHASES)
    # a later batch with the tracer off still reaches the subscriber
    client.tracer.enabled = False
    n = len(rec.named("batch"))
    client.submit(np.arange(4, dtype=np.int32), max_new=2)
    client.drain()
    assert len(rec.named("batch")) == n + 1 and engine.on_stage is rec
