"""Continuous-batching engine: per-request outputs must be identical to
solo windowed flush() runs regardless of admission order; exact max_new
accounting; OutOfBlocks deferral; attention-only guard; per-request
sampling determinism; the deprecated BatchingServer shim."""
import numpy as np
import jax
import pytest

from repro.models import transformer as T
from repro.runtime.paging import OutOfBlocksError
from repro.runtime.sampling import SamplingParams
from repro.runtime.serve import (BatchingServer, ContinuousBatchingEngine,
                                 Request, WindowedBaselineServer)

from conftest import tiny_dense

PROMPT_LEN, MAX_LEN, BLOCK = 8, 16, 4


@pytest.fixture(scope="module")
def model():
    cfg = tiny_dense()
    params = T.model_init(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def workload():
    rng = np.random.default_rng(3)
    return [(i,
             rng.integers(0, 256, int(rng.integers(2, PROMPT_LEN))
                          ).astype(np.int32),
             int(rng.integers(1, MAX_LEN - PROMPT_LEN + 1)))
            for i in range(6)]


@pytest.fixture(scope="module")
def solo_reference(model, workload):
    """Each request served alone by the windowed baseline."""
    cfg, params = model
    ref = {}
    for rid, prompt, max_new in workload:
        srv = WindowedBaselineServer(params, cfg, max_batch=1,
                                     prompt_len=PROMPT_LEN, max_len=MAX_LEN)
        srv.submit(Request(rid, prompt, max_new=max_new))
        srv.flush()
        ref[rid] = srv.done[rid].output
    return ref


def _drain(engine):
    done = []
    steps = 0
    while engine.pending:
        done += engine.step()
        steps += 1
        assert steps < 1000, "engine failed to make progress"
    return done


# admission orders: arrival, reversed, and an interleave — outputs must
# not depend on which requests shared a decode batch
@pytest.mark.parametrize("order", [
    [0, 1, 2, 3, 4, 5], [5, 4, 3, 2, 1, 0], [3, 0, 5, 1, 4, 2]])
def test_outputs_match_solo_flush_any_admission_order(model, workload,
                                                      solo_reference, order):
    cfg, params = model
    eng = ContinuousBatchingEngine(params, cfg, max_slots=3,
                                   prompt_len=PROMPT_LEN, max_len=MAX_LEN,
                                   block_size=BLOCK)
    for idx in order:
        rid, prompt, max_new = workload[idx]
        eng.submit(Request(rid, prompt, max_new=max_new))
    done = _drain(eng)
    assert len(done) == len(workload)
    for rid, prompt, max_new in workload:
        out = eng.done[rid].output
        assert out.shape == (max_new,)          # max_new honored exactly
        np.testing.assert_array_equal(out, solo_reference[rid])


def _pools(eng):
    return [a for st in eng.caches.values() for a in (st.k_pool, st.v_pool)]


@pytest.mark.parametrize("harden", [False, True])
def test_steps_donate_the_pool(model, workload, solo_reference, harden):
    """Bucket admission and decode (plain or hardened) donate the caches:
    after every step the pools the engine held before it are deleted, so
    the pool is held once, and the outputs still match the solo runs."""
    cfg, params = model
    eng = ContinuousBatchingEngine(params, cfg, max_slots=3,
                                   prompt_len=PROMPT_LEN, max_len=MAX_LEN,
                                   block_size=BLOCK, harden=harden)
    for rid, prompt, max_new in workload:
        eng.submit(Request(rid, prompt, max_new=max_new))
    steps = 0
    while eng.pending:
        before = _pools(eng)
        eng.step()
        assert all(a.is_deleted() for a in before)
        assert not any(a.is_deleted() for a in _pools(eng))
        steps += 1
        assert steps < 1000, "engine failed to make progress"
    for rid, _, _ in workload:
        np.testing.assert_array_equal(eng.done[rid].output,
                                      solo_reference[rid])


def test_two_segment_plan_matches_one_segment(model, workload,
                                              solo_reference):
    """A plan of two segments hands each the whole pool at its layer
    offset: the tokens are those of the one-segment runs."""
    from repro.core.partition import PartitionPlan, Segment
    from repro.core.precision import PrecisionPolicy
    cfg, params = model
    bf16 = PrecisionPolicy.bf16()
    plan = PartitionPlan((Segment("a", 0, 1, bf16), Segment("b", 1, 2, bf16)))
    eng = ContinuousBatchingEngine(params, cfg, plan, max_slots=3,
                                   prompt_len=PROMPT_LEN, max_len=MAX_LEN,
                                   block_size=BLOCK)
    for rid, prompt, max_new in workload:
        eng.submit(Request(rid, prompt, max_new=max_new))
    _drain(eng)
    for rid, _, _ in workload:
        np.testing.assert_array_equal(eng.done[rid].output,
                                      solo_reference[rid])


def test_mid_decode_admission_and_exact_steps(model):
    """A long request decodes while short ones churn through the freed
    slots; total decode steps stay well under the windowed equivalent."""
    cfg, params = model
    eng = ContinuousBatchingEngine(params, cfg, max_slots=2,
                                   prompt_len=PROMPT_LEN, max_len=MAX_LEN,
                                   block_size=BLOCK)
    prompts = np.random.default_rng(0).integers(
        0, 256, (5, 4)).astype(np.int32)
    eng.submit(Request(0, prompts[0], max_new=8))
    for i in range(1, 5):
        eng.submit(Request(i, prompts[i], max_new=2))
    _drain(eng)
    assert len(eng.done) == 5
    for i in range(1, 5):
        assert eng.done[i].output.shape == (2,)
    # windowed max_batch=2 would burn >= 3 windows x max(max_new) steps;
    # continuous: the rid-0 slot runs 8 steps total while the other slot
    # serves all four short requests
    assert eng.stats()["decode_steps"] <= 9
    assert eng.stats()["total_tokens"] == 8 + 4 * 2


def test_admission_defers_on_block_exhaustion(model):
    """Pool sized for one max-length request: admission falls back to
    one-at-a-time instead of crashing, and everything completes."""
    cfg, params = model
    eng = ContinuousBatchingEngine(params, cfg, max_slots=3,
                                   prompt_len=PROMPT_LEN, max_len=MAX_LEN,
                                   block_size=BLOCK,
                                   num_blocks=MAX_LEN // BLOCK)
    for i in range(3):
        eng.submit(Request(i, np.array([1, 2, 3], np.int32), max_new=4))
    assert eng.occupancy == 0.0
    done = _drain(eng)
    assert len(done) == 3
    for r in done:
        assert r.output.shape == (4,)


def test_single_token_requests_complete_at_admission(model):
    cfg, params = model
    eng = ContinuousBatchingEngine(params, cfg, max_slots=2,
                                   prompt_len=PROMPT_LEN, max_len=MAX_LEN,
                                   block_size=BLOCK)
    eng.submit(Request(0, np.array([5], np.int32), max_new=1))
    # max_new=0 completes with empty output, like the windowed baseline
    eng.submit(Request(1, np.array([5], np.int32), max_new=0))
    done = eng.step()
    assert sorted(r.rid for r in done) == [0, 1]
    assert eng.done[0].output.shape == (1,)
    assert eng.done[1].output.shape == (0,)
    assert eng.alloc.available == eng.alloc.num_blocks   # all blocks freed


def test_blocks_recycle_across_requests(model, workload):
    cfg, params = model
    eng = ContinuousBatchingEngine(params, cfg, max_slots=2,
                                   prompt_len=PROMPT_LEN, max_len=MAX_LEN,
                                   block_size=BLOCK)
    for rid, prompt, max_new in workload:
        eng.submit(Request(rid, prompt, max_new=max_new))
    _drain(eng)
    assert eng.alloc.available == eng.alloc.num_blocks
    assert (eng.table == -1).all()


def test_paged_decode_rejects_non_attention_stacks(model):
    cfg, params = model
    hybrid = tiny_dense(mixer="mamba")
    with pytest.raises(ValueError, match="attention-only"):
        ContinuousBatchingEngine(params, hybrid, max_slots=2,
                                 prompt_len=PROMPT_LEN, max_len=MAX_LEN,
                                 block_size=BLOCK)


def test_out_of_blocks_is_typed_and_atomic():
    from repro.runtime.paging import BlockAllocator, plan_blocks
    alloc = BlockAllocator(4)
    table = -np.ones((2, 8), np.int32)
    with pytest.raises(OutOfBlocksError):
        plan_blocks(table, alloc, [3, 3])
    assert alloc.available == 4            # nothing leaked
    assert (table == -1).all()             # caller's table untouched


# ---------------------------------------------------------------------------
# per-request sampling
# ---------------------------------------------------------------------------
def _run(engine, reqs):
    for r in reqs:
        engine.submit(r)
    while engine.pending:
        engine.step()
    return engine.done


def test_sampling_deterministic_and_batch_invariant(model, workload):
    """Same seed -> same tokens, whether the request decodes solo or
    packed into slots with strangers (keys fold (seed, token index),
    never batch position or composition)."""
    cfg, params = model
    sp = SamplingParams(temperature=1.0, top_k=8, seed=42)
    _, prompt, _ = workload[0]

    def engine():
        return ContinuousBatchingEngine(params, cfg, max_slots=3,
                                        prompt_len=PROMPT_LEN,
                                        max_len=MAX_LEN, block_size=BLOCK)
    solo = _run(engine(), [Request(0, prompt, max_new=6, sampling=sp)])
    again = _run(engine(), [Request(0, prompt, max_new=6, sampling=sp)])
    np.testing.assert_array_equal(solo[0].output, again[0].output)
    mixed = _run(engine(), [
        Request(7, workload[1][1], max_new=3),
        Request(0, prompt, max_new=6, sampling=sp),
        Request(8, workload[2][1], max_new=2),
    ])
    np.testing.assert_array_equal(mixed[0].output, solo[0].output)


def test_sampled_requests_do_not_perturb_greedy_neighbors(
        model, workload, solo_reference):
    """Greedy requests sharing a batch with a sampled one still match
    their solo-greedy goldens (sampling defaults keep goldens intact)."""
    cfg, params = model
    eng = ContinuousBatchingEngine(params, cfg, max_slots=3,
                                   prompt_len=PROMPT_LEN, max_len=MAX_LEN,
                                   block_size=BLOCK)
    sp = SamplingParams(temperature=0.9, seed=3)
    reqs = [Request(workload[0][0], workload[0][1],
                    max_new=workload[0][2]),
            Request(99, workload[1][1], max_new=6, sampling=sp),
            Request(workload[2][0], workload[2][1],
                    max_new=workload[2][2])]
    done = _run(eng, reqs)
    for rid, _, max_new in (workload[0], workload[2]):
        np.testing.assert_array_equal(done[rid].output,
                                      solo_reference[rid])
    assert done[99].output.shape == (6,)


def test_sampling_respects_top_k_one(model, workload):
    """top_k=1 at any temperature is argmax — must equal the greedy run."""
    cfg, params = model
    rid, prompt, _ = workload[3]

    def engine():
        return ContinuousBatchingEngine(params, cfg, max_slots=2,
                                        prompt_len=PROMPT_LEN,
                                        max_len=MAX_LEN, block_size=BLOCK)
    greedy = _run(engine(), [Request(0, prompt, max_new=5)])
    topk1 = _run(engine(), [Request(0, prompt, max_new=5,
                                    sampling=SamplingParams(
                                        temperature=2.0, top_k=1,
                                        seed=11))])
    np.testing.assert_array_equal(topk1[0].output, greedy[0].output)


# ---------------------------------------------------------------------------
# deprecated windowed entry point
# ---------------------------------------------------------------------------
def test_batching_server_shim_warns_and_forwards_to_engine(model):
    cfg, params = model
    with pytest.warns(DeprecationWarning, match="repro.serving"):
        srv = BatchingServer(params, cfg, max_batch=2,
                             prompt_len=PROMPT_LEN, max_len=MAX_LEN)
    assert isinstance(srv, ContinuousBatchingEngine)
    srv.submit(Request(0, np.array([1, 2, 3], np.int32), max_new=3))
    srv.flush()
    assert srv.done[0].output.shape == (3,)


def test_batching_server_shim_falls_back_for_non_pageable_stacks():
    hybrid = tiny_dense(mixer="mamba")
    params = T.model_init(jax.random.PRNGKey(0), hybrid)
    with pytest.warns(DeprecationWarning):
        srv = BatchingServer(params, hybrid, max_batch=2,
                             prompt_len=PROMPT_LEN, max_len=MAX_LEN)
    assert isinstance(srv, WindowedBaselineServer)


# ---------------------------------------------------------------------------
# accounting bugfixes
# ---------------------------------------------------------------------------
def test_max_new_zero_emits_no_tokens_and_counts_none(model):
    """Regression: the admission token used to be counted into
    total_tokens even for max_new=0 requests, whose token is never
    emitted — inflating tokens/s."""
    cfg, params = model
    eng = ContinuousBatchingEngine(params, cfg, max_slots=2,
                                   prompt_len=PROMPT_LEN, max_len=MAX_LEN,
                                   block_size=BLOCK)
    emitted = []
    eng.on_token = lambda rid, tok: emitted.append((rid, tok))
    eng.submit(Request(0, np.array([5, 6], np.int32), max_new=0))
    done = eng.step()
    assert [r.rid for r in done] == [0]
    assert eng.done[0].output.shape == (0,)
    assert eng.stats()["total_tokens"] == 0
    assert emitted == []                 # counted tokens == emitted tokens
    # a real request afterwards counts exactly its own tokens
    eng.submit(Request(1, np.array([5, 6], np.int32), max_new=3))
    _drain(eng)
    assert eng.stats()["total_tokens"] == 3
    assert [rid for rid, _ in emitted] == [1, 1, 1]


def test_empty_prompt_rejected_at_submit(model):
    cfg, params = model
    eng = ContinuousBatchingEngine(params, cfg, max_slots=2,
                                   prompt_len=PROMPT_LEN, max_len=MAX_LEN,
                                   block_size=BLOCK)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(Request(0, np.zeros((0,), np.int32), max_new=2))
    srv = WindowedBaselineServer(params, cfg, max_batch=2,
                                 prompt_len=PROMPT_LEN, max_len=MAX_LEN)
    with pytest.raises(ValueError, match="empty prompt"):
        srv.submit(Request(0, np.zeros((0,), np.int32), max_new=2))


# ---------------------------------------------------------------------------
# chunked paged prefill (prompts beyond the prompt_len bucket)
# ---------------------------------------------------------------------------
LONG_MAX_LEN = 48


def _long_engine(model, **kw):
    cfg, params = model
    kw.setdefault("max_slots", 3)
    kw.setdefault("prompt_len", PROMPT_LEN)
    kw.setdefault("max_len", LONG_MAX_LEN)
    kw.setdefault("block_size", BLOCK)
    return ContinuousBatchingEngine(params, cfg, **kw)


def test_chunked_prefill_matches_windowed_at_full_length(model):
    """A prompt longer than the prompt_len bucket admits via chunked
    paged prefill and decodes to exactly the tokens of a windowed
    baseline whose bucket spans the whole (padded) prompt."""
    cfg, params = model
    prompt = np.random.default_rng(11).integers(0, 256, 21).astype(np.int32)
    eng = _long_engine(model)
    eng.submit(Request(0, prompt, max_new=6))
    _drain(eng)
    padded = eng.padded_prompt_len(21)          # 24 for chunk 8
    ref = WindowedBaselineServer(params, cfg, max_batch=1,
                                 prompt_len=padded, max_len=padded + 8)
    ref.submit(Request(0, prompt, max_new=6))
    ref.flush()
    np.testing.assert_array_equal(eng.done[0].output, ref.done[0].output)
    assert eng.alloc.available == eng.alloc.num_blocks


def test_chunked_prefill_donates_the_pool(model):
    """Each prefill chunk donates the caches it is handed."""
    prompt = np.random.default_rng(12).integers(0, 256, 21).astype(np.int32)
    eng = _long_engine(model)
    eng.submit(Request(0, prompt, max_new=2))
    chunk_step, donated = eng._chunk_step, []

    def watched(*a):
        out = chunk_step(*a)
        donated.append(all(x.is_deleted() for st in a[2].values()
                           for x in (st.k_pool, st.v_pool)))
        return out
    eng._chunk_step = watched
    _drain(eng)
    assert donated == [True] * 3                # 24 padded tokens, chunk 8


def test_chunked_prefill_mixed_with_bucket_admissions(model):
    """Long and short prompts share slots and decode batches; outputs
    match their solo runs and every block recycles."""
    cfg, params = model
    rng = np.random.default_rng(4)
    reqs = [(0, rng.integers(0, 256, 30).astype(np.int32), 5),
            (1, rng.integers(0, 256, 4).astype(np.int32), 3),
            (2, rng.integers(0, 256, 17).astype(np.int32), 7)]
    eng = _long_engine(model)
    for rid, p, mn in reqs:
        eng.submit(Request(rid, p, max_new=mn))
    _drain(eng)
    for rid, p, mn in reqs:
        solo = _long_engine(model)
        solo.submit(Request(rid, p, max_new=mn))
        _drain(solo)
        np.testing.assert_array_equal(eng.done[rid].output,
                                      solo.done[rid].output)
    assert eng.alloc.available == eng.alloc.num_blocks
    assert (eng.table == -1).all()


def test_chunked_prefill_defers_on_block_exhaustion(model):
    """A pool sized for one long request serves them one at a time."""
    cfg, params = model
    prompt = np.random.default_rng(5).integers(0, 256, 20).astype(np.int32)
    eng = _long_engine(model, max_slots=3,
                       num_blocks=LONG_MAX_LEN // BLOCK)
    for i in range(3):
        eng.submit(Request(i, prompt, max_new=4))
    done = _drain(eng)
    assert len(done) == 3
    assert eng.stats()["deferrals"] > 0
    assert eng.alloc.available == eng.alloc.num_blocks


def test_prompt_over_max_len_rejected(model):
    eng = _long_engine(model)
    with pytest.raises(AssertionError):
        eng.submit(Request(0, np.arange(LONG_MAX_LEN + 1, dtype=np.int32),
                           max_new=4))


# ---------------------------------------------------------------------------
# prefix-block sharing (content-hashed index)
# ---------------------------------------------------------------------------
def test_prefix_sharing_reuses_live_blocks_without_changing_outputs(model):
    cfg, params = model
    prompt = np.random.default_rng(6).integers(0, 256, 24).astype(np.int32)
    eng = _long_engine(model)
    eng.submit(Request(0, prompt, max_new=6))
    eng.submit(Request(1, prompt, max_new=6))
    eng.step()                       # both admitted into one round
    st = eng.stats()
    # the second request's chunks[:-1] (16 tokens = 4 blocks) came from
    # the index, and only its final chunk was recomputed
    assert st["shared_block_hits"] == 16 // BLOCK
    assert st["prefill_tokens"] == 24 + 8
    _drain(eng)
    np.testing.assert_array_equal(eng.done[0].output, eng.done[1].output)
    solo = _long_engine(model)
    solo.submit(Request(9, prompt, max_new=6))
    _drain(solo)
    np.testing.assert_array_equal(eng.done[0].output, solo.done[9].output)
    # refcounts drained: every block is back in the pool and the index
    # holds nothing once the last sharer finished
    assert eng.alloc.available == eng.alloc.num_blocks
    assert eng.shared.release([]) == []


def test_prefix_sharing_survives_first_owner_finishing_last(model):
    """The owner releasing before the sharer must not free shared
    blocks under the still-running request (refcount, not ownership)."""
    cfg, params = model
    prompt = np.random.default_rng(8).integers(0, 256, 24).astype(np.int32)
    eng = _long_engine(model, max_slots=2)
    eng.submit(Request(0, prompt, max_new=1))    # owner: done at admission
    eng.submit(Request(1, prompt, max_new=8))    # sharer decodes on
    _drain(eng)
    solo = _long_engine(model, max_slots=2)
    solo.submit(Request(1, prompt, max_new=8))
    _drain(solo)
    np.testing.assert_array_equal(eng.done[1].output, solo.done[1].output)
    assert eng.alloc.available == eng.alloc.num_blocks


# ---------------------------------------------------------------------------
# co-processing handoff (prefill-class -> decode-class engines)
# ---------------------------------------------------------------------------
def _coproc(model, prefill_chunk=None, decode_blocks=None):
    from repro.runtime.serve import CoProcServer
    cfg, params = model
    pre = ContinuousBatchingEngine(params, cfg, max_slots=1,
                                   prompt_len=PROMPT_LEN,
                                   max_len=LONG_MAX_LEN, block_size=BLOCK,
                                   prefill_chunk=prefill_chunk)
    dec = ContinuousBatchingEngine(params, cfg, max_slots=2,
                                   prompt_len=PROMPT_LEN,
                                   max_len=LONG_MAX_LEN, block_size=BLOCK,
                                   num_blocks=decode_blocks)
    return CoProcServer(pre, dec)


def test_coproc_outputs_match_unified_engine(model):
    rng = np.random.default_rng(12)
    reqs = [(0, rng.integers(0, 256, 20).astype(np.int32), 6),
            (1, rng.integers(0, 256, 5).astype(np.int32), 3),
            (2, rng.integers(0, 256, 33).astype(np.int32), 4)]
    co = _coproc(model)
    uni = _long_engine(model)
    for srv in (co, uni):
        for rid, p, mn in reqs:
            srv.submit(Request(rid, p, max_new=mn))
        while srv.pending:
            srv.step()
    for rid, _, mn in reqs:
        assert co.done[rid].output.shape == (mn,)
        np.testing.assert_array_equal(co.done[rid].output,
                                      uni.done[rid].output)
    assert co.stats()["handoffs"] == 3


def test_coproc_streams_every_token_exactly_once(model):
    """Exact stream completeness across the handoff: the prefill stage
    emits token 0, the decode stage the rest — no loss, no dup."""
    rng = np.random.default_rng(13)
    co = _coproc(model)
    emitted = []
    co.on_token = lambda rid, tok: emitted.append((rid, tok))
    reqs = [(i, rng.integers(0, 256, int(rng.integers(10, 30))
                             ).astype(np.int32), int(rng.integers(1, 7)))
            for i in range(4)]
    for rid, p, mn in reqs:
        co.submit(Request(rid, p, max_new=mn))
    while co.pending:
        co.step()
    for rid, _, mn in reqs:
        stream = [t for r, t in emitted if r == rid]
        assert len(stream) == mn
        np.testing.assert_array_equal(stream, co.done[rid].output)


def test_coproc_wide_prefill_chunk_matches_narrow(model):
    """The DPU-analogue's wide fused chunk is a pure scheduling choice:
    per-block online softmax makes the math identical at any width."""
    prompt = np.random.default_rng(14).integers(0, 256, 37).astype(np.int32)
    wide = _coproc(model, prefill_chunk=40)
    narrow = _coproc(model)
    outs = {}
    for name, srv in (("wide", wide), ("narrow", narrow)):
        srv.submit(Request(0, prompt, max_new=5))
        while srv.pending:
            srv.step()
        outs[name] = srv.done[0].output
    # both pad to 40 tokens here (ceil(37/8)*8 == ceil(37/40)*40), so
    # the streams must agree token for token
    np.testing.assert_array_equal(outs["wide"], outs["narrow"])


def test_coproc_seam_backpressure_defers_without_losing_work(model):
    """A decode pool sized for one request at a time parks the prefilled
    handoff at the seam instead of dropping or re-prefilling it."""
    rng = np.random.default_rng(15)
    co = _coproc(model, decode_blocks=LONG_MAX_LEN // BLOCK)
    for i in range(3):
        co.submit(Request(i, rng.integers(0, 256, 20).astype(np.int32),
                          max_new=4))
    pre_tokens_seen = set()
    while co.pending:
        co.step()
        pre_tokens_seen.add(co.prefill.prefill_tokens)
    assert len(co.done) == 3
    assert co.stats()["deferrals"] > 0
    # prefill ran exactly once per request (24 padded tokens each):
    # deferral parks the handoff, it never burns the prefill again
    assert co.prefill.prefill_tokens == 3 * 24
    assert co.decode.alloc.available == co.decode.alloc.num_blocks
