"""Paged KV cache: allocator behaviour + attention equivalence vs the
dense cache path."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime.paging import (BlockAllocator, PagedKVState,
                                  append_tokens, ensure_blocks, gather_kv,
                                  init_paged_cache, paged_decode_attention,
                                  release_sequence)

B, P, KV, HD = 3, 4, 2, 8


def _write_tokens(state, alloc, n, seed=0):
    rng = np.random.default_rng(seed)
    ks, vs = [], []
    for t in range(n):
        state = ensure_blocks(state, alloc, np.ones(B, np.int64))
        k = jnp.asarray(rng.normal(size=(B, KV, HD)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(B, KV, HD)).astype(np.float32))
        state = append_tokens(state, k, v)
        ks.append(k)
        vs.append(v)
    return state, jnp.stack(ks, 1), jnp.stack(vs, 1)   # [B, n, KV, HD]


def test_gather_reconstructs_written_tokens():
    alloc = BlockAllocator(32)
    state = init_paged_cache(B, 32, P, KV, HD, dtype=jnp.float32)
    state, ks, vs = _write_tokens(state, alloc, 10)
    k, v, valid = gather_kv(state, 12)         # heads merged: [B, T, KV*HD]
    np.testing.assert_allclose(np.asarray(k[:, :10]),
                               np.asarray(ks).reshape(B, 10, -1), atol=1e-6)
    np.testing.assert_allclose(np.asarray(v[:, :10]),
                               np.asarray(vs).reshape(B, 10, -1), atol=1e-6)
    assert bool(valid[:, :10].all()) and not bool(valid[:, 10:].any())


def test_paged_attention_matches_dense():
    alloc = BlockAllocator(32)
    state = init_paged_cache(B, 32, P, KV, HD, dtype=jnp.float32)
    state, ks, vs = _write_tokens(state, alloc, 9)
    gp = 2
    q = jnp.asarray(np.random.default_rng(1).normal(
        size=(B, KV, gp, HD)).astype(np.float32))
    out = paged_decode_attention(q, state, max_len=12)
    # dense reference
    scores = jnp.einsum("bkgd,btkd->bkgt", q, ks) / math.sqrt(HD)
    w = jax.nn.softmax(scores, axis=-1)
    ref = jnp.einsum("bkgt,btkd->bkgd", w, vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_allocator_reuse_after_release():
    alloc = BlockAllocator(8)
    state = init_paged_cache(2, 8, P, KV, HD)
    state = ensure_blocks(state, alloc, np.array([P * 3, P * 3]))
    assert alloc.available == 2
    state = release_sequence(state, alloc, 0)
    assert alloc.available == 5
    assert int(state.lengths[0]) == 0
    # freed blocks are reusable by the other sequence (has 3, needs 4)
    state = ensure_blocks(state, alloc, np.array([0, P * 4]))
    assert alloc.available == 4


def test_pool_exhaustion_raises():
    alloc = BlockAllocator(2)
    state = init_paged_cache(1, 2, P, KV, HD)
    with pytest.raises(RuntimeError, match="exhausted"):
        ensure_blocks(state, alloc, np.array([P * 3]))


def test_exhaustion_is_typed_and_atomic():
    from repro.runtime.paging import OutOfBlocksError
    alloc = BlockAllocator(3)
    state = init_paged_cache(2, 3, P, KV, HD)
    state = ensure_blocks(state, alloc, np.array([P, 0]))
    with pytest.raises(OutOfBlocksError):
        # needs 1 + 3 more blocks, only 2 left — nothing may leak, not
        # even seq 0's satisfiable share
        ensure_blocks(state, alloc, np.array([P * 2, P * 3]))
    assert alloc.available == 2
    assert int((np.asarray(state.block_table) >= 0).sum()) == 1


def test_write_prefill_roundtrips_through_gather():
    from repro.runtime.paging import write_prefill
    alloc = BlockAllocator(8)
    state = init_paged_cache(2, 8, P, KV, HD, dtype=jnp.float32)
    s = 7                                     # partial last block
    state = ensure_blocks(state, alloc, np.array([s, 0]))
    rng = np.random.default_rng(5)
    k = jnp.asarray(rng.normal(size=(s, KV, HD)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(s, KV, HD)).astype(np.float32))
    state = write_prefill(state, k, v, 0)
    assert int(state.lengths[0]) == s and int(state.lengths[1]) == 0
    gk, gv, valid = gather_kv(state, 8)
    np.testing.assert_allclose(np.asarray(gk[0, :s]),
                               np.asarray(k).reshape(s, -1), atol=1e-6)
    np.testing.assert_allclose(np.asarray(gv[0, :s]),
                               np.asarray(v).reshape(s, -1), atol=1e-6)
    assert bool(valid[0, :s].all()) and not bool(valid[0, s:].any())
