"""Pallas paged-decode-attention kernel vs the gather_kv reference path
(interpret mode): ragged lengths, partially-filled blocks, GQA head
layouts, the layer index into a stacked pool, and the trash-row
contract for inactive batch slots.  Pools are ``[L, rows, P, KVp*hd]``
with the heads merged into the minor axis."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.kernels.paged_attention import paged_attention_pallas
from repro.runtime.paging import (BlockAllocator, append_tokens,
                                  ensure_blocks, init_paged_cache,
                                  paged_decode_attention)

RNG = np.random.default_rng(7)

IMPLS = {
    "pallas": lambda *a: paged_attention_pallas(*a, interpret=True),
    "xla": ops.paged_attention_xla,
}


def _ragged_state(lengths, block_size, kv_heads, head_dim, num_blocks,
                  dtype=jnp.float32, max_blocks=None, layers=1):
    """Build a paged cache holding random KV at the given ragged lengths
    (different in every layer), allocated out of a shuffled free list
    (non-contiguous block rows)."""
    b = len(lengths)
    alloc = BlockAllocator(num_blocks)
    RNG.shuffle(alloc.free)                       # rows land out of order
    state = init_paged_cache(b, num_blocks, block_size, kv_heads, head_dim,
                             dtype=dtype, max_blocks=max_blocks,
                             layers=layers)
    for t in range(max(lengths)):
        grow = np.array([1 if t < L else 0 for L in lengths])
        state = ensure_blocks(state, alloc, grow)
        written = state.lengths
        for layer in range(layers):
            k = RNG.normal(size=(b, kv_heads, head_dim)).astype(np.float32)
            v = RNG.normal(size=(b, kv_heads, head_dim)).astype(np.float32)
            # only sequences still growing get a real write; freeze
            # others by writing then restoring is overkill — instead
            # append to all and rebuild lengths below (append_tokens
            # advances every valid row, once per layer written)
            state = append_tokens(state._replace(lengths=written),
                                  jnp.asarray(k), jnp.asarray(v), layer)
        state = state._replace(lengths=jnp.asarray(
            np.minimum(np.asarray(state.lengths), lengths)))
    np.testing.assert_array_equal(np.asarray(state.lengths), lengths)
    return state


@pytest.mark.parametrize("lengths,block_size", [
    ([9, 1, 6], 4),            # ragged, partial last blocks
    ([8, 8], 4),               # exactly block-aligned
    ([1, 1, 1, 1], 8),         # single token, mostly-empty blocks
    ([33, 7, 20, 15], 8),      # multi-block walks
])
@pytest.mark.parametrize("kv,gp", [(2, 2), (1, 4), (4, 1)])
@pytest.mark.parametrize("impl", sorted(IMPLS))
def test_kernel_matches_gather_reference(lengths, block_size, kv, gp, impl):
    hd = 16
    state = _ragged_state(lengths, block_size, kv, hd, num_blocks=32)
    q = jnp.asarray(RNG.normal(size=(len(lengths), kv, gp, hd))
                    .astype(np.float32))
    out = IMPLS[impl](q, state.k_pool, state.v_pool,
                      state.block_table, state.lengths, 0)
    ref = paged_decode_attention(q, state, max_len=max(lengths))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-2)


@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("layers", [1, 3])
@pytest.mark.parametrize("impl", sorted(IMPLS))
def test_kernel_reads_its_layer_of_a_stacked_pool(impl, layers, hd):
    """The layer index selects one layer of the stacked pool in place:
    the middle one of three (each holding different KV), or the only
    one, at the served head widths."""
    lengths = [9, 1, 6]
    state = _ragged_state(lengths, 4, 2, hd, num_blocks=16, layers=layers)
    assert state.k_pool.shape == (layers, 17, 4, 2 * hd)
    layer = layers // 2
    q = jnp.asarray(RNG.normal(size=(3, 2, 2, hd)).astype(np.float32))
    out = IMPLS[impl](q, state.k_pool, state.v_pool, state.block_table,
                      state.lengths, jnp.int32(layer))
    ref = paged_decode_attention(q, state, max_len=9, layer=layer)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-2)
    if layers > 1:                  # another layer's KV gives another answer
        other = paged_decode_attention(q, state, max_len=9, layer=0)
        assert np.abs(np.asarray(other) - np.asarray(ref)).max() > 0.1


@pytest.mark.parametrize("impl", sorted(IMPLS))
def test_kernel_matches_reference_bf16_pool(impl):
    lengths = [11, 3]
    state = _ragged_state(lengths, 4, 2, 16, num_blocks=16,
                          dtype=jnp.bfloat16)
    q = jnp.asarray(RNG.normal(size=(2, 2, 2, 16)).astype(np.float32))
    out = IMPLS[impl](q, state.k_pool, state.v_pool,
                      state.block_table, state.lengths, 0)
    ref = paged_decode_attention(q, state, max_len=11)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-2)


def test_kernel_ignores_blocks_past_length():
    """Garbage in a sequence's unallocated/dead region must not leak in."""
    lengths = [5, 2]
    state = _ragged_state(lengths, 4, 2, 8, num_blocks=16)
    ref_q = jnp.asarray(RNG.normal(size=(2, 2, 2, 8)).astype(np.float32))
    ref = np.asarray(ops.paged_attention(ref_q, state.k_pool, state.v_pool,
                                         state.block_table, state.lengths,
                                         0))
    # poison every pool row not referenced within a live prefix
    table = np.asarray(state.block_table)
    live = set()
    for b, L in enumerate(lengths):
        live |= set(table[b, :-(-L // 4)].tolist())
    pk = np.asarray(state.k_pool).copy()
    pv = np.asarray(state.v_pool).copy()
    for row in range(pk.shape[1]):
        if row not in live:
            pk[:, row] = 1e4
            pv[:, row] = 1e4
    state = state._replace(k_pool=jnp.asarray(pk), v_pool=jnp.asarray(pv))
    out = np.asarray(ops.paged_attention(ref_q, state.k_pool, state.v_pool,
                                         state.block_table, state.lengths,
                                         0))
    np.testing.assert_allclose(out, ref, atol=1e-2)


@pytest.mark.parametrize("impl", sorted(IMPLS))
def test_zero_length_slot_attends_to_nothing(impl):
    """A slot with lengths == 0 (freed / never admitted) must return
    exactly 0 from both implementations — not leak pool row 0."""
    state = _ragged_state([6, 1], 4, 2, 8, num_blocks=16)
    state = state._replace(lengths=jnp.asarray(np.array([6, 0], np.int32)))
    q = jnp.asarray(RNG.normal(size=(2, 2, 2, 8)).astype(np.float32))
    out = np.asarray(IMPLS[impl](q, state.k_pool, state.v_pool,
                                 state.block_table, state.lengths, 0))
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out[1], np.zeros_like(out[1]))


def test_inactive_slot_appends_to_trash_row():
    """A batch slot with no allocated blocks writes to the trash row and
    its length does not advance — live blocks stay untouched."""
    alloc = BlockAllocator(8)
    state = init_paged_cache(2, 8, 4, 2, 8, dtype=jnp.float32)
    state = ensure_blocks(state, alloc, np.array([4, 0]))   # slot 1 inactive
    k = jnp.asarray(RNG.normal(size=(2, 2, 8)).astype(np.float32))
    before = np.asarray(state.k_pool[0, :-1]).copy()        # live rows
    state = append_tokens(state, k, k)
    np.testing.assert_array_equal(np.asarray(state.lengths), [1, 0])
    after = np.asarray(state.k_pool[0, :-1])
    # only slot 0's first block changed; slot 1's write went to trash
    row0 = int(np.asarray(state.block_table)[0, 0])
    changed = [r for r in range(after.shape[0])
               if not np.array_equal(before[r], after[r])]
    assert changed == [row0], changed
