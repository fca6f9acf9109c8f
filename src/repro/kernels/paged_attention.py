"""Pallas TPU kernel: paged decode attention (one query token vs a paged
KV cache, walked through the block table — no KV materialization).

The dense-gather decode path (`runtime.paging.gather_kv`) copies every
sequence's KV out of the shared pool into a `[B, max_len, KVp, hd]`
buffer on **every decode step** — O(B * max_len) HBM traffic before a
single MXU cycle runs.  This kernel instead walks each sequence's live
pages straight out of the pool: the block table and the per-sequence
lengths ride in as scalar-prefetch operands, and the kernel copies
exactly the pool rows a sequence has written.  Decode-step work scales
with the pages touched, not with the table's width.

Block-table layout contract (shared with ``runtime.paging``):

  * ``k_pool``/``v_pool``: ``[L, num_rows, P, KVp * hd]`` — for each of
    ``L`` layers, ``num_rows`` fixed-size rows of ``P`` token slots
    each, heads merged into the minor axis (head ``h`` is lanes
    ``h*hd .. (h+1)*hd``).  The kernel reads layer ``layer`` of the
    stack in place: the model's layer scan carries the whole pool and
    never slices a layer out of it.  Row ``num_rows - 1`` is the *trash
    row*: never handed out by the allocator, it absorbs writes for
    inactive batch slots and is never read by this kernel.
  * ``block_table``: ``[B, MB] int32`` — row ``b`` lists the pool rows
    of sequence ``b`` in token order; ``-1`` marks an unallocated entry.
    Tokens ``[j*P, (j+1)*P)`` of sequence ``b`` live in pool row
    ``block_table[b, j]`` at slot ``token % P``.
  * ``lengths``: ``[B] int32`` — tokens written per sequence.  Entries
    of ``block_table[b]`` at or past ``ceil(lengths[b] / P)`` are dead:
    the kernel never reads them, so they may hold ``-1`` or any row.  A
    sequence of length 0 touches no page and its output is exactly 0.

The grid is ``(B,)``, one step per sequence, "arbitrary" because the
steps hand buffers to each other.  The pools stay in HBM
(``memory_space=pl.ANY``); a step walks its sequence's live pages in
compute blocks of ``pages_per_block`` pages with a bounded loop, each
page one asynchronous copy from its pool row into a VMEM buffer.  The
buffers are doubled: block k+1's copies run behind block k's compute,
and a sequence's last block starts the copies of the next sequence's
first.  Pages of a block past the sequence's last live page are never
copied; their buffer slots are masked out of the scores and the values.

The body works all heads of a block at once.  The query is laid out
block-diagonally, ``[KVp * gp, KVp * hd]`` with row ``(h, g)`` holding
query ``g`` of KV head ``h`` in head ``h``'s lanes and zeros elsewhere,
so one MXU contraction over the merged lane axis gives every head's
scores against the block (``[KVp * gp, pages_per_block * P]``) and one
more gives every head's ``p @ v``, of which each row keeps its own
head's lanes.  The same form serves grouped (``gp > 1``) and ungrouped
layouts.  Scores, the online-softmax statistics and ``p @ v`` are f32;
the score contraction takes q and K in their common dtype (bf16 x bf16
products are exact in f32) with f32 accumulation.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# tokens a compute block aims at, and the VMEM the doubled K/V buffers
# and the block's f32 temporaries may take
BLOCK_TOKENS = 256
VMEM_BUDGET = 8 * 1024 * 1024


def pages_per_block(page: int, width: int, itemsize: int,
                    table_width: int) -> int:
    """Pages a compute block takes: ``BLOCK_TOKENS`` worth, no more than
    the table holds, and as many as fit ``VMEM_BUDGET`` (K and V, two
    buffers each, plus the block's f32 copies of K and V)."""
    per_page = page * width * (4 * itemsize + 2 * 4)
    return max(1, min(BLOCK_TOKENS // page, table_width,
                      VMEM_BUDGET // per_page))


def _paged_kernel(tbl_ref, len_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref,
                  k_buf, v_buf, sems, slot_ref, qbd_ref, m_ref, l_ref,
                  acc_ref, *, scale: float):
    b = pl.program_id(0)
    n_seq = pl.num_programs(0)
    _, ppb, page, width = k_buf.shape
    rows, table_width = k_hbm.shape[1], tbl_ref.shape[1]
    kvp, gp, hd = q_ref.shape[1:]
    tokens = ppb * page
    layer = layer_ref[0]

    def live_pages(seq):
        return jnp.minimum((len_ref[seq] + page - 1) // page, table_width)

    def copies(seq, blk, slot, start):
        """Start (or wait for) the copies of block ``blk`` of ``seq``
        into buffer ``slot``: one per live page, none past them."""
        first = blk * ppb

        def page_copy(i, carry):
            row = jnp.clip(tbl_ref[seq, first + i], 0, rows - 1)
            for n, (pool, buf) in enumerate(((k_hbm, k_buf),
                                             (v_hbm, v_buf))):
                cp = pltpu.make_async_copy(pool.at[layer, row],
                                           buf.at[slot, i], sems.at[n, slot])
                if start:
                    cp.start()
                else:
                    cp.wait()
            return carry

        jax.lax.fori_loop(0, jnp.clip(live_pages(seq) - first, 0, ppb),
                          page_copy, 0)

    def fetch_ahead(seq, blk, slot):
        @pl.when(seq < n_seq)
        def _ahead():
            copies(seq, blk, slot, start=True)

    @pl.when(b == 0)
    def _first():                 # later sequences are fetched ahead
        slot_ref[0] = 0
        copies(0, 0, 0, start=True)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    qbd_ref[...] = jnp.zeros_like(qbd_ref)
    for h in range(kvp):              # the block-diagonal query
        qbd_ref[h * gp:(h + 1) * gp, h * hd:(h + 1) * hd] = (
            q_ref[0, h].astype(qbd_ref.dtype))

    seq_len = len_ref[b]
    n_blk = (live_pages(b) + ppb - 1) // ppb

    def block(blk, slot):
        nxt = 1 - slot
        more = blk + 1 < n_blk        # else the next sequence's first block
        fetch_ahead(jnp.where(more, b, b + 1), jnp.where(more, blk + 1, 0),
                    nxt)
        copies(b, blk, slot, start=False)
        pos = blk * tokens + jax.lax.broadcasted_iota(jnp.int32,
                                                      (1, tokens), 1)
        live_col = (blk * tokens + jax.lax.broadcasted_iota(
            jnp.int32, (tokens, 1), 0)) < seq_len
        k = k_buf[slot].reshape(tokens, width).astype(qbd_ref.dtype)
        v = jnp.where(live_col,
                      v_buf[slot].reshape(tokens, width).astype(jnp.float32),
                      0.0)
        s = jax.lax.dot_general(qbd_ref[...], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(pos < seq_len, s, NEG_INF)          # [KVp*gp, T]

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return nxt

    slot = jax.lax.fori_loop(0, n_blk, block, slot_ref[0])

    @pl.when(n_blk == 0)
    def _empty():                 # nothing in flight: fetch ahead here
        fetch_ahead(b + 1, 0, slot)

    slot_ref[0] = slot
    for h in range(kvp):              # each row keeps its own head's lanes
        r = slice(h * gp, (h + 1) * gp)
        o_ref[0, h] = (acc_ref[r, h * hd:(h + 1) * hd] /
                       jnp.maximum(l_ref[r], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_pallas(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, block_table: jnp.ndarray,
                           lengths: jnp.ndarray, layer,
                           interpret: bool = False) -> jnp.ndarray:
    """One decode token of paged attention against layer ``layer``.

    q: [B, KVp, gp, hd]; k_pool/v_pool: [L, num_rows, P, KVp * hd];
    block_table: [B, MB] int32; lengths: [B] int32; layer: int32
    scalar -> [B, KVp, gp, hd].
    """
    b, kvp, gp, hd = q.shape
    n_layers, _, page, width = k_pool.shape
    ppb = pages_per_block(page, width, k_pool.dtype.itemsize,
                          block_table.shape[1])
    mxu_dtype = jnp.promote_types(q.dtype, k_pool.dtype)
    kernel = functools.partial(_paged_kernel, scale=1.0 / math.sqrt(hd))

    def q_map(i, tbl, lens, lay):
        return (i, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, kvp, gp, hd), q_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, kvp, gp, hd), q_map),
        scratch_shapes=[
            # doubled page buffers and their copy semaphores [K|V, slot]
            pltpu.VMEM((2, ppb, page, width), k_pool.dtype),
            pltpu.VMEM((2, ppb, page, width), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),      # the next sequence's slot
            pltpu.VMEM((kvp * gp, width), mxu_dtype),
            # VMEM-resident online-softmax statistics across the walk
            pltpu.VMEM((kvp * gp, 1), jnp.float32),
            pltpu.VMEM((kvp * gp, 1), jnp.float32),
            pltpu.VMEM((kvp * gp, width), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        # the op name in device traces; the benchmark's roofline reader
        # matches it, so it must not follow this wrapper's name
        name="paged_attention_pallas",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvp, gp, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_table, lengths,
      jnp.clip(jnp.reshape(layer, (1,)), 0, n_layers - 1).astype(jnp.int32),
      q, k_pool, v_pool)
