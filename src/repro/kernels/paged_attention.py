"""Pallas TPU kernel: paged decode attention (one query token vs a paged
KV cache, walked through the block table — no KV materialization).

The dense-gather decode path (`runtime.paging.gather_kv`) copies every
sequence's KV out of the shared pool into a `[B, max_len, KVp, hd]`
buffer on **every decode step** — O(B * max_len) HBM traffic before a
single MXU cycle runs.  This kernel instead walks each sequence's block
table directly inside the grid: the table and the per-sequence lengths
ride in as scalar-prefetch operands, so the BlockSpec index map can DMA
exactly the pool rows a sequence owns, and `pl.when` skips every block
past the sequence's current length (no DMA'd-but-dead MXU work).
Decode-step traffic drops from O(context) gather+attend to
O(blocks-touched) attend.

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
    the index map clamps them to row 0 (the DMA must target *something*)
    and the kernel body is predicated off, so they contribute nothing.

Grid is ``(B, MB)`` with the block sweep innermost and "arbitrary"
semantics, so the online-softmax statistics (m, l, acc) stay
VMEM-resident across a sequence's whole table walk — the decode
analogue of `flash_attention.py`'s KV sweep.  Each grid step DMAs one
whole pool row, ``(1, 1, P, KVp * hd)`` at ``(layer, row, 0, 0)``, and
walks the KVp heads inside the body with static lane slices: a block's
last two dims then equal the pool's, which is what the TPU's (8, 128)
tiling rule asks of a block that is not tile-aligned.  The layer index
rides in as a third scalar-prefetch operand.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(tbl_ref, len_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, page: int, num_blk: int,
                  kv_heads: int, scale: float):
    b_idx = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seq_len = len_ref[b_idx]

    # Skip blocks entirely past this sequence's length: the DMA engine
    # still fetched *a* row (the index map clamps dead table entries to
    # row 0) but no head's matmuls are issued for it.
    @pl.when(j * page < seq_len)
    def _block():
        gp, hd = q_ref.shape[2:]
        pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (gp, page), 1)
        live = pos < seq_len
        for h in range(kv_heads):                 # static walk over heads
            lanes = slice(h * hd, (h + 1) * hd)
            q = q_ref[0, h].astype(jnp.float32) * scale       # [gp, hd]
            k = k_ref[0, 0, :, lanes].astype(jnp.float32)     # [P, hd]
            v = v_ref[0, 0, :, lanes].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(live, s, NEG_INF)                   # [gp, P]

            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(j == num_blk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


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
    mb = block_table.shape[1]
    scale = 1.0 / math.sqrt(hd)
    kernel = functools.partial(_paged_kernel, page=page, num_blk=mb,
                               kv_heads=kvp, scale=scale)

    def q_map(i, j, tbl, lens, lay):
        return (i, 0, 0, 0)

    def kv_map(i, j, tbl, lens, lay):
        # dead entries (-1) clamp to row 0; the body is predicated off
        return (jnp.clip(lay[0], 0, n_layers - 1),
                jnp.maximum(tbl[i, j], 0), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, mb),
        in_specs=[
            pl.BlockSpec((1, kvp, gp, hd), q_map),
            pl.BlockSpec((1, 1, page, width), kv_map),
            pl.BlockSpec((1, 1, page, width), kv_map),
        ],
        out_specs=pl.BlockSpec((1, kvp, gp, hd), q_map),
        scratch_shapes=[
            # VMEM-resident online-softmax statistics across the walk
            pltpu.VMEM((kvp, gp, 1), jnp.float32),
            pltpu.VMEM((kvp, gp, 1), jnp.float32),
            pltpu.VMEM((kvp, gp, hd), jnp.float32),
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
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_table, lengths, jnp.reshape(layer, (1,)).astype(jnp.int32),
      q, k_pool, v_pool)
