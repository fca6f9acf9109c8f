"""Public jit'd wrappers for the Pallas kernels.

Handles shape alignment (padding to block multiples), GQA kv expansion,
and backend selection, which the backend alone decides: on a TPU the
kernels always run compiled; elsewhere ``interpret=True`` executes the
kernel bodies in Python for correctness validation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.int8_matmul import (DEFAULT_BK, DEFAULT_BM, DEFAULT_BN,
                                       int8_matmul_pallas)
from repro.kernels.quant import rowwise_quant_pallas
from repro.kernels.selective_scan import selective_scan_pallas
from repro.kernels.wkv import wkv_pallas


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x: jnp.ndarray, mults) -> jnp.ndarray:
    pads = []
    for dim, mult in zip(x.shape, mults):
        rem = (-dim) % mult
        pads.append((0, rem))
    if any(p[1] for p in pads):
        return jnp.pad(x, pads)
    return x


def int8_matmul(x: jnp.ndarray, w: jnp.ndarray,
                bm: int = DEFAULT_BM, bk: int = DEFAULT_BK,
                bn: int = DEFAULT_BN) -> jnp.ndarray:
    """x: [M, K] int8, w: [K, N] int8 -> [M, N] int32 (padded + unpadded)."""
    m, k = x.shape
    _, n = w.shape
    xp = _pad_to(x, (bm, bk))
    wp = _pad_to(w, (bk, bn))
    out = int8_matmul_pallas(xp, wp, bm=bm, bk=bk, bn=bn,
                             interpret=_interpret())
    return out[:m, :n]


def rowwise_quant(x: jnp.ndarray, bm: int = 256):
    """x: [M, K] float -> (q int8 [M, K], scale f32 [M, 1])."""
    m, k = x.shape
    bm = min(bm, max(8, m))
    xp = _pad_to(x, (bm, 1))
    q, s = rowwise_quant_pallas(xp, bm=bm, interpret=_interpret())
    return q[:m], s[:m]


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    bq: int = 256, bk: int = 256) -> jnp.ndarray:
    """Causal attention.  q: [B, S, H, D]; k, v: [B, S, KV, D] (GQA ok)."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    if kv != h:
        rep = h // kv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    bq = min(bq, s)
    bk_ = min(bk, s)
    if s % bq or s % bk_:
        raise ValueError(f"seq {s} must divide block sizes ({bq},{bk_})")
    out = flash_attention_pallas(fold(q), fold(k), fold(v), bq=bq, bk=bk_,
                                 interpret=_interpret())
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)


@jax.jit
def paged_attention_xla(q: jnp.ndarray, k_pool: jnp.ndarray,
                        v_pool: jnp.ndarray, block_table: jnp.ndarray,
                        lengths: jnp.ndarray) -> jnp.ndarray:
    """XLA-native analogue of the Pallas paged-decode kernel: a scan over
    block-table columns with online softmax — one block per sequence in
    flight at a time, never the materialized [B, max_len] KV of
    ``gather_kv``.  This is the fast path on non-TPU backends, where the
    Pallas kernel would run under the (slow) interpreter."""
    b, kvp, gp, hd = q.shape
    page = k_pool.shape[1]
    mb = block_table.shape[1]
    qf = q.astype(jnp.float32) / jnp.sqrt(jnp.float32(hd))

    def step(carry, j):
        m, l, acc = carry
        rows = jnp.maximum(block_table[:, j], 0)
        k = k_pool[rows].astype(jnp.float32)          # [B, P, KVp, hd]
        v = v_pool[rows].astype(jnp.float32)
        s = jnp.einsum("bkgd,bpkd->bkgp", qf, k)
        pos = j * page + jnp.arange(page)
        mask = pos[None, :] < lengths[:, None]        # [B, P]
        s = jnp.where(mask[:, None, None, :], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # zero masked contributions explicitly: for a fully-masked row
        # (lengths == 0) m_new stays -1e30 and exp(s - m_new) would be 1,
        # leaking clamped row-0 V; the Pallas kernel returns exactly 0
        # there (its body never runs) and this path must match
        p = jnp.where(mask[:, None, None, :],
                      jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bkgp,bpkd->bkgd", p, v)
        return (m_new, l, acc), None

    init = (jnp.full((b, kvp, gp), -1e30, jnp.float32),
            jnp.zeros((b, kvp, gp), jnp.float32),
            jnp.zeros((b, kvp, gp, hd), jnp.float32))
    # unrolled: MB is small (max_len / P) and per-iteration scan overhead
    # would dominate the tiny per-block einsums on CPU
    (m, l, acc), _ = jax.lax.scan(step, init, jnp.arange(mb), unroll=True)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def paged_attention(q: jnp.ndarray, k_pool: jnp.ndarray, v_pool: jnp.ndarray,
                    block_table: jnp.ndarray,
                    lengths: jnp.ndarray) -> jnp.ndarray:
    """One-token paged decode attention — walks the block table, never
    materializing a sequence's full KV.

    q: [B, KVp, gp, hd] (one query token per sequence, grouped-query
    layout); k_pool/v_pool: [num_rows, P, KVp, hd] shared block pools;
    block_table: [B, MB] int32; lengths: [B] int32.  Returns
    [B, KVp, gp, hd].  See kernels/paged_attention.py for the layout
    contract.  No shape padding here: head_layout already aligns KVp/gp
    and the pool's P is the engine's block size.

    Backend selection differs from the other wrappers: on TPU the Pallas
    kernel runs compiled; elsewhere the serving path takes the XLA
    block-walk analogue at full native speed instead of the Pallas
    interpreter (which emulates the grid serially — fine for the
    equivalence tests that pin kernel-vs-reference numerics, hopeless
    for a throughput benchmark).
    """
    if _interpret():
        return paged_attention_xla(q, k_pool, v_pool, block_table, lengths)
    return paged_attention_pallas(q, k_pool, v_pool, block_table, lengths)


@jax.jit
def paged_chunk_attention_xla(q: jnp.ndarray, k_pool: jnp.ndarray,
                              v_pool: jnp.ndarray, table_row: jnp.ndarray,
                              qpos: jnp.ndarray) -> jnp.ndarray:
    """Chunk-prefill attention for ONE sequence against its paged KV.

    The chunked-paged-prefill companion to :func:`paged_attention_xla`:
    a C-token query block (one prefill chunk, already pasted into the
    pool by ``paging.write_prefill_chunk``) attends causally to every
    earlier position of its own sequence — the paged prefix written by
    previous chunks plus the in-chunk lower triangle — walking the
    sequence's block-table row with an online softmax, one block in
    flight at a time.

    q: [C, KVp, gp, hd]; k_pool/v_pool: [num_rows, P, KVp, hd];
    table_row: [MB] int32 (-1 = unallocated); qpos: [C] int32 absolute
    positions of the chunk.  Returns [C, KVp, gp, hd].  Blocks past the
    chunk (decode-budget rows, dead entries) fall to the causal mask:
    their positions exceed every query position.
    """
    c, kvp, gp, hd = q.shape
    page = k_pool.shape[1]
    mb = table_row.shape[0]
    qf = q.astype(jnp.float32) / jnp.sqrt(jnp.float32(hd))

    def step(carry, j):
        m, l, acc = carry
        row = jnp.maximum(table_row[j], 0)
        k = k_pool[row].astype(jnp.float32)           # [P, KVp, hd]
        v = v_pool[row].astype(jnp.float32)
        s = jnp.einsum("ckgd,pkd->kgcp", qf, k)
        pos = j * page + jnp.arange(page)
        mask = (pos[None, :] <= qpos[:, None]) & (table_row[j] >= 0)
        s = jnp.where(mask[None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(mask[None, None], jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("kgcp,pkd->kgcd", p, v)
        return (m_new, l, acc), None

    init = (jnp.full((kvp, gp, c), -1e30, jnp.float32),
            jnp.zeros((kvp, gp, c), jnp.float32),
            jnp.zeros((kvp, gp, c, hd), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(step, init, jnp.arange(mb), unroll=True)
    out = acc / jnp.maximum(l, 1e-30)[..., None]      # [KVp, gp, C, hd]
    return out.transpose(2, 0, 1, 3).astype(q.dtype)


def paged_chunk_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                          v_pool: jnp.ndarray, table_row: jnp.ndarray,
                          qpos: jnp.ndarray) -> jnp.ndarray:
    """Backend front door for chunk-prefill paged attention (see
    :func:`paged_chunk_attention_xla`).  The block-walk runs as native
    XLA everywhere today; a Pallas grid over (q-block, kv-block) with
    the same scalar-prefetch table walk as the decode kernel is the
    drop-in TPU upgrade and slots in here."""
    return paged_chunk_attention_xla(q, k_pool, v_pool, table_row, qpos)


def selective_scan(x, dt, b, c, a, d, bd: int = 512, q: int = 256):
    """Fused Mamba selective scan.  x, dt: [B,S,D]; b, c: [B,S,N];
    a: [D,N]; d: [D] -> y [B,S,D] (pads D and S to block multiples)."""
    bsz, s, dim = x.shape
    bd = min(bd, dim)
    q = min(q, s)
    pd = (-dim) % bd
    ps = (-s) % q
    if pd or ps:
        padx = lambda t_: jnp.pad(t_, ((0, 0), (0, ps), (0, pd)))
        padn = lambda t_: jnp.pad(t_, ((0, 0), (0, ps), (0, 0)))
        x, dt = padx(x), padx(dt)
        b, c = padn(b), padn(c)
        a = jnp.pad(a, ((0, pd), (0, 0)))
        d = jnp.pad(d, (0, pd))
    out = selective_scan_pallas(x, dt, b, c, a, d, bd=bd, q=q,
                                interpret=_interpret())
    return out[:, :s, :dim]


def wkv(r, k, v, w, u, q: int = 128):
    """Fused RWKV-6 wkv.  r,k,v,w: [B,S,H,N]; u: [H,N] -> y [B,S,H,N]."""
    bsz, s, h, n = r.shape
    q = min(q, s)
    ps = (-s) % q
    if ps:
        padz = lambda t_: jnp.pad(t_, ((0, 0), (0, ps), (0, 0), (0, 0)))
        r, k, v = padz(r), padz(k), padz(v)
        w = jnp.pad(w, ((0, 0), (0, ps), (0, 0), (0, 0)),
                    constant_values=1.0)           # identity decay on pad
    out = wkv_pallas(r, k, v, w, u, q=q, interpret=_interpret())
    return out[:, :s]


# re-export oracles for tests/benchmarks
int8_matmul_ref = ref.int8_matmul_ref
rowwise_quant_ref = ref.rowwise_quant_ref
flash_attention_ref = ref.flash_attention_ref
selective_scan_ref = ref.selective_scan_ref
wkv_ref = ref.wkv_ref
