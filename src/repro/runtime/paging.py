"""Paged KV cache — vLLM-style block-table memory management for serving.

The dense per-request cache allocates max_len for every slot; with mixed
request lengths most of it is dead.  Here KV storage is a shared pool of
fixed-size token blocks; each sequence owns a block table (indices into
the pool) that grows on demand and frees on completion — fragmentation-
free reuse across a serving batch, the enabler for continuous batching.

Pure-JAX data plane (scatter on the pool) + a tiny host-side allocator.
Two attention paths read the paged cache:

  * ``paged_decode_attention`` — gathers the sequence's blocks into a
    dense ``[B, max_len, ...]`` buffer, then a dense softmax.  O(context)
    HBM traffic per decode step; kept as the *reference* the Pallas
    kernel is validated against.
  * ``kernels.ops.paged_attention`` — walks the block table inside the
    kernel grid (scalar-prefetch index maps), O(blocks-touched) traffic.
    This is what the continuous-batching engine serves with.

Layout note: a pool is ``[L, num_blocks + 1, P, KVp * hd]`` — one
stack of block rows per layer, the heads merged into the minor axis so
that the TPU's default layout is row-major and unpadded, the layout the
Pallas kernel reads.  Writers and readers take a ``layer`` index into
the stack and reshape only their small per-token operands; the pool is
never reshaped or sliced per layer (on a TPU either is a copy of it).
Pools carry ``num_blocks + 1`` rows.  The last row is a
*trash row* the allocator never hands out; ``append_tokens`` routes
writes from batch slots with no allocated blocks (inactive slots of a
fixed-size serving batch) into it so they can ride in the same scatter
without corrupting live blocks.  Readers never touch it: ``gather_kv``
clamps dead table entries to row 0 and masks by length, and the Pallas
kernel predicates those blocks off entirely.
"""
from __future__ import annotations

import hashlib
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class OutOfBlocksError(RuntimeError):
    """KV block pool exhausted — callers defer admission instead of dying.

    Subclasses RuntimeError so legacy ``except RuntimeError`` sites keep
    working; the serving engine catches this type to hold a request in
    its queue until completions release blocks.
    """


class PagedKVState(NamedTuple):
    k_pool: jnp.ndarray       # [L, num_blocks + 1, P, KVp * hd] (last = trash)
    v_pool: jnp.ndarray
    block_table: jnp.ndarray  # [B, max_blocks] int32 (-1 = unallocated)
    lengths: jnp.ndarray      # [B] int32 tokens written per sequence


class BlockAllocator:
    """Host-side free-list over the shared pool.

    Radiation hardening adds a *quarantine* lane: a block a scrub pass
    found corrupted is pulled out of service (``quarantine``) and never
    re-enters the free list — ``release`` silently skips it, so every
    existing teardown path stays exact without knowing about upsets.
    The accounting invariant is ``free + live + quarantined ==
    num_blocks`` with ``live`` derived, which the property tests pin
    under random op interleavings.  ``on_release`` (optional) fires once
    per block that actually returns to the free list — the serving
    engine hooks it to retire stale integrity digests no matter which
    path (finalize, shared-index refcount, release_sequence) freed the
    block.
    """

    def __init__(self, num_blocks: int):
        self.num_blocks = num_blocks
        self.free: List[int] = list(range(num_blocks - 1, -1, -1))
        self.quarantined: set = set()
        self.on_release = None            # callable(block) | None

    def alloc(self) -> int:
        if not self.free:
            raise OutOfBlocksError("KV block pool exhausted")
        return self.free.pop()

    def release(self, blocks) -> None:
        for b in blocks:
            b = int(b)
            if b < 0 or b in self.quarantined:
                continue
            self.free.append(b)
            if self.on_release is not None:
                self.on_release(b)

    def quarantine(self, block: int) -> bool:
        """Take ``block`` out of service; True if newly quarantined."""
        b = int(block)
        if b < 0 or b in self.quarantined:
            return False
        self.quarantined.add(b)
        if b in self.free:                # upset caught while block idle
            self.free.remove(b)
        return True

    @property
    def available(self) -> int:
        return len(self.free)

    @property
    def live(self) -> int:
        return self.num_blocks - len(self.free) - len(self.quarantined)


def init_paged_cache(batch: int, num_blocks: int, block_size: int,
                     kv_heads: int, head_dim: int,
                     dtype=jnp.bfloat16,
                     max_blocks: Optional[int] = None,
                     layers: int = 1) -> PagedKVState:
    """Pools of ``layers`` layers, each with one extra trash row (see
    module docstring).

    ``max_blocks`` bounds the per-sequence table width (defaults to
    ``num_blocks``: any sequence may own the whole pool).  The serving
    engine passes ``ceil(max_len / block_size)`` so the kernel's table
    walk is O(max_len / P), not O(pool size).
    """
    if max_blocks is None:
        max_blocks = num_blocks
    shape = (layers, num_blocks + 1, block_size, kv_heads * head_dim)
    return PagedKVState(
        jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
        -jnp.ones((batch, max_blocks), jnp.int32),
        jnp.zeros((batch,), jnp.int32))


def plan_blocks(table: np.ndarray, alloc: BlockAllocator,
                need_blocks: np.ndarray) -> np.ndarray:
    """Host step: grow each sequence's table row to ``need_blocks[i]``.

    Atomic: the total block need is checked against the allocator before
    anything is taken, so an :class:`OutOfBlocksError` leaks nothing and
    the caller can simply retry later.  Returns a new table array
    (``table`` itself is not mutated).  Counts are *blocks*, not tokens —
    ``ensure_blocks`` does the token division; the serving engine calls
    this directly on its host-side table mirror.
    """
    table = np.asarray(table).copy()
    grows = []
    total = 0
    for i, add in enumerate(np.asarray(need_blocks)):
        need, have = int(add), int((table[i] >= 0).sum())
        if need > table.shape[1]:
            raise OutOfBlocksError(
                f"KV block pool exhausted: sequence {i} needs {need} "
                f"blocks > table width {table.shape[1]}")
        if need > have:
            grows.append((i, have, need))
            total += need - have
    if total > alloc.available:
        raise OutOfBlocksError(
            f"KV block pool exhausted: need {total} blocks, "
            f"{alloc.available} available")
    for i, have, need in grows:
        for j in range(have, need):
            table[i, j] = alloc.alloc()
    return table


def ensure_blocks(state: PagedKVState, alloc: BlockAllocator,
                  new_tokens: np.ndarray) -> PagedKVState:
    """Grow each sequence's table to cover len+new tokens (atomic)."""
    p = state.k_pool.shape[2]
    lengths = np.asarray(state.lengths)
    need = -(-(lengths + np.asarray(new_tokens)) // p)   # blocks, not tokens
    table = plan_blocks(np.asarray(state.block_table), alloc, need)
    return state._replace(block_table=jnp.asarray(table))


def release_sequence(state: PagedKVState, alloc: BlockAllocator,
                     seq: int) -> PagedKVState:
    table = np.asarray(state.block_table).copy()
    alloc.release(table[seq][table[seq] >= 0])
    table[seq] = -1
    lengths = np.asarray(state.lengths).copy()
    lengths[seq] = 0
    return state._replace(block_table=jnp.asarray(table),
                          lengths=jnp.asarray(lengths))


def _rows(x: jnp.ndarray, lead: int) -> jnp.ndarray:
    """Merge a per-token operand's heads into the pool's minor axis:
    ``[*lead dims, KVp, hd]`` -> ``[*lead dims, KVp * hd]``."""
    return x.reshape(*x.shape[:lead], -1)


@jax.jit
def append_tokens(state: PagedKVState, k: jnp.ndarray,
                  v: jnp.ndarray, layer=0) -> PagedKVState:
    """Write one new token per sequence into layer ``layer``.  k, v:
    [B, KVp, hd].

    Sequences whose next block is unallocated (inactive slots of a
    fixed-size serving batch) write to the trash row instead and their
    length does not advance — the continuous-batching engine relies on
    this to run one fixed-shape scatter for a partially-occupied batch.
    Only the B token rows are written: inside the model's layer scan the
    pool is updated in place.
    """
    p = state.k_pool.shape[2]
    trash = state.k_pool.shape[1] - 1
    blk_idx = jnp.minimum(state.lengths // p, state.block_table.shape[1] - 1)
    blk = jnp.take_along_axis(state.block_table, blk_idx[:, None],
                              axis=1)[:, 0]                    # [B]
    active = blk >= 0
    blk = jnp.where(active, blk, trash)
    off = jnp.where(active, state.lengths % p, 0)
    k_pool = state.k_pool.at[layer, blk, off].set(
        _rows(k, 1).astype(state.k_pool.dtype))
    v_pool = state.v_pool.at[layer, blk, off].set(
        _rows(v, 1).astype(state.v_pool.dtype))
    return PagedKVState(k_pool, v_pool, state.block_table,
                        state.lengths + active.astype(jnp.int32))


@jax.jit
def write_prefill(state: PagedKVState, k: jnp.ndarray, v: jnp.ndarray,
                  seq, layer=0) -> PagedKVState:
    """Paste a prefilled sequence's KV into its blocks of layer
    ``layer`` in one scatter.

    k, v: [S, KVp, hd] — tokens 0..S-1 of sequence ``seq`` (whose table
    row must already cover ceil(S / P) blocks and whose length restarts
    at S).  The pad tail of the last block is zero-filled; it sits past
    ``lengths[seq]`` so every reader masks it off, and subsequent
    ``append_tokens`` writes land on the exact slots anyway.
    """
    p = state.k_pool.shape[2]
    s = k.shape[0]
    nb = -(-s // p)
    pad = nb * p - s

    def blocked(x):
        x = jnp.pad(_rows(x, 1), ((0, pad), (0, 0)))
        return x.reshape(nb, p, -1).astype(state.k_pool.dtype)
    rows = jnp.maximum(state.block_table[seq, :nb], 0)
    k_pool = state.k_pool.at[layer, rows].set(blocked(k))
    v_pool = state.v_pool.at[layer, rows].set(blocked(v))
    return PagedKVState(k_pool, v_pool, state.block_table,
                        state.lengths.at[seq].set(s))


@jax.jit
def write_prefill_batch(state: PagedKVState, k: jnp.ndarray,
                        v: jnp.ndarray, admit: jnp.ndarray) -> PagedKVState:
    """Batched :func:`write_prefill`: paste every admitted slot's prefill
    KV, every layer at once, in one scatter per pool.

    k, v: [L, B, S, KVp, hd] — the whole prefill batch of each of the
    pool's L layers; ``admit``: [B] bool.  Non-admitted rows (occupied
    slots riding along in the fixed-shape prefill bucket, or empty
    slots) scatter into the trash row; admitted rows land in their table
    blocks and restart at length S.
    """
    p = state.k_pool.shape[2]
    trash = state.k_pool.shape[1] - 1
    n_layers, b, s = k.shape[:3]
    nb = -(-s // p)
    pad = nb * p - s

    def blocked(x):
        x = jnp.pad(_rows(x, 3), ((0, 0), (0, 0), (0, pad), (0, 0)))
        return x.reshape(n_layers, b * nb, p, -1).astype(state.k_pool.dtype)
    rows = state.block_table[:, :nb]
    rows = jnp.where(admit[:, None] & (rows >= 0), rows, trash).reshape(-1)
    k_pool = state.k_pool.at[:, rows].set(blocked(k))
    v_pool = state.v_pool.at[:, rows].set(blocked(v))
    return PagedKVState(k_pool, v_pool, state.block_table,
                        jnp.where(admit, s, state.lengths))


@jax.jit
def write_prefill_chunk(state: PagedKVState, k: jnp.ndarray, v: jnp.ndarray,
                        seq, start, layer=0) -> PagedKVState:
    """Paste one *chunk* of a prefill into sequence ``seq``'s blocks of
    layer ``layer``.

    k, v: [C, KVp, hd] — tokens ``start .. start+C-1`` of the sequence.
    Both ``start`` and ``C`` must be block-aligned (``% P == 0``), so the
    paste is a whole-block scatter (the Pallas-friendly layout: rows are
    written in full, never read-modify-written) and chunks can land in
    any order without masking.  ``seq`` and ``start`` may be traced —
    this is the jit-safe building block chunked paged prefill and the
    co-processing KV handoff both ride.  The sequence's length advances
    to ``start + C``; callers paste chunks left to right so the final
    chunk leaves the true prefill length behind.  Unallocated table
    entries route to the trash row (same contract as ``append_tokens``).
    """
    p = state.k_pool.shape[2]
    trash = state.k_pool.shape[1] - 1
    c = k.shape[0]
    nb = c // p
    row = jax.lax.dynamic_slice(state.block_table[seq], (start // p,), (nb,))
    rows = jnp.where(row >= 0, row, trash)

    def blocked(x):
        return _rows(x, 1).reshape(nb, p, -1).astype(state.k_pool.dtype)
    k_pool = state.k_pool.at[layer, rows].set(blocked(k))
    v_pool = state.v_pool.at[layer, rows].set(blocked(v))
    return PagedKVState(k_pool, v_pool, state.block_table,
                        state.lengths.at[seq].set(start + c))


def block_checksums(state: PagedKVState, rows: jnp.ndarray) -> jnp.ndarray:
    """Integrity checksums for pool rows ``rows`` — jit-safe.

    Bit-casts each block's K and V content, over every layer, to
    unsigned integers and sums them mod 2**32, so any single-event upset
    (one flipped bit anywhere in the block) changes the checksum.  Pass
    the trash row to pad ``rows`` to a fixed width — its checksum comes
    back like any other and callers just ignore it, keeping one compiled
    shape.
    """
    total = None
    for pool in (state.k_pool, state.v_pool):
        nbits = jnp.dtype(pool.dtype).itemsize * 8
        u = jax.lax.bitcast_convert_type(
            pool[:, rows],
            jnp.uint16 if nbits == 16 else jnp.uint32).astype(jnp.uint32)
        s = jnp.sum(u, axis=(0, 2, 3), dtype=jnp.uint32)
        total = s if total is None else total + s
    return total


def pool_checksums(state: PagedKVState) -> jnp.ndarray:
    """Integrity checksums for *every* block row of the pool at once
    (trash row included) — jit-safe, shape ``[NB+1]``.

    The gather-free sibling of :func:`block_checksums`: one straight
    reduction over each pool with no row-index operand, so XLA emits a
    single pass over memory it was going to read anyway instead of
    materializing a gathered copy.  This is the decode hot path's fused
    verify operand — the host indexes the result by the blocks it
    actually has digests for.
    """
    total = None
    for pool in (state.k_pool, state.v_pool):
        nbits = jnp.dtype(pool.dtype).itemsize * 8
        u = jax.lax.bitcast_convert_type(
            pool, jnp.uint16 if nbits == 16 else jnp.uint32)
        # 16-bit pools accumulate mod 2**16 — a single-event upset flips
        # one bit, shifting the sum by a nonzero power of two either
        # way, and the native-width accumulate skips the elementwise
        # upcast on the decode hot path
        s = jnp.sum(u, axis=(0, 2, 3), dtype=u.dtype).astype(jnp.uint32)
        total = s if total is None else total + s
    return total


def wire_checksum(payload: bytes) -> int:
    """Integrity checksum for a serialized handoff payload — the host
    sibling of :func:`pool_checksums`.

    Same construction, different memory: the buffer is zero-padded to a
    word boundary, viewed as native-width unsigned integers, and summed
    mod 2**32, so any single-event upset on the interconnect (one
    flipped bit anywhere in the payload) changes the sum.  Pure numpy —
    the wire format must be checkable on a host that has no accelerator
    at all (the receiving pod verifies before it ever touches a device).
    """
    pad = (-len(payload)) % 4
    if pad:
        payload = payload + b"\x00" * pad
    words = np.frombuffer(payload, dtype=np.uint32)
    return int(np.sum(words, dtype=np.uint32))


class BlockDigestStore:
    """Host-side registry of *sealed* block checksums.

    A block is sealed once its content is final — the tail block a
    decode step is still appending into stays out until it fills, so
    digests never churn on the hot path.  ``scrub_batch`` hands back up
    to ``budget`` sealed blocks round-robin for a budgeted verify pass;
    the engine wires ``BlockAllocator.on_release`` to :meth:`forget` so
    a freed block's digest dies with it and a recycled block can never
    false-positive against a stale seal.
    """

    def __init__(self):
        self._sums: Dict[int, int] = {}
        self._cursor = 0

    def seal(self, block: int, checksum: int) -> None:
        self._sums[int(block)] = int(checksum)

    def forget(self, block: int) -> None:
        self._sums.pop(int(block), None)

    def get(self, block: int) -> Optional[int]:
        return self._sums.get(int(block))

    def items(self) -> List[Tuple[int, int]]:
        """Snapshot of (block, sealed checksum) pairs — safe to iterate
        while corruption handling forgets entries mid-walk."""
        return list(self._sums.items())

    def __contains__(self, block: int) -> bool:
        return int(block) in self._sums

    def __len__(self) -> int:
        return len(self._sums)

    def scrub_batch(self, budget: int) -> List[int]:
        """Next ``budget`` sealed blocks to verify (round-robin)."""
        if not self._sums or budget <= 0:
            return []
        keys = sorted(self._sums)
        self._cursor %= len(keys)
        out = [keys[(self._cursor + j) % len(keys)]
               for j in range(min(budget, len(keys)))]
        self._cursor = (self._cursor + len(out)) % len(keys)
        return out


class SharedBlockIndex:
    """Content-hashed prefix-block sharing over one allocator's pool.

    A full block of prompt tokens is identified by the *chain digest* of
    its content: ``sha1(parent_digest + tokens.tobytes())``.  Because the
    digest folds in the whole token prefix, two sequences map to the
    same digest exactly when their prompts agree through that block —
    the condition under which their KV is bit-identical and the block
    can be shared read-only.  The index tracks a refcount per registered
    block: the prefilling owner holds one reference, each sharer adds
    one, and the block returns to the allocator only when the last
    reference releases.  Entries leave the index the moment their
    refcount hits zero, so sharing happens across *concurrently live*
    sequences (a common system prompt across a batch) and the allocator
    accounting stays exact — no unreferenced cache to evict.
    """

    ROOT = b""

    def __init__(self, alloc: BlockAllocator):
        self.alloc = alloc
        self._by_digest: Dict[bytes, int] = {}
        self._digest_of: Dict[int, bytes] = {}
        self._refs: Dict[int, int] = {}
        self.hits = 0                     # blocks reused instead of refilled
        self.lookups = 0                  # share attempts (hits + misses)

    @staticmethod
    def chain(parent: bytes, tokens: np.ndarray) -> bytes:
        return hashlib.sha1(parent
                            + np.ascontiguousarray(tokens, np.int32)
                            .tobytes()).digest()

    def lookup(self, digest: bytes) -> Optional[int]:
        return self._by_digest.get(digest)

    def acquire(self, digest: bytes) -> Optional[int]:
        """Take a reference on the block holding ``digest``'s KV."""
        self.lookups += 1
        blk = self._by_digest.get(digest)
        if blk is not None:
            self._refs[blk] += 1
            self.hits += 1
        return blk

    def register(self, digest: bytes, block: int) -> None:
        """Publish a freshly prefilled block (owner's reference)."""
        if digest in self._by_digest:     # raced by an identical prompt:
            return                        # keep the first copy canonical
        self._by_digest[digest] = block
        self._digest_of[block] = digest
        self._refs[block] = self._refs.get(block, 0) + 1

    def release(self, blocks: Iterable[int] = ()) -> List[int]:
        """Drop one reference per block; returns the blocks NOT tracked
        here (still owned solely by the caller) so the caller can hand
        them straight back to the allocator.  Tracked blocks go back to
        the allocator automatically when their last reference drops."""
        untracked: List[int] = []
        for b in blocks:
            b = int(b)
            if b < 0:
                continue
            if b not in self._refs:
                untracked.append(b)
                continue
            self._refs[b] -= 1
            if self._refs[b] <= 0:
                del self._refs[b]
                self._by_digest.pop(self._digest_of.pop(b), None)
                self.alloc.release([b])
        return untracked

    def purge(self, block: int) -> None:
        """Evict a corrupted block from the index unconditionally.

        The block is headed for quarantine, not the free list, so all
        outstanding references are dropped at once — future prompts with
        the same prefix re-prefill a fresh copy instead of sharing the
        upset one.  Refcounts cannot leak: the entry is gone, so every
        holder's eventual ``release`` treats the block as untracked and
        the allocator (already holding it in quarantine) skips it.
        """
        b = int(block)
        if b not in self._refs:
            return
        del self._refs[b]
        self._by_digest.pop(self._digest_of.pop(b), None)


def gather_kv(state: PagedKVState, max_len: int, layer=0
              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Materialize each sequence's KV of layer ``layer`` up to max_len.

    Returns (k [B, max_len, KVp * hd], v likewise, valid [B, max_len]).
    """
    p = state.k_pool.shape[2]
    nb = -(-max_len // p)
    table = jnp.where(state.block_table[:, :nb] >= 0,
                      state.block_table[:, :nb], 0)
    k = state.k_pool[layer][table]             # [B, nb, P, KVp * hd]
    v = state.v_pool[layer][table]
    b = k.shape[0]
    k = k.reshape(b, nb * p, -1)[:, :max_len]
    v = v.reshape(b, nb * p, -1)[:, :max_len]
    valid = jnp.arange(max_len)[None, :] < state.lengths[:, None]
    return k, v, valid


def paged_decode_attention(q: jnp.ndarray, state: PagedKVState,
                           max_len: int, layer=0) -> jnp.ndarray:
    """q: [B, KVp, gp, hd] (one token) -> [B, KVp, gp, hd], against
    layer ``layer``.

    Reference path: gathers the full KV then runs a dense softmax.  The
    serving engine uses the Pallas kernel (``kernels.ops.paged_attention``)
    instead; tests check the two agree.
    """
    import math
    k, v, valid = gather_kv(state, max_len, layer)
    b, kvp, _, hd = q.shape
    k = k.reshape(b, max_len, kvp, hd)
    v = v.reshape(b, max_len, kvp, hd)
    scores = jnp.einsum("bkgd,btkd->bkgt", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(hd)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bkgt,btkd->bkgd", w,
                      v.astype(jnp.float32)).astype(q.dtype)
