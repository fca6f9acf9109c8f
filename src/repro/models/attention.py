"""Grouped-query attention with TP-aware head padding.

TP head padding (DESIGN.md §5): head counts that do not divide the tensor-
parallel degree are padded.  We pick the smallest (Hp, KVp) with

    tp | Hp,  tp | KVp,  kv | KVp,  KVp | Hp,  Hp >= H,

replicate each real kv head into its ``KVp // kv`` padded slots and lay the
real q heads of one kv group contiguously across those slots.  Every padded
q slot beyond the real heads carries zero weights, so the math is exact and
each shard's q heads attend only to that shard's kv heads — no cross-shard
gathers.  (kv-cache inflation is KVp/kv, e.g. 2x for qwen3 at tp=16.)

Three execution paths:
  * ``full``    — materialized scores (small seq / smoke tests)
  * ``chunked`` — online-softmax scan over kv chunks (32k prefill; the
                  XLA-native analogue of the Pallas flash kernel)
  * ``decode``  — single-token query against a KV cache
"""
from __future__ import annotations

import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.precision import PrecisionPolicy, DEFAULT_POLICY
from repro.core.quantization import pdot
from repro.kernels import ops as kops
from repro.models.layers import apply_rope, dense_init, rmsnorm, rmsnorm_init
from repro.runtime import paging

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Head-padding layout
# ---------------------------------------------------------------------------
class HeadLayout(NamedTuple):
    H: int            # real q heads
    KV: int           # real kv heads
    Hp: int           # padded q heads
    KVp: int          # padded kv heads
    gp: int           # padded group size  Hp // KVp


def head_layout(h: int, kv: int, tp: int) -> HeadLayout:
    tp = max(tp, 1)
    kvp = math.lcm(kv, tp)
    hp = ((max(h, kvp) + kvp - 1) // kvp) * kvp
    assert hp % tp == 0 and kvp % tp == 0 and hp % kvp == 0
    return HeadLayout(h, kv, hp, kvp, hp // kvp)


def q_slot_map(layout: HeadLayout) -> jnp.ndarray:
    """[Hp] -> real q head index, or -1 for a pad slot."""
    H, KV, Hp, _, _ = layout
    g = H // KV                       # real group size
    per_kv = Hp // KV                 # padded q slots per real kv head
    src = -jnp.ones((Hp,), jnp.int32)
    for c in range(KV):
        for r in range(g):
            src = src.at[c * per_kv + r].set(c * g + r)
    return src


def kv_slot_map(layout: HeadLayout) -> jnp.ndarray:
    """[KVp] -> real kv head index (every padded kv slot is a replica)."""
    return jnp.arange(layout.KVp, dtype=jnp.int32) // (layout.KVp // layout.KV)


def _pad_proj(w: jnp.ndarray, slot_map: jnp.ndarray, head_dim: int) -> jnp.ndarray:
    """Expand [D, n_real*hd] -> [D, n_pad*hd] following slot_map (-1 -> 0)."""
    d = w.shape[0]
    wr = w.reshape(d, -1, head_dim)
    safe = jnp.maximum(slot_map, 0)
    out = wr[:, safe, :] * (slot_map >= 0)[None, :, None]
    return out.reshape(d, -1)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------
def attention_init(key, cfg: ModelConfig, tp: int = 1) -> Dict:
    hd = cfg.resolved_head_dim()
    lay = head_layout(cfg.num_heads, cfg.num_kv_heads, tp)
    kq, kk, kv_, ko = jax.random.split(key, 4)
    wq = _pad_proj(dense_init(kq, cfg.d_model, cfg.num_heads * hd), q_slot_map(lay), hd)
    wk = _pad_proj(dense_init(kk, cfg.d_model, cfg.num_kv_heads * hd), kv_slot_map(lay), hd)
    wv = _pad_proj(dense_init(kv_, cfg.d_model, cfg.num_kv_heads * hd), kv_slot_map(lay), hd)
    wo_r = dense_init(ko, cfg.num_heads * hd, cfg.d_model, scale=1.0 / math.sqrt(cfg.num_heads * hd))
    # rows of wo follow the padded q layout (pad rows zero)
    smap = q_slot_map(lay)
    wo = (wo_r.reshape(-1, hd, cfg.d_model)[jnp.maximum(smap, 0)]
          * (smap >= 0)[:, None, None]).reshape(lay.Hp * hd, cfg.d_model)
    p = {"wq": wq, "wk": wk, "wv": wv, "wo": wo}
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd)
        p["k_norm"] = rmsnorm_init(hd)
    return p


# ---------------------------------------------------------------------------
# Score paths
# ---------------------------------------------------------------------------
def _mask(qpos, kpos, window: int):
    m = kpos[None, :] <= qpos[:, None]
    if window:
        m &= kpos[None, :] > qpos[:, None] - window
    return m


def full_attention(q, k, v, qpos, kpos, window: int = 0) -> jnp.ndarray:
    """q: [B,S,KVp,gp,hd]  k,v: [B,T,KVp,hd] -> [B,S,KVp,gp,hd]."""
    hd = q.shape[-1]
    scores = jnp.einsum("bskgd,btkd->bkgst", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(hd)
    scores = jnp.where(_mask(qpos, kpos, window)[None, None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", w, v.astype(jnp.float32))
    return out.astype(q.dtype)


def chunked_attention(q, k, v, qpos, kpos, window: int = 0,
                      chunk: int = 1024, unroll: bool = False) -> jnp.ndarray:
    """Online-softmax scan over kv chunks (flash-style, pure XLA)."""
    b, s, kvp, gp, hd = q.shape
    t = k.shape[1]
    nchunk = -(-t // chunk)
    pad = nchunk * chunk - t
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kpos = jnp.pad(kpos, (0, pad), constant_values=2 ** 30)
    kc = k.reshape(b, nchunk, chunk, kvp, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nchunk, chunk, kvp, hd).transpose(1, 0, 2, 3, 4)
    pc = kpos.reshape(nchunk, chunk)
    qf = q.astype(jnp.float32) / math.sqrt(hd)

    def step(carry, inp):
        m, l, acc = carry
        kb, vb, pb = inp
        s_blk = jnp.einsum("bskgd,btkd->bkgst", qf, kb.astype(jnp.float32))
        s_blk = jnp.where(_mask(qpos, pb, window)[None, None, None], s_blk, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s_blk, axis=-1))
        p = jnp.exp(s_blk - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bkgst,btkd->bkgsd", p, vb.astype(jnp.float32))
        return (m_new, l, acc), None

    init = (jnp.full((b, kvp, gp, s), NEG_INF, jnp.float32),
            jnp.zeros((b, kvp, gp, s), jnp.float32),
            jnp.zeros((b, kvp, gp, s, hd), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(step, init, (kc, vc, pc), unroll=unroll)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).astype(q.dtype)   # [B,S,KVp,gp,hd]


# ---------------------------------------------------------------------------
# Block-level apply
# ---------------------------------------------------------------------------
def _project_qkv(params, cfg: ModelConfig, x, positions,
                 policy: PrecisionPolicy):
    hd = cfg.resolved_head_dim()
    b, s, _ = x.shape
    q = pdot(x, params["wq"], policy).reshape(b, s, -1, hd)
    k = pdot(x, params["wk"], policy).reshape(b, s, -1, hd)
    v = pdot(x, params["wv"], policy).reshape(b, s, -1, hd)
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(params["k_norm"], k, cfg.norm_eps)
    if cfg.rope_theta:          # theta == 0 -> no positional rotation (Jamba)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_apply(params: Dict, cfg: ModelConfig, x: jnp.ndarray,
                    positions: jnp.ndarray,
                    policy: PrecisionPolicy = DEFAULT_POLICY,
                    chunked: Optional[bool] = None) -> jnp.ndarray:
    """Self-attention over a full sequence (train / prefill)."""
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim()
    q, k, v = _project_qkv(params, cfg, x, positions, policy)
    kvp = k.shape[2]
    q = q.reshape(b, s, kvp, -1, hd)
    pos1 = positions[0] if positions.ndim == 2 else positions
    if chunked is None:
        chunked = s > 2048
    if chunked:
        out = chunked_attention(q, k, v, pos1, pos1, cfg.sliding_window,
                                unroll=not cfg.scan_layers)
    else:
        out = full_attention(q, k, v, pos1, pos1, cfg.sliding_window)
    out = out.reshape(b, s, -1)
    return pdot(out, params["wo"], policy)


class KVCache(NamedTuple):
    k: jnp.ndarray        # [B, T, KVp, hd]  (bf16, or int8 + scales)
    v: jnp.ndarray
    pos: jnp.ndarray      # [] int32 — next write slot (== tokens seen)
    k_scale: Optional[jnp.ndarray] = None   # [B, T, KVp, 1] f32 (int8 mode)
    v_scale: Optional[jnp.ndarray] = None

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


def _cache_quant(x: jnp.ndarray):
    """Per-token-per-head absmax int8.  x: [..., hd]."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def _cache_deq(q: jnp.ndarray, scale) -> jnp.ndarray:
    if scale is None:
        return q.astype(jnp.float32)
    return q.astype(jnp.float32) * scale


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, tp: int = 1,
                  dtype=jnp.bfloat16) -> KVCache:
    lay = head_layout(cfg.num_heads, cfg.num_kv_heads, tp)
    hd = cfg.resolved_head_dim()
    length = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    shape = (batch, length, lay.KVp, hd)
    if cfg.kv_cache_dtype == "int8":
        sshape = (batch, length, lay.KVp, 1)
        return KVCache(jnp.zeros(shape, jnp.int8),
                       jnp.zeros(shape, jnp.int8),
                       jnp.zeros((), jnp.int32),
                       jnp.ones(sshape, jnp.float32),
                       jnp.ones(sshape, jnp.float32))
    return KVCache(jnp.zeros(shape, dtype),
                   jnp.zeros(shape, dtype),
                   jnp.zeros((), jnp.int32))


def paged_decode_attention_apply(params: Dict, cfg: ModelConfig,
                                 x: jnp.ndarray,
                                 state: paging.PagedKVState,
                                 positions: jnp.ndarray, layer,
                                 policy: PrecisionPolicy = DEFAULT_POLICY
                                 ) -> Tuple[jnp.ndarray, paging.PagedKVState]:
    """One-token decode step against layer ``layer`` of a paged KV
    cache.  x: [B, 1, D].

    Unlike the dense path there is no single ``cache.pos``: every batch
    slot sits at its own context length, so the caller passes per-
    sequence rope ``positions`` [B, 1] (== ``state.lengths[:, None]``).
    The new token is appended into the slot's current block and
    attention walks the block table in the Pallas kernel — the full KV
    is never materialized.  Sliding-window and int8 KV modes are dense-
    path-only (the serving engine rejects those configs up front).
    """
    b = x.shape[0]
    hd = cfg.resolved_head_dim()
    q, k, v = _project_qkv(params, cfg, x, positions, policy)
    state = paging.append_tokens(state, k[:, 0], v[:, 0], layer)
    kvp = k.shape[2]
    qh = q[:, 0].reshape(b, kvp, -1, hd)          # grouped-query layout
    out = kops.paged_attention(qh, state.k_pool, state.v_pool,
                               state.block_table, state.lengths, layer)
    out = out.reshape(b, 1, -1).astype(x.dtype)
    return pdot(out, params["wo"], policy), state


def paged_chunk_attention_apply(params: Dict, cfg: ModelConfig,
                                x: jnp.ndarray,
                                state: paging.PagedKVState,
                                positions: jnp.ndarray, seq, layer,
                                policy: PrecisionPolicy = DEFAULT_POLICY
                                ) -> Tuple[jnp.ndarray, paging.PagedKVState]:
    """One prefill *chunk* of sequence ``seq`` against layer ``layer``
    of the paged cache.

    x: [1, C, D]; positions: [1, C] absolute (chunk start must be
    block-aligned, C a whole number of blocks).  The chunk's KV is
    pasted into the sequence's blocks first (``write_prefill_chunk``),
    then attention reads the pool back through the block-table walk —
    queries see the paged prefix written by earlier chunks plus the
    in-chunk causal triangle, so chunked prefill computes exactly the
    full-prompt attention, C tokens at a time.  ``seq`` may be traced.
    """
    b, c, _ = x.shape
    hd = cfg.resolved_head_dim()
    q, k, v = _project_qkv(params, cfg, x, positions, policy)
    state = paging.write_prefill_chunk(state, k[0], v[0], seq,
                                       positions[0, 0], layer)
    kvp = k.shape[2]
    qh = q[0].reshape(c, kvp, -1, hd)             # grouped-query layout
    out = kops.paged_chunk_attention(qh, state.k_pool, state.v_pool,
                                     state.block_table[seq], positions[0],
                                     layer)
    out = out.reshape(b, c, -1).astype(x.dtype)
    return pdot(out, params["wo"], policy), state


def decode_attention_apply(params: Dict, cfg: ModelConfig, x: jnp.ndarray,
                           cache: KVCache,
                           policy: PrecisionPolicy = DEFAULT_POLICY
                           ) -> Tuple[jnp.ndarray, KVCache]:
    """One-token decode step.  x: [B, 1, D]."""
    b = x.shape[0]
    hd = cfg.resolved_head_dim()
    t = cache.k.shape[1]
    pos = jnp.full((b, 1), cache.pos, jnp.int32)
    q, k, v = _project_qkv(params, cfg, x, pos, policy)
    slot = (cache.pos % t) if cfg.sliding_window else jnp.minimum(cache.pos, t - 1)
    upd = lambda buf, val: jax.lax.dynamic_update_slice(
        buf, val.astype(buf.dtype), (0, slot, 0, 0))
    if cache.quantized:
        kq, ks = _cache_quant(k)
        vq, vs = _cache_quant(v)
        new_cache = KVCache(upd(cache.k, kq), upd(cache.v, vq),
                            cache.pos + 1,
                            upd(cache.k_scale, ks), upd(cache.v_scale, vs))
    else:
        new_cache = KVCache(upd(cache.k, k), upd(cache.v, v), cache.pos + 1)
    kvp = k.shape[2]
    qh = q.reshape(b, 1, kvp, -1, hd).astype(jnp.float32)
    k_read = _cache_deq(new_cache.k, new_cache.k_scale)
    v_read = _cache_deq(new_cache.v, new_cache.v_scale)
    scores = jnp.einsum("bskgd,btkd->bkgst", qh / math.sqrt(hd), k_read)
    # valid slots: written and (if windowed) within the ring buffer
    idx = jnp.arange(t)
    valid = idx < jnp.minimum(cache.pos + 1, t)
    scores = jnp.where(valid[None, None, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", w, v_read)
    out = out.astype(x.dtype).reshape(b, 1, -1)
    return pdot(out, params["wo"], policy), new_cache
