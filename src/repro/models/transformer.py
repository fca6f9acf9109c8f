"""Model composition: sublayer pattern -> super-blocks -> segment scans.

Layer stacks are built from *super-blocks*: the smallest periodic unit of
the architecture's layer pattern (1 for homogeneous archs, 8 for Jamba's
1-attention-per-8 + MoE-every-2 interleave).  Super-blocks are stacked
with a leading dimension and executed with ``lax.scan`` — essential to
keep HLO size sane at 126 layers x 512 devices.

MPAI integration: a :class:`~repro.core.partition.PartitionPlan` splits
the stack into contiguous segments; each segment runs its own scan under
its own precision policy, so a partition boundary is literally a scan
boundary (and, in stage-pipeline mode, a device-group boundary).
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.partition import PartitionPlan
from repro.core.precision import PrecisionPolicy
from repro.models import attention as attn
from repro.models import mamba as mam
from repro.runtime import paging
from repro.runtime.paging import PagedKVState
from repro.models import rwkv as rwk
from repro.models import moe as moe_mod
from repro.models.layers import (cross_entropy, embed, embedding_init,
                                 lm_logits, make_norm, mlp_apply, mlp_init)

AUX_LOSS_COEF = 0.01


# ---------------------------------------------------------------------------
# Layer pattern
# ---------------------------------------------------------------------------
def pattern_period(cfg: ModelConfig) -> int:
    p = cfg.attn_every if cfg.mixer == "hybrid" else 1
    if cfg.moe is not None:
        p = math.lcm(p, cfg.moe.every)
    assert cfg.num_layers % p == 0, (cfg.name, cfg.num_layers, p)
    return p


def sublayer_pattern(cfg: ModelConfig) -> Tuple[Tuple[str, bool], ...]:
    p = pattern_period(cfg)
    pat = tuple((cfg.layer_mixer(j), cfg.is_moe_layer(j)) for j in range(p))
    # the pattern must be offset-invariant (periodicity check)
    for i in range(cfg.num_layers):
        assert (cfg.layer_mixer(i), cfg.is_moe_layer(i)) == pat[i % p], cfg.name
    return pat


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def _sublayer_init(key, cfg: ModelConfig, kind: str, is_moe: bool, tp: int) -> Dict:
    norm_init, _ = make_norm("rmsnorm")
    k1, k2 = jax.random.split(key)
    p: Dict[str, Any] = {"norm1": norm_init(cfg.d_model),
                         "norm2": norm_init(cfg.d_model)}
    if kind == "attention":
        p["mixer"] = attn.attention_init(k1, cfg, tp)
    elif kind == "mamba":
        p["mixer"] = mam.mamba_init(k1, cfg)
    elif kind == "rwkv6":
        p["mixer"] = rwk.time_mix_init(k1, cfg, tp)
    else:
        raise ValueError(kind)
    if kind == "rwkv6":
        p["mlp"] = rwk.channel_mix_init(k2, cfg)
    elif is_moe:
        p["mlp"] = moe_mod.moe_init(k2, cfg.d_model, cfg.moe, cfg.glu)
    else:
        p["mlp"] = mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.glu)
    return p


def superblock_init(key, cfg: ModelConfig, tp: int) -> Dict:
    pat = sublayer_pattern(cfg)
    keys = jax.random.split(key, len(pat))
    return {f"sub_{j}": _sublayer_init(keys[j], cfg, kind, is_moe, tp)
            for j, (kind, is_moe) in enumerate(pat)}


def model_init(key, cfg: ModelConfig, tp: int = 1) -> Dict:
    period = pattern_period(cfg)
    n_super = cfg.num_layers // period
    k_emb, k_head, k_layers = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, n_super)
    blocks = [superblock_init(layer_keys[i], cfg, tp) for i in range(n_super)]
    layers = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)
    norm_init, _ = make_norm("rmsnorm")
    params = {"embed": embedding_init(k_emb, cfg.vocab_size, cfg.d_model),
              "layers": layers,
              "final_norm": norm_init(cfg.d_model)}
    if not cfg.tie_embeddings:
        params["lm_head"] = embedding_init(k_head, cfg.vocab_size, cfg.d_model)
    return params


# ---------------------------------------------------------------------------
# Per-sublayer caches (decode)
# ---------------------------------------------------------------------------
def _sublayer_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                    tp: int):
    if kind == "attention":
        return attn.init_kv_cache(cfg, batch, max_len, tp)
    if kind == "mamba":
        return mam.init_mamba_cache(cfg, batch)
    if kind == "rwkv6":
        return rwk.init_rwkv_cache(cfg, batch, tp)
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, tp: int = 1):
    pat = sublayer_pattern(cfg)
    n_super = cfg.num_layers // len(pat)
    one = {f"sub_{j}": _sublayer_cache(cfg, kind, batch, max_len, tp)
           for j, (kind, _) in enumerate(pat)}
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (n_super,) + x.shape), one)


def init_paged_decode_cache(cfg: ModelConfig, batch: int, num_blocks: int,
                            block_size: int, tp: int = 1,
                            max_blocks: Optional[int] = None,
                            dtype=jnp.bfloat16):
    """Per-sublayer paged KV caches for continuous-batching decode.

    Every attention sublayer gets its own block pool, ``[n_super, rows,
    P, KVp * hd]`` (one layer per super-block); table and lengths are
    replicated per super-block so they scan like a dense cache (the
    engine keeps them in lockstep from its host-side mirror), while the
    pools ride the layer scan whole (:func:`_paged_scan`).  Paged
    decode is attention-only for now — SSM /
    RWKV recurrent states are tiny and per-slot already, but their
    prefill handoff into a mixed paged batch is future work (see
    ROADMAP "decode engine").
    """
    pat = sublayer_pattern(cfg)
    bad = [kind for kind, _ in pat if kind != "attention"]
    if bad:
        raise ValueError(f"paged decode needs an attention-only stack; "
                         f"{cfg.name} has {sorted(set(bad))} sublayers")
    if cfg.sliding_window:
        raise ValueError("paged decode does not support sliding windows")
    if cfg.kv_cache_dtype == "int8":
        raise ValueError("paged decode does not support int8 KV caches")
    lay = attn.head_layout(cfg.num_heads, cfg.num_kv_heads, tp)
    hd = cfg.resolved_head_dim()
    n_super = cfg.num_layers // len(pat)
    stack = lambda x: jnp.broadcast_to(x[None], (n_super,) + x.shape)
    caches = {}
    for j in range(len(pat)):
        st = paging.init_paged_cache(batch, num_blocks, block_size, lay.KVp,
                                     hd, dtype=dtype, max_blocks=max_blocks,
                                     layers=n_super)
        caches[f"sub_{j}"] = st._replace(block_table=stack(st.block_table),
                                         lengths=stack(st.lengths))
    return caches


def first_paged_state(cache) -> Optional[PagedKVState]:
    """The first PagedKVState in a cache tree (None when fully dense)."""
    for leaf in jax.tree_util.tree_leaves(
            cache, is_leaf=lambda s: isinstance(s, PagedKVState)):
        if isinstance(leaf, PagedKVState):
            return leaf
    return None


# ---------------------------------------------------------------------------
# Apply
# ---------------------------------------------------------------------------
def _sublayer_apply(sub: Dict, cfg: ModelConfig, kind: str, is_moe: bool,
                    x: jnp.ndarray, positions: jnp.ndarray,
                    policy: PrecisionPolicy, tp: int,
                    cache=None, decode: bool = False, chunk_seq=None,
                    layer=None):
    """Returns (x, new_cache, aux).  ``chunk_seq`` (paged caches only)
    switches prefill into chunked-paged mode: the window is one chunk of
    sequence ``chunk_seq`` at absolute ``positions``, pasted into its
    blocks and attended against the paged prefix.  A paged cache holds
    every layer's pool; ``layer`` is the one this sublayer owns."""
    _, norm = make_norm("rmsnorm")
    aux = jnp.zeros((), jnp.float32)
    h = norm(sub["norm1"], x, cfg.norm_eps)
    new_cache = cache
    if kind == "attention":
        if decode:
            if isinstance(cache, PagedKVState):
                out, new_cache = attn.paged_decode_attention_apply(
                    sub["mixer"], cfg, h, cache, positions, layer, policy)
            else:
                out, new_cache = attn.decode_attention_apply(
                    sub["mixer"], cfg, h, cache, policy)
        elif chunk_seq is not None and isinstance(cache, PagedKVState):
            out, new_cache = attn.paged_chunk_attention_apply(
                sub["mixer"], cfg, h, cache, positions, chunk_seq, layer,
                policy)
        else:
            out = attn.attention_apply(sub["mixer"], cfg, h, positions, policy)
            if cache is not None:
                new_cache = _prefill_kv(sub["mixer"], cfg, h, positions, cache,
                                        policy)
    elif kind == "mamba":
        if decode:
            out, new_cache = mam.mamba_decode_step(sub["mixer"], cfg, h, cache,
                                                   policy)
        else:
            if cache is not None:
                out, new_cache = mam.mamba_apply(sub["mixer"], cfg, h, policy,
                                                 return_state=True)
            else:
                out = mam.mamba_apply(sub["mixer"], cfg, h, policy)
    elif kind == "rwkv6":
        if decode:
            y, s_new, x_last = rwk.time_mix_decode(sub["mixer"], cfg, h[:, 0],
                                                   cache.s, cache.x_tmix, tp,
                                                   policy)
            out = y[:, None] if y.ndim == 2 else y
        else:
            out, s_new, x_last = rwk.time_mix_apply(
                sub["mixer"], cfg, h,
                jnp.zeros_like(h[:, 0]) if cache is None else cache.x_tmix,
                tp, policy)
        if cache is not None:
            new_cache = rwk.RWKVCache(s_new, x_last, cache.x_cmix)
    else:
        raise ValueError(kind)
    x = x + out

    h2 = norm(sub["norm2"], x, cfg.norm_eps)
    if kind == "rwkv6":
        if decode:
            out2, x_last2 = rwk.channel_mix_decode(sub["mlp"], h2[:, 0],
                                                   new_cache.x_cmix, policy)
            out2 = out2[:, None]
        else:
            prev = (jnp.zeros_like(h2[:, 0]) if new_cache is None
                    else new_cache.x_cmix)
            out2, x_last2 = rwk.channel_mix_apply(sub["mlp"], h2, prev, policy)
        if new_cache is not None:
            new_cache = rwk.RWKVCache(new_cache.s, new_cache.x_tmix, x_last2)
    elif is_moe:
        out2, aux = moe_mod.moe_apply(sub["mlp"], cfg.moe, h2, cfg.act,
                                      cfg.glu, policy)
    else:
        out2 = mlp_apply(sub["mlp"], h2, cfg.act, cfg.glu, policy)
    return x + out2, new_cache, aux


def _prefill_kv(mix_params, cfg, h, positions, cache, policy):
    """Recompute k/v over the prefill window and write into the cache."""
    q, k, v = attn._project_qkv(mix_params, cfg, h, positions, policy)
    s = k.shape[1]
    t = cache.k.shape[1]
    ks = vs = None
    if cache.quantized:
        k, ks = attn._cache_quant(k)
        v, vs = attn._cache_quant(v)

    def write(buf, val):
        if val is None:
            return None
        if s >= t:        # keep only the trailing window, ring-aligned so
            # that position p lands in slot p % t (decode's ring writes)
            shift = (s - t) % t
            return jnp.roll(val[:, s - t:], shift, axis=1).astype(buf.dtype)
        return jax.lax.dynamic_update_slice(buf, val.astype(buf.dtype),
                                            (0, 0, 0, 0))
    if cache.quantized:
        return attn.KVCache(write(cache.k, k), write(cache.v, v),
                            cache.pos + s,
                            write(cache.k_scale, ks),
                            write(cache.v_scale, vs))
    return attn.KVCache(write(cache.k, k), write(cache.v, v), cache.pos + s)


# ---------------------------------------------------------------------------
# Segment scans
# ---------------------------------------------------------------------------
def _segment_scan(seg_params, cfg: ModelConfig, x, positions,
                  policy: PrecisionPolicy, tp: int, caches=None,
                  decode: bool = False, chunk_seq=None,
                  layer_offset: int = 0):
    """Scan a segment's super-blocks.  Returns (x, new_caches, aux_sum).

    Paged caches hold the pools of the whole stack; this segment's
    super-blocks own layers ``layer_offset ..`` of them."""
    pat = sublayer_pattern(cfg)

    def block(x, blk_params, blk_cache, layer=None):
        aux_tot = jnp.zeros((), jnp.float32)
        new_blk_cache = {} if blk_cache is not None else None
        for j, (kind, is_moe) in enumerate(pat):
            c = None if blk_cache is None else blk_cache[f"sub_{j}"]
            x, c2, aux = _sublayer_apply(blk_params[f"sub_{j}"], cfg, kind,
                                         is_moe, x, positions, policy, tp,
                                         c, decode, chunk_seq, layer)
            if new_blk_cache is not None:
                new_blk_cache[f"sub_{j}"] = c2
            aux_tot = aux_tot + aux
        return x, new_blk_cache, aux_tot

    if cfg.remat:
        ckpt_policy = (
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            if cfg.remat_policy == "dots" else None)
        block = jax.checkpoint(block, static_argnums=(), policy=ckpt_policy)
        if cfg.remat_group and not cfg.scan_layers:
            # probes mimic the sqrt-remat double-recompute cost structure
            block = jax.checkpoint(block, static_argnums=())

    if first_paged_state(caches) is not None:
        return _paged_scan(block, cfg, x, seg_params, caches, layer_offset)

    if not cfg.scan_layers:          # unrolled (cost probes, tiny models)
        n = jax.tree_util.tree_leaves(seg_params)[0].shape[0]
        aux = jnp.zeros((), jnp.float32)
        new_caches = []
        for i in range(n):
            blk_params = jax.tree_util.tree_map(lambda a: a[i], seg_params)
            blk_cache = (None if caches is None else
                         jax.tree_util.tree_map(lambda a: a[i], caches))
            x, nc, a = block(x, blk_params, blk_cache)
            aux = aux + a
            new_caches.append(nc)
        if caches is None:
            return x, None, aux
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *new_caches)
        return x, stacked, aux

    if caches is None:
        def body(carry, blk_params):
            x, aux = carry
            x, _, a = block(x, blk_params, None)
            return (x, aux + a), None

        g = cfg.remat_group
        n = jax.tree_util.tree_leaves(seg_params)[0].shape[0]
        if cfg.remat and g and n % max(g, 1) == 0 and n >= g > 0:
            # sqrt remat: outer scan over groups of g blocks, each group
            # checkpointed as a unit (saves n/g + g boundaries, not n)
            grouped = jax.tree_util.tree_map(
                lambda a: a.reshape(n // g, g, *a.shape[1:]), seg_params)

            @jax.checkpoint
            def group_body(carry, grp_params):
                return jax.lax.scan(body, carry, grp_params)[0], None
            (x, aux), _ = jax.lax.scan(
                group_body, (x, jnp.zeros((), jnp.float32)), grouped)
            return x, None, aux
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   seg_params)
        return x, None, aux

    def body(carry, inp):
        blk_params, blk_cache = inp
        x, aux = carry
        x, new_cache, a = block(x, blk_params, blk_cache)
        return (x, aux + a), new_cache
    (x, aux), new_caches = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), (seg_params, caches))
    return x, new_caches, aux


def _paged_scan(block, cfg: ModelConfig, x, seg_params, caches,
                layer_offset: int):
    """Scan a segment over paged caches, carrying every pool whole.

    ``caches``: ``{sub_j: PagedKVState}`` whose pools hold every layer of
    the stack and whose tables and lengths hold this segment's n
    super-blocks.  Super-block i writes its new token rows into layer
    ``layer_offset + i`` of each pool and its kernel reads that layer in
    place.  The pools are the scan's carry, not its xs/ys: a ys buffer
    cannot alias the xs it is read from, and slicing a layer out of the
    pool copies it.  Returns (x, new_caches, aux_sum).
    """
    assert all(isinstance(st, PagedKVState) for st in caches.values()), \
        "paged decode needs a paged cache for every sublayer"
    n = jax.tree_util.tree_leaves(seg_params)[0].shape[0]

    def body(carry, inp):
        x, aux, pools = carry
        blk_params, layer, tables = inp
        blk_cache = {key: PagedKVState(*pools[key], *tables[key])
                     for key in pools}
        x, new, a = block(x, blk_params, blk_cache, layer)
        return ((x, aux + a,
                 {key: (st.k_pool, st.v_pool) for key, st in new.items()}),
                {key: (st.block_table, st.lengths)
                 for key, st in new.items()})

    init = (x, jnp.zeros((), jnp.float32),
            {key: (st.k_pool, st.v_pool) for key, st in caches.items()})
    xs = (seg_params, layer_offset + jnp.arange(n, dtype=jnp.int32),
          {key: (st.block_table, st.lengths) for key, st in caches.items()})
    if cfg.scan_layers:
        (x, aux, pools), tables = jax.lax.scan(body, init, xs)
    else:                            # unrolled (cost probes, tiny models)
        carry, outs = init, []
        for i in range(n):
            carry, out = body(carry,
                              jax.tree_util.tree_map(lambda a: a[i], xs))
            outs.append(out)
        x, aux, pools = carry
        tables = jax.tree_util.tree_map(lambda *ys: jnp.stack(ys), *outs)
    return (x, {key: PagedKVState(*pools[key], *tables[key])
                for key in pools}, aux)


def _slice_stack(tree, lo: int, hi: int):
    return jax.tree_util.tree_map(lambda a: a[lo:hi], tree)


def _is_paged(node) -> bool:
    return isinstance(node, PagedKVState)


def _segment_cache(cache, lo: int, hi: int):
    """Super-blocks ``lo:hi`` of a cache tree.  Dense caches are sliced;
    a paged cache keeps its pools whole (the segment indexes them at its
    layer offset) and slices only its tables and lengths."""
    def part(c):
        if _is_paged(c):
            return c._replace(block_table=c.block_table[lo:hi],
                              lengths=c.lengths[lo:hi])
        return c[lo:hi]
    return jax.tree_util.tree_map(part, cache, is_leaf=_is_paged)


def _join_segments(parts):
    """Inverse of :func:`_segment_cache` over the segments in order.  A
    paged pool comes from the last segment, which wrote after the
    others into the same pool."""
    def join(*cs):
        if _is_paged(cs[0]):
            return cs[-1]._replace(
                block_table=jnp.concatenate([c.block_table for c in cs]),
                lengths=jnp.concatenate([c.lengths for c in cs]))
        return jnp.concatenate(cs, axis=0)
    return jax.tree_util.tree_map(join, *parts, is_leaf=_is_paged)


# ---------------------------------------------------------------------------
# Public model API
# ---------------------------------------------------------------------------
class LMOutput(NamedTuple):
    logits: jnp.ndarray
    cache: Any
    aux_loss: jnp.ndarray


def forward(params: Dict, cfg: ModelConfig, tokens: jnp.ndarray,
            plan: Optional[PartitionPlan] = None, tp: int = 1,
            cache=None, decode: bool = False,
            frontend_embeds: Optional[jnp.ndarray] = None,
            chunk=None) -> LMOutput:
    """Unified forward.

    * train/prefill: tokens [B, S], cache None or prefill-target cache
    * decode: tokens [B, 1], cache required
    * chunked paged prefill: tokens [1, C], cache paged, ``chunk`` =
      ``(seq, start)`` — one chunk of sequence ``seq`` at absolute
      positions ``start .. start+C-1`` (see :func:`prefill_paged_chunk`)
    """
    period = pattern_period(cfg)
    plan = plan or PartitionPlan.uniform(cfg.num_layers)
    plan = plan.align_to_period(period, cfg.num_layers)
    plan.validate(cfg.num_layers, period)

    x = embed(params["embed"], tokens,
              plan.embed_policy.precision.compute_dtype)
    if frontend_embeds is not None:
        x = jnp.concatenate([frontend_embeds.astype(x.dtype), x], axis=1)
    chunk_seq = None
    if decode:
        ps = first_paged_state(cache)
        if ps is not None:
            # paged decode: every batch slot sits at its own context
            # length, so positions are per-sequence, not a scalar
            lens = ps.lengths[0] if ps.lengths.ndim == 2 else ps.lengths
            positions = lens[:, None].astype(jnp.int32)
        else:
            start = cache_position(cfg, cache)
            positions = jnp.broadcast_to(start,
                                         (x.shape[0], 1)).astype(jnp.int32)
    elif chunk is not None:
        chunk_seq, start = chunk
        positions = (jnp.asarray(start, jnp.int32)
                     + jnp.arange(x.shape[1], dtype=jnp.int32))[None, :]
    else:
        positions = jnp.broadcast_to(jnp.arange(x.shape[1], dtype=jnp.int32),
                                     x.shape[:2])

    aux_total = jnp.zeros((), jnp.float32)
    new_cache_parts = []
    for seg in plan.segments:
        lo, hi = seg.start // period, seg.end // period
        seg_params = _slice_stack(params["layers"], lo, hi)
        seg_cache = None if cache is None else _segment_cache(cache, lo, hi)
        x, seg_new, aux = _segment_scan(seg_params, cfg, x, positions,
                                        seg.policy, tp, seg_cache, decode,
                                        chunk_seq, lo)
        if cache is not None:
            # the next segment writes into the pools this one left
            cache = jax.tree_util.tree_map(
                lambda c, n: (c._replace(k_pool=n.k_pool, v_pool=n.v_pool)
                              if _is_paged(c) else c),
                cache, seg_new, is_leaf=_is_paged)
        new_cache_parts.append(seg_new)
        aux_total = aux_total + aux

    _, norm = make_norm("rmsnorm")
    x = norm(params["final_norm"], x, cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = lm_logits(table, x, plan.head_policy)

    new_cache = None
    if cache is not None:
        new_cache = _join_segments(new_cache_parts)
    return LMOutput(logits, new_cache, aux_total)


def cache_position(cfg: ModelConfig, cache) -> jnp.ndarray:
    """Current decode position from the first attention cache (or 0-d int)."""
    leaves = jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(lambda x: x, cache))
    # attention caches carry pos as an int32 scalar per layer stack
    for leaf in leaves:
        if leaf.dtype == jnp.int32 and leaf.ndim == 1:
            return leaf[0]
    return jnp.zeros((), jnp.int32)


def loss_fn(params: Dict, cfg: ModelConfig, tokens: jnp.ndarray,
            labels: jnp.ndarray, plan: Optional[PartitionPlan] = None,
            tp: int = 1,
            frontend_embeds: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    out = forward(params, cfg, tokens, plan, tp,
                  frontend_embeds=frontend_embeds)
    logits = out.logits[:, -tokens.shape[1]:]   # skip frontend positions
    return cross_entropy(logits, labels) + AUX_LOSS_COEF * out.aux_loss


def decode_step(params: Dict, cfg: ModelConfig, tokens: jnp.ndarray, cache,
                plan: Optional[PartitionPlan] = None, tp: int = 1) -> LMOutput:
    return forward(params, cfg, tokens, plan, tp, cache=cache, decode=True)


def prefill(params: Dict, cfg: ModelConfig, tokens: jnp.ndarray, cache,
            plan: Optional[PartitionPlan] = None, tp: int = 1,
            frontend_embeds: Optional[jnp.ndarray] = None) -> LMOutput:
    return forward(params, cfg, tokens, plan, tp, cache=cache, decode=False,
                   frontend_embeds=frontend_embeds)


def prefill_paged_chunk(params: Dict, cfg: ModelConfig, tokens: jnp.ndarray,
                        caches, seq, start,
                        plan: Optional[PartitionPlan] = None,
                        tp: int = 1) -> LMOutput:
    """One chunk of a chunked paged prefill.

    tokens: [1, C] — tokens ``start .. start+C-1`` of sequence ``seq``
    (``start`` and ``C`` block-aligned); ``caches`` is the engine's
    paged cache tree.  The chunk's KV lands directly in the sequence's
    blocks and attention reads the paged prefix back, so no dense
    scratch cache bounds the prompt length.  ``seq``/``start`` may be
    traced — the serving engine jits this once per chunk shape.
    Returns logits for the chunk (callers use ``logits[:, -1]`` of the
    final chunk to sample the first output token) and the updated caches.
    """
    return forward(params, cfg, tokens, plan, tp, cache=caches,
                   decode=False, chunk=(seq, start))
