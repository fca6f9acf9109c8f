"""Pallas TPU kernel: causal flash attention forward (online softmax).

Blocked [bq x bk] score tiles with running (m, l, acc) statistics held in
VMEM scratch; the KV grid dimension is innermost so statistics stay
resident across the KV sweep.  This is the TPU-native replacement for the
prefill hot loop — VMEM tiles instead of SRAM tiles, MXU matmuls for both
QK^T and PV.

Layout: q, k, v are [BH, S, D] (batch*heads folded; GQA callers repeat kv
in the ops wrapper).  Causal masking is positional, so block pairs with
q_block < kv_block contribute nothing (masked to -inf; the `l` correction
keeps the math exact).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BQ = 256
DEFAULT_BK = 256
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, scale: float, num_kv: int):
    q_idx = pl.program_id(1)
    kv_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Causality: a tile whose first k position is past the tile's last q
    # position is fully masked — skip both MXU matmuls for the whole
    # upper-triangular half of the (q, kv) grid (~2x at long S).
    @pl.when(kv_idx * bk <= q_idx * bq + bq - 1)
    def _tile():
        q = q_ref[0].astype(jnp.float32) * scale      # [bq, d]
        k = k_ref[0].astype(jnp.float32)              # [bk, d]
        v = v_ref[0].astype(jnp.float32)

        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # [bq, bk]
        q_pos = q_idx * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        k_pos = kv_idx * bk + jax.lax.broadcasted_iota(jnp.int32,
                                                       (bq, bk), 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kv_idx == num_kv - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "bk", "interpret"))
def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                           interpret: bool = False) -> jnp.ndarray:
    """q, k, v: [BH, S, D] -> [BH, S, D], causal."""
    bh, s, d = q.shape
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    num_kv = s // bk
    scale = 1.0 / math.sqrt(d)
    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, scale=scale,
                               num_kv=num_kv)
    return pl.pallas_call(
        kernel,
        grid=(bh, s // bq, num_kv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            # VMEM running statistics for the online softmax
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


