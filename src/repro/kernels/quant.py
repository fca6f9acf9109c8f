"""Pallas TPU kernel: fused row-wise absmax int8 quantization.

One VMEM pass computes the per-row absmax and writes the quantized int8
rows plus fp32 scales — fusing what XLA would schedule as a reduce +
HBM round-trip + elementwise pass.  Feeds the int8 matmul kernel's
activation operand (dynamic per-tensor/row activation quantization of the
DPU path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


QMAX = 127.0
DEFAULT_BM = 256


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / QMAX
    q_ref[...] = jnp.clip(jnp.round(x / scale), -QMAX, QMAX).astype(jnp.int8)
    s_ref[...] = scale


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def rowwise_quant_pallas(x: jnp.ndarray, bm: int = DEFAULT_BM,
                         interpret: bool = False):
    """x: [M, K] float -> (q [M, K] int8, scale [M, 1] f32).

    M must be a multiple of bm; the full K extent of a row block lives in
    VMEM (ops.py asserts K*bm fits).
    """
    m, k = x.shape
    assert m % bm == 0, (m, bm)
    grid = (m // bm,)
    return pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0)),
                   pl.BlockSpec((bm, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((m, k), jnp.int8),
                   jax.ShapeDtypeStruct((m, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x)
