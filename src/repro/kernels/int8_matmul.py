"""Pallas TPU kernel: blocked int8 x int8 -> int32 matmul (the DPU path).

The DPU's deep-pipelined INT8 MAC array maps onto the MXU's int8 mode
(2x bf16 throughput on v5e).  Tiling: [bm, bk] x [bk, bn] blocks staged
through VMEM; the K grid dimension is innermost ("arbitrary") so each
[bm, bn] output tile stays resident in VMEM across the K loop —
the VMEM-as-accumulator role the DPU assigns to its on-chip activation
buffers.

Block defaults (128, 256, 128) keep the working set at
128*256 + 256*128 + 128*128*4 = 128 KiB << 16 MiB VMEM and all matmul
dims MXU-aligned (multiples of 128 / int8 lane packing of 32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



DEFAULT_BM = 128
DEFAULT_BK = 256
DEFAULT_BN = 128


def _int8_matmul_kernel(x_ref, w_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def int8_matmul_pallas(x: jnp.ndarray, w: jnp.ndarray,
                       bm: int = DEFAULT_BM, bk: int = DEFAULT_BK,
                       bn: int = DEFAULT_BN,
                       interpret: bool = False) -> jnp.ndarray:
    """x: [M, K] int8, w: [K, N] int8 -> [M, N] int32.

    M, K, N must be multiples of the block sizes (ops.py pads).
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2 and m % bm == 0 and k % bk == 0 and n % bn == 0, (
        (m, k, n), (bm, bk, bn))
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        _int8_matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w)
