"""Pallas TPU kernel: fused Mamba selective scan.

Motivated directly by the §Perf finding on jamba train_4k: the XLA
chunked-associative-scan path materializes [B, Q, d_inner, N] state
tensors for the backward pass (1.38 TB/dev transient at full scale).
The fused kernel keeps the running state h [bd, N] in VMEM scratch and
streams the sequence through it — the TPU analogue of the CUDA
selective-scan kernel's shared-memory recurrence (DESIGN.md §2):

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) B_t
    y_t = h_t . C_t + D * x_t

Grid: (batch, d_inner blocks, seq chunks) with the seq dimension
innermost ("arbitrary"), so each [bd, N] state tile stays resident in
VMEM across its whole sequence.  Inside a chunk the recurrence runs as a
``fori_loop`` over positions — [bd, N] elementwise VPU work per step.

Block sizing: bd=512, N=16 -> h tile 32 KiB; x/dt chunks [Q=256, bd]
bf16 = 256 KiB; B/C chunks [Q, N] tiny.  Working set ~1 MiB << VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BD = 512
DEFAULT_Q = 256


def _selective_scan_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref,
                           y_ref, h_ref, *, q: int):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[...].astype(jnp.float32)            # [bd, N]
    dskip = d_ref[...].astype(jnp.float32)        # [bd]

    def step(t, h):
        dt_t = dt_ref[0, t, :].astype(jnp.float32)        # [bd]
        x_t = x_ref[0, t, :].astype(jnp.float32)          # [bd]
        b_t = b_ref[0, t, :].astype(jnp.float32)          # [N]
        c_t = c_ref[0, t, :].astype(jnp.float32)          # [N]
        a_bar = jnp.exp(dt_t[:, None] * a)                # [bd, N]
        h = a_bar * h + (dt_t * x_t)[:, None] * b_t[None, :]
        y_t = jnp.sum(h * c_t[None, :], axis=1) + dskip * x_t
        y_ref[0, t, :] = y_t.astype(y_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, q, step, h_ref[...])


@functools.partial(jax.jit,
                   static_argnames=("bd", "q", "interpret"))
def selective_scan_pallas(x: jnp.ndarray, dt: jnp.ndarray, b: jnp.ndarray,
                          c: jnp.ndarray, a: jnp.ndarray, d: jnp.ndarray,
                          bd: int = DEFAULT_BD, q: int = DEFAULT_Q,
                          interpret: bool = False) -> jnp.ndarray:
    """x, dt: [B, S, D]; b, c: [B, S, N]; a: [D, N]; d: [D] -> y [B, S, D].

    D % bd == 0 and S % q == 0 (ops.py pads).
    """
    bsz, s, dim = x.shape
    n = b.shape[-1]
    assert dim % bd == 0 and s % q == 0, (dim, bd, s, q)
    grid = (bsz, dim // bd, s // q)
    kernel = functools.partial(_selective_scan_kernel, q=q)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q, bd), lambda i, j, k: (i, k, j)),   # x
            pl.BlockSpec((1, q, bd), lambda i, j, k: (i, k, j)),   # dt
            pl.BlockSpec((1, q, n), lambda i, j, k: (i, k, 0)),    # B
            pl.BlockSpec((1, q, n), lambda i, j, k: (i, k, 0)),    # C
            pl.BlockSpec((bd, n), lambda i, j, k: (j, 0)),         # A
            pl.BlockSpec((bd,), lambda i, j, k: (j,)),             # D skip
        ],
        out_specs=pl.BlockSpec((1, q, bd), lambda i, j, k: (i, k, j)),
        out_shape=jax.ShapeDtypeStruct((bsz, s, dim), x.dtype),
        scratch_shapes=[pltpu.VMEM((bd, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, b, c, a, d)
