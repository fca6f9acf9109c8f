"""Pallas TPU kernel: fused RWKV-6 wkv recurrence.

Per head (size N): S_t = diag(w_t) S_{t-1} + k_t v_t^T,
                   y_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T).

Same VMEM-resident-state pattern as the selective-scan kernel: the
[N, N] wkv state lives in scratch across the whole sequence (grid seq
dim innermost), avoiding the XLA path's [B, Q, H, N, N] chunk
materialization.  N=64 -> 16 KiB state tile; the per-step work is a
rank-1 update + row-vector product on the VPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_Q = 128


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, s_ref, *, q: int):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    u = u_ref[0].astype(jnp.float32)                       # [N]

    def step(t, s):
        r_t = r_ref[0, t, 0, :].astype(jnp.float32)        # [N]
        k_t = k_ref[0, t, 0, :].astype(jnp.float32)
        v_t = v_ref[0, t, 0, :].astype(jnp.float32)
        w_t = w_ref[0, t, 0, :].astype(jnp.float32)
        kv = k_t[:, None] * v_t[None, :]                   # [N, N]
        y_t = jnp.sum(r_t[:, None] * (s + u[:, None] * kv), axis=0)
        y_ref[0, t, 0, :] = y_t.astype(y_ref.dtype)
        return w_t[:, None] * s + kv

    s_ref[...] = jax.lax.fori_loop(0, q, step, s_ref[...])


@functools.partial(jax.jit, static_argnames=("q", "interpret"))
def wkv_pallas(r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
               w: jnp.ndarray, u: jnp.ndarray, q: int = DEFAULT_Q,
               interpret: bool = False) -> jnp.ndarray:
    """r, k, v, w: [B, S, H, N]; u: [H, N] -> y [B, S, H, N].

    S % q == 0 (ops.py pads with identity decay).
    """
    bsz, s, h, n = r.shape
    assert s % q == 0, (s, q)
    grid = (bsz, h, s // q)
    kernel = functools.partial(_wkv_kernel, q=q)
    spec = pl.BlockSpec((1, q, 1, n), lambda i, j, c: (i, c, j, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec((1, n), lambda i, j, c: (j, 0))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((bsz, s, h, n), r.dtype),
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u)
