"""Co-processing stage pipeline — the DPU->VPU handoff at pod scale.

The paper streams activations from the INT8 engine to the FP16 engine over
a board-level link.  At pod scale the analogue is a *stage axis* of the
device mesh: device group s holds segment s's parameters and executes its
precision policy; activations hand off to group s+1 with
``lax.ppermute`` while group s starts the next microbatch — a
double-buffered inference pipeline (GPipe-style schedule, depth-1 buffers).

Implemented with ``shard_map`` over the stage axis.  All stages execute the
same program; ``lax.switch`` on the stage index selects the segment body,
so only the resident segment actually runs per device group.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, List, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_apply(mesh: Mesh, stage_axis: str,
                   stage_fns: Sequence[Callable],
                   stage_params_stacked,
                   micro_inputs: jnp.ndarray,
                   hidden_shape: tuple, out_shape: tuple,
                   hidden_dtype=jnp.bfloat16, out_dtype=jnp.float32):
    """Run ``micro_inputs`` [n_micro, ...] through a linear stage pipeline.

    ``stage_fns[s](x, params_s) -> (hidden, out)``: stage s consumes the
    previous stage's hidden (stage 0 consumes the raw microbatch) and
    emits (hidden_for_next, final_output_or_zeros).

    ``stage_params_stacked``: pytree with leading dim = num_stages,
    sharded over ``stage_axis``.
    Returns outputs [n_micro, *out_shape] (valid output of the last stage).
    """
    num_stages = len(stage_fns)
    n_micro = micro_inputs.shape[0]
    steps = n_micro + num_stages - 1
    perm = [(s, s + 1) for s in range(num_stages - 1)]

    def body(params_local, xs_local):
        params_local = jax.tree_util.tree_map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(stage_axis)

        def run(x):
            branches = [partial(fn, params=params_local) for fn in stage_fns]
            return jax.lax.switch(stage, branches, x)

        def step(carry, t):
            buf, outs = carry
            feed_idx = jnp.clip(t, 0, n_micro - 1)
            feed = jax.lax.dynamic_index_in_dim(xs_local, feed_idx, 0,
                                                keepdims=False)
            x = jnp.where(stage == 0,
                          feed.astype(hidden_dtype),
                          buf)
            hidden, out = run(x)
            buf_next = jax.lax.ppermute(hidden, stage_axis, perm)
            out_idx = jnp.clip(t - (num_stages - 1), 0, n_micro - 1)
            take = t >= (num_stages - 1)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(take, out.astype(out_dtype),
                                jax.lax.dynamic_index_in_dim(
                                    outs, out_idx, 0, keepdims=False)),
                out_idx, 0)
            return (buf_next, outs), None

        buf0 = jnp.zeros(hidden_shape, hidden_dtype)
        outs0 = jnp.zeros((n_micro,) + out_shape, out_dtype)
        (_, outs), _ = jax.lax.scan(step, (buf0, outs0),
                                    jnp.arange(steps))
        return outs[None]              # leading stage dim for out_specs

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(stage_axis), P()),
                       out_specs=P(stage_axis), check_vma=False)
    outs_per_stage = fn(stage_params_stacked, micro_inputs)
    return outs_per_stage[-1]          # the last stage's buffer is the answer


# ---------------------------------------------------------------------------
# LM convenience: two-stage MPAI serve pipeline
# ---------------------------------------------------------------------------
def lm_two_stage_fns(cfg, plan, tp: int = 1):
    """Build (stage0_fn, stage1_fn) for an LM split at plan.segments[0].end.

    Stage 0: embed + backbone segment (int8 policy).
    Stage 1: tail segment + final norm + head (high precision).
    Stage params: {'embed':..., 'layers': <segment slice>, ...}.
    """
    from repro.models import transformer as T
    from repro.models.layers import lm_logits, make_norm

    period = T.pattern_period(cfg)
    plan = plan.align_to_period(period, cfg.num_layers)
    seg0, seg1 = plan.segments[0], plan.segments[-1]

    def stage0(tokens_embed, params):
        # tokens arrive pre-embedded (embedding runs host-side or in-stage;
        # here in-stage via the passed embed table)
        x = tokens_embed
        x, _, _ = T._segment_scan(params["layers"], cfg, x,
                                  _positions(x), seg0.policy, tp)
        return x, jnp.zeros(x.shape[:-1] + (cfg.vocab_size,), jnp.float32)

    def stage1(x, params):
        x, _, _ = T._segment_scan(params["layers"], cfg, x,
                                  _positions(x), seg1.policy, tp)
        _, norm = make_norm("rmsnorm")
        x = norm(params["final_norm"], x, cfg.norm_eps)
        logits = lm_logits(params["lm_head"], x, plan.head_policy)
        return jnp.zeros_like(x), logits.astype(jnp.float32)

    def _positions(x):
        return jnp.broadcast_to(jnp.arange(x.shape[1], dtype=jnp.int32),
                                x.shape[:2])
    return stage0, stage1, (seg0, seg1)


def split_lm_params_for_stages(params, cfg, plan, period: int,
                               combine=None):
    """Split a monolithic LM param tree into per-stage trees with identical
    structure (required for stacking over the stage axis).  Stage trees are
    padded with zero-size-compatible entries where a stage lacks a part.

    ``combine(stage0_leaf, stage1_leaf)`` builds each stacked leaf (default
    ``jnp.stack``); it runs leaf by leaf, so a caller that places each
    stage's half on its own device never holds a whole stage copy at
    once."""
    import jax.numpy as jnp

    if combine is None:
        combine = lambda a, b: jnp.stack([a, b])
    seg0, seg1 = plan.segments[0], plan.segments[-1]
    lo = seg0.end // period
    table = params["embed"] if "lm_head" not in params else params["lm_head"]
    n0, n1 = lo, (seg1.end - seg1.start) // period
    assert n0 == n1, ("two-stage pipeline requires equal segment lengths; "
                      f"got {n0} vs {n1} super-blocks")
    tmap = jax.tree_util.tree_map
    return {"layers": tmap(lambda a: combine(a[:lo], a[lo:lo + n1]),
                           params["layers"]),
            "final_norm": tmap(lambda a: combine(jnp.zeros_like(a), a),
                               params["final_norm"]),
            "lm_head": combine(jnp.zeros_like(table), table)}
