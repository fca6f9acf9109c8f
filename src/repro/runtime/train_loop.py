"""Distributed training loop: jit'd step with explicit shardings,
microbatch gradient accumulation, checkpointing, and fault-tolerant
restart hooks.

The step function is pure pjit: DP gradients reduce over (pod, data),
TP/EP collectives over model, FSDP weight gathers overlap with the layer
scan (XLA schedules the next layer's all-gather against the current
layer's compute).  Partition-aware QAT is just a plan argument — the
same loop trains baseline and MPAI variants.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import sharding as shard
from repro.configs.base import MeshConfig, ModelConfig, ShapeConfig, TrainConfig
from repro.core.partition import PartitionPlan
from repro.launch.mesh import make_mesh_from_config
from repro.models import transformer as T
from repro.optim import adamw


class TrainState(NamedTuple):
    params: Any
    opt: adamw.AdamWState
    step: jnp.ndarray


def make_step_fn(cfg: ModelConfig, tc: TrainConfig,
                 plan: Optional[PartitionPlan], tp: int):
    """(state, batch) -> (state, metrics); grad-accum aware."""

    def loss(params, tokens, labels, fe):
        return T.loss_fn(params, cfg, tokens, labels, plan, tp,
                         frontend_embeds=fe)

    def step(state: TrainState, batch: Dict[str, jnp.ndarray]):
        fe = batch.get("frontend_embeds")
        if cfg.grad_accum > 1:
            b = batch["tokens"].shape[0]
            mb = b // cfg.grad_accum
            split = lambda a: a.reshape(cfg.grad_accum, mb, *a.shape[1:])
            toks = split(batch["tokens"])
            labs = split(batch["labels"])
            fes = split(fe) if fe is not None else None

            def micro(carry, inp):
                gsum, lsum = carry
                tk, lb, f = inp
                l, g = jax.value_and_grad(loss)(state.params, tk, lb, f)
                gsum = jax.tree_util.tree_map(
                    lambda a, b_: a + b_.astype(a.dtype), gsum, g)
                return (gsum, lsum + l), None
            acc_dt = jnp.dtype(tc.accum_dtype)
            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, acc_dt), state.params)
            (gsum, lsum), _ = jax.lax.scan(micro, (g0, 0.0),
                                           (toks, labs, fes))
            grads = jax.tree_util.tree_map(lambda g: g / cfg.grad_accum, gsum)
            l = lsum / cfg.grad_accum
        else:
            l, grads = jax.value_and_grad(loss)(state.params,
                                                batch["tokens"],
                                                batch["labels"], fe)
        params, opt, gnorm = adamw.apply_updates(state.params, grads,
                                                 state.opt, tc)
        return (TrainState(params, opt, state.step + 1),
                {"loss": l, "grad_norm": gnorm})
    return step


class Trainer:
    def __init__(self, cfg: ModelConfig, shape: ShapeConfig,
                 mesh_cfg: MeshConfig, tc: TrainConfig,
                 plan: Optional[PartitionPlan] = None,
                 mesh: Optional[Mesh] = None):
        self.cfg, self.shape, self.tc, self.plan = cfg, shape, tc, plan
        self.mesh_cfg = mesh_cfg
        self.mesh = (mesh if mesh is not None
                     else make_mesh_from_config(mesh_cfg))
        self.tp = mesh_cfg.tp

        pshape = jax.eval_shape(partial(T.model_init, cfg=cfg, tp=self.tp),
                                jax.random.PRNGKey(tc.seed))
        self.param_specs = shard.param_specs(cfg, pshape, mesh_cfg)
        opt_specs = adamw.AdamWState(self.param_specs, self.param_specs, P())
        self.state_specs = TrainState(self.param_specs, opt_specs, P())
        self.data_specs = shard.data_specs(cfg, shape, mesh_cfg)

        self.state_shardings = shard.make_shardings(self.mesh,
                                                    self.state_specs)
        data_shardings = shard.make_shardings(self.mesh, self.data_specs)

        step = make_step_fn(cfg, tc, plan, self.tp)
        self.step_fn = jax.jit(
            step,
            in_shardings=(self.state_shardings, data_shardings),
            out_shardings=(self.state_shardings, None),
            donate_argnums=(0,))
        self._init_fn = jax.jit(
            lambda key: self._init_state(key),
            out_shardings=self.state_shardings)

    def _init_state(self, key):
        import jax.numpy as _jnp
        params = T.model_init(key, self.cfg, self.tp)
        return TrainState(params,
                          adamw.init(params, _jnp.dtype(self.tc.opt_dtype)),
                          jnp.zeros((), jnp.int32))

    def init_state(self) -> TrainState:
        with self.mesh:
            return self._init_fn(jax.random.PRNGKey(self.tc.seed))

    def run(self, state: TrainState, data_fn, num_steps: int,
            ckpt=None, log_every: int = 10, on_step=None):
        """data_fn(step) -> batch dict.  Returns (state, history)."""
        history = []
        start = int(state.step)
        for s in range(start, start + num_steps):
            batch = data_fn(s)
            with self.mesh:
                state, metrics = self.step_fn(state, batch)
            if on_step is not None:
                on_step(s, state, metrics)
            if (s + 1) % log_every == 0 or s == start:
                history.append({"step": s + 1,
                                "loss": float(metrics["loss"]),
                                "grad_norm": float(metrics["grad_norm"])})
            if ckpt is not None and (s + 1) % self.tc.checkpoint_every == 0:
                ckpt.save(s + 1, state)
        return state, history
