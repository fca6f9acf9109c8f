"""Multi-device behaviour via subprocesses (8 faked host devices) — keeps
the main test process at 1 device.  Covers: the MPAI two-stage
co-processing pipeline vs monolithic forward, int8-compressed gradient
collectives vs exact mean, and a sharded train step on a (2,2,2) mesh."""
import os
import subprocess
import sys
import textwrap

import pytest

ENV = {**os.environ,
       "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
       "PYTHONPATH": "src"}


def _run(code: str):
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       env=ENV, capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_two_stage_pipeline_matches_monolithic():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import ModelConfig, MeshConfig
        from repro.core.partition import PartitionPlan, Segment
        from repro.core.precision import PrecisionPolicy
        from repro.core.pipeline import (lm_two_stage_fns, pipeline_apply,
                                         split_lm_params_for_stages)
        from repro.models import transformer as T
        from repro.models.layers import embed

        cfg = ModelConfig(name="t", family="dense", num_layers=4, d_model=32,
                          num_heads=4, num_kv_heads=4, d_ff=64,
                          vocab_size=128, remat=False)
        params = T.model_init(jax.random.PRNGKey(0), cfg)
        plan = PartitionPlan((
            Segment("backbone", 0, 2, PrecisionPolicy.bf16()),
            Segment("head", 2, 4, PrecisionPolicy.bf16())))

        from repro.launch.mesh import make_mesh

        mesh = make_mesh((2, 4), ("stage", "model"))
        s0, s1, _ = lm_two_stage_fns(cfg, plan)
        sp = split_lm_params_for_stages(params, cfg, plan, 1)

        n_micro, b, s = 3, 2, 8
        toks = jax.random.randint(jax.random.PRNGKey(1), (n_micro, b, s),
                                  0, 128)
        embeds = jnp.stack([embed(params["embed"], toks[i])
                            for i in range(n_micro)])
        outs = pipeline_apply(mesh, "stage", [s0, s1], sp, embeds,
                              hidden_shape=(b, s, 32),
                              out_shape=(b, s, 128))
        ref = jnp.stack([T.forward(params, cfg, toks[i]).logits
                         for i in range(n_micro)])
        d = float(jnp.max(jnp.abs(outs - ref.astype(outs.dtype))))
        assert d < 0.05, d
        print("pipeline ok", d)
    """)
    assert "pipeline ok" in out


def test_compressed_grad_mean_close_to_exact():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.optim.compression import compressed_grad_mean, CHUNK

        from repro.launch.mesh import make_mesh

        shard_map = partial(jax.shard_map, check_vma=False)
        mesh = make_mesh((8,), ("pod",))
        grads = {"w": jax.random.normal(jax.random.PRNGKey(0),
                                        (8, CHUNK * 2)),
                 "b": jax.random.normal(jax.random.PRNGKey(1), (8, 4))}

        @partial(shard_map, mesh=mesh, in_specs=P("pod"), out_specs=P())
        def comp(g):
            g = jax.tree.map(lambda a: a[0], g)
            return compressed_grad_mean(g, "pod")

        got = comp(grads)
        want = jax.tree.map(lambda a: jnp.mean(a, 0), grads)
        # int8-compressed large leaf: close; small leaf: exact fp32 pmean
        rel = (jnp.abs(got["w"] - want["w"]).max()
               / jnp.abs(want["w"]).max())
        assert float(rel) < 0.05, rel
        np.testing.assert_allclose(np.asarray(got["b"]),
                                   np.asarray(want["b"]), rtol=1e-5)
        print("compression ok", float(rel))
    """)
    assert "compression ok" in out


def test_sharded_train_step_on_222_mesh():
    out = _run("""
        import jax, numpy as np
        from repro.configs.base import (MeshConfig, ModelConfig, ShapeConfig,
                                        TrainConfig)
        from repro.data.pipeline import lm_batch
        from repro.runtime.train_loop import Trainer

        cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                          num_heads=4, num_kv_heads=2, d_ff=128,
                          vocab_size=256, remat=False, fsdp=True)
        shape = ShapeConfig("t", 32, 8, "train")
        mesh_cfg = MeshConfig((2, 2, 2), ("pod", "data", "model"))
        tr = Trainer(cfg, shape, mesh_cfg, TrainConfig(learning_rate=1e-2))
        state = tr.init_state()
        losses = []
        for s in range(10):
            state, m = tr.step_fn(state, lm_batch(cfg, shape, s))
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], losses
        print("sharded train ok", losses[0], "->", losses[-1])
    """)
    assert "sharded train ok" in out


def test_elastic_restart_across_mesh_shapes():
    """Save on a (2,4) mesh, restore on (1,8) and (4,2) — resharding works
    and parameters are bitwise identical."""
    out = _run("""
        import jax, numpy as np, tempfile
        from repro.checkpoint.manager import CheckpointManager
        from repro.configs.base import (MeshConfig, ModelConfig, ShapeConfig,
                                        TrainConfig)
        from repro.data.pipeline import lm_batch
        from repro.runtime.train_loop import Trainer

        cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                          num_heads=8, num_kv_heads=8, d_ff=128,
                          vocab_size=256, remat=False)
        shape = ShapeConfig("t", 32, 8, "train")
        tc = TrainConfig()
        tmp = tempfile.mkdtemp()
        tr = Trainer(cfg, shape, MeshConfig((2, 4), ("data", "model")), tc)
        state = tr.init_state()
        state, _ = tr.run(state, lambda s: lm_batch(cfg, shape, s), 3)
        mgr = CheckpointManager(tmp)
        mgr.save(3, state, blocking=True)

        for ms in [((1, 8)), ((4, 2))]:
            tr2 = Trainer(cfg, shape, MeshConfig(ms, ("data", "model")), tc)
            like = jax.eval_shape(tr2._init_state, jax.random.PRNGKey(0))
            restored, step = mgr.restore(like,
                                         shardings=tr2.state_shardings)
            assert step == 3
            for a, b in zip(jax.tree_util.tree_leaves(state.params),
                            jax.tree_util.tree_leaves(restored.params)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            restored, _ = tr2.step_fn(restored,
                                      lm_batch(cfg, shape, 3))
        print("elastic ok")
    """)
    assert "elastic ok" in out
