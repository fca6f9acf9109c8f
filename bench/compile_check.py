#!/usr/bin/env python3
"""Compile each cell's serving programs for a described TPU v5e, with
no chip attached, and print what they hold in device memory.

    JAX_PLATFORMS=cpu python3 bench/compile_check.py [workload ...]

For each cell: the greedy decode step, bucket admission and (where the
mix has prompts over the bucket) a prefill chunk, at the cell's pool
shapes and the configuration's widths, with the Pallas paged kernel.
The pool is an argument and an output of every program (it is not
donated), so it is held twice while one runs.  The estimate printed is
weights + 2 x pool + the admission scratch cache + the largest
temporaries; the chip's 16 GB less what the runtime keeps must hold it.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def check(workload: str, one_chip) -> dict:
    import jax
    import jax.numpy as jnp

    from bench.cells import load_cell
    from repro.configs.base import ModelConfig
    from repro.kernels import ops
    from repro.models import transformer as T
    from repro.runtime.serve import ContinuousBatchingEngine as E
    from repro.serving import PoolSpec

    ops._interpret = lambda: False        # compile the Pallas kernel
    cell = load_cell(ROOT, workload)
    mcfg = ModelConfig(**cell.reference.program_kwargs(cell.config))
    ps = PoolSpec("lm", ("tpu_v5e_bf16",), backend="engine",
                  **cell.traffic["pool"])
    width = -(-ps.max_len // ps.block_size)
    blocks = ps.num_blocks or ps.max_slots * width
    dt = jnp.bfloat16 if cell.config["dtype"] == "bfloat16" else jnp.float32

    def place(tree, dtype=None):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, dtype if dtype and x.dtype == jnp.float32
                else x.dtype, sharding=one_chip), tree)
    params = place(jax.eval_shape(lambda: T.model_init(
        jax.random.PRNGKey(0), mcfg)), dt)
    caches = place(jax.eval_shape(lambda: T.init_paged_decode_cache(
        mcfg, ps.max_slots, blocks, ps.block_size, 1, max_blocks=width)))
    scratch = place(jax.eval_shape(lambda: T.init_cache(
        mcfg, ps.max_slots, ps.prompt_len, 1)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32,
                                          sharding=one_chip)
    b = ps.max_slots
    me = SimpleNamespace(cfg=mcfg, plan=None, tp=1)

    def decode(p, toks, c):
        out = T.decode_step(p, mcfg, toks, c, None, 1)
        return jnp.argmax(out.logits[:, -1], axis=-1), out.cache

    progs = {"decode": jax.jit(decode).lower(params, i32(b, 1), caches),
             "admit": jax.jit(lambda *a: E._admit_impl(me, *a, False)).lower(
                 params, i32(b, ps.prompt_len), scratch, caches,
                 jax.ShapeDtypeStruct((b,), jnp.bool_, sharding=one_chip),
                 f32(b), i32(b), i32(b))}
    if cell.traffic["prompt"]["max"] > ps.prompt_len:
        progs["chunk"] = jax.jit(
            lambda p, t, c, s, st, a, k, d: E._chunk_impl(
                me, p, t, c, s, st, a, k, d, False)).lower(
            params, i32(1, ps.chunk), caches, i32(), i32(), f32(1), i32(1),
            i32(1))
    size = lambda t: sum(x.size * x.dtype.itemsize
                         for x in jax.tree_util.tree_leaves(t))
    out = {"weights": size(params), "pool": size(caches),
           "scratch": size(scratch), "blocks": blocks}
    for name, lowered in progs.items():
        m = lowered.compile().memory_analysis()
        out[name] = {"args": m.argument_size_in_bytes,
                     "out": m.output_size_in_bytes,
                     "temp": m.temp_size_in_bytes}
    out["estimate"] = (out["weights"] + 2 * out["pool"] + out["scratch"]
                       + max(v["temp"] for k, v in out.items()
                             if isinstance(v, dict)))
    return out


def main(argv=None) -> int:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    names = argv or [w["name"] for w in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["workloads"]]
    for w in names:
        print(json.dumps({"workload": w, **check(w, one_chip)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
