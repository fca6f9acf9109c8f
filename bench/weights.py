"""Random weights from the seed, made on the device in one jitted call,
in the dtype the configuration file states.

The tree follows the program's parameter layout, read with
``jax.eval_shape`` from its own initialiser (shapes only; nothing the
program computes is kept).  Each leaf is drawn from its own fold of the
seed's key: norm scales are ones, embedding and head rows N(0, 0.02),
every other matrix N(0, 1 / fan_in), fan_in being its second-to-last
axis.  The key is an argument of the compiled program, so every seed
reuses one compilation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def key_data(seed: int) -> np.ndarray:
    """Two 32-bit words of key from any whole-number seed."""
    return np.random.SeedSequence(seed).generate_state(2).astype(np.uint32)


def _leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def make(layout, dtype_name: str, seed: int):
    """``layout``: a tree of ``jax.ShapeDtypeStruct`` (the program's
    parameter shapes).  Returns the weights on the default device."""
    dtype = DTYPES[dtype_name]
    paths, treedef = jax.tree_util.tree_flatten_with_path(layout)

    @jax.jit
    def init(kd):
        key = jax.random.wrap_key_data(kd)
        out = []
        for i, (path, leaf) in enumerate(paths):
            name = _leaf_name(path)
            if name.endswith("scale"):
                out.append(jnp.ones(leaf.shape, dtype))
                continue
            std = (0.02 if name in ("embed", "lm_head")
                   else 1.0 / np.sqrt(leaf.shape[-2]))
            z = jax.random.normal(jax.random.fold_in(key, i), leaf.shape,
                                  jnp.float32)
            out.append((z * std).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return init(jnp.asarray(key_data(seed)))
