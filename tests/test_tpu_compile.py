"""The paged-decode kernel of the serving path, compiled by the TPU
compiler for a described (not attached) v5e chip at real widths.

Interpret mode accepts block shapes the chip's compiler refuses (the
(8, 128) tiling rule), so these compiles are what guard the kernel's
BlockSpecs.  The topology is described inside a fixture, never at
import time: only one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention import paged_attention_pallas


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache without that chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


# (KVp, gp, hd): stablelm-1.6b as served (32 kv heads of 64, no
# grouping) and a grouped-query layout (8 kv heads x 5 of 128)
LAYOUTS = {"stablelm-1.6b": (32, 1, 64), "gqa-8x5": (8, 5, 128)}


@pytest.mark.parametrize("page", [16, 8])     # 8: the PoolSpec default
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_paged_attention_compiles_for_v5e(one_chip, no_persistent_cache,
                                          layout, page):
    kvp, gp, hd = LAYOUTS[layout]
    batch, max_len = 8, 1024
    mb = max_len // page
    rows = batch * mb + 1                     # + the trash row

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = paged_attention_pallas.lower(
        arg((batch, kvp, gp, hd), jnp.bfloat16),
        arg((rows, page, kvp, hd), jnp.bfloat16),
        arg((rows, page, kvp, hd), jnp.bfloat16),
        arg((batch, mb), jnp.int32),
        arg((batch,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
