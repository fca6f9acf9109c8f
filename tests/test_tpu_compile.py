"""The paged-decode kernel of the serving path, and the engine's
programs around it, compiled by the TPU compiler for a described (not
attached) v5e chip.

Interpret mode accepts block shapes the chip's compiler refuses (the
(8, 128) tiling rule), so these compiles are what guard the kernel's
BlockSpecs.  The engine's compiles guard how the paged KV pool moves
through a step: written in place and never copied.  The topology is
described inside a fixture, never at import time: only one process at a
time may load the TPU library.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.partition import PartitionPlan, Segment
from repro.core.precision import PrecisionPolicy
from repro.kernels import ops
from repro.kernels.paged_attention import paged_attention_pallas
from repro.models import transformer as T
from repro.runtime.serve import ContinuousBatchingEngine

from conftest import tiny_dense


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
# the kernel also at mistral-7b's served layout (8 kv heads x 4 of 128)
KERNEL_LAYOUTS = {**LAYOUTS, "mistral-7b": (8, 4, 128)}
# id -> (layout, P, slots, table entries, layers): every layout at 16-
# and 8-token pages (8: the PoolSpec default) over 1024-token tables, and
# the benchmark cells' served pools, whose table widths set how many
# pages a compute block takes and so what the kernel holds in VMEM
KERNEL_CASES = {
    **{f"{layout}-{page}": (layout, page, 8, 1024 // page, 3)
       for layout in sorted(KERNEL_LAYOUTS) for page in (16, 8)},
    "stablelm-1.6b-chat-16x80": ("stablelm-1.6b", 16, 16, 80, 24),
    "mistral-7b-decode-long-32x72": ("mistral-7b", 16, 32, 72, 16),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_paged_attention_compiles_for_v5e(one_chip, no_persistent_cache,
                                          case):
    layout, page, batch, mb, layers = KERNEL_CASES[case]
    kvp, gp, hd = KERNEL_LAYOUTS[layout]
    rows = batch * mb + 1                     # + the trash row

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = paged_attention_pallas.lower(
        arg((batch, kvp, gp, hd), jnp.bfloat16),
        arg((layers, rows, page, kvp * hd), jnp.bfloat16),
        arg((layers, rows, page, kvp * hd), jnp.bfloat16),
        arg((batch, mb), jnp.int32),
        arg((batch,), jnp.int32),
        arg((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


# The engine's programs at a small model: 4 layers, 4 slots, a 16-token
# bucket and 16-token pages.  The pool's 2048 blocks (2049 rows with the
# trash row: a length no other array of these programs has) make it
# larger than the chip's on-core memory, as a served pool is, so that
# the compiler stages no part of it there; compiling allocates nothing
SLOTS, BUCKET, MAX_LEN, PAGE, BLOCKS, LAYERS = 4, 16, 128, 16, 2048, 4
ROWS = BLOCKS + 1

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?(\S+)\s*=\s*\w+\[([\d,]*)\]\S*"
                    r"\s+([\w-]+)\((.*)$")
_COMP = re.compile(r"^(?:ENTRY\s+)?%?(\S+)\s.*\{\s*$")   # unindented
# what may produce a pool-sized array besides the scatter that writes a
# step's new rows in place: the plumbing that moves no bytes
_PLUMBING = {"parameter", "get-tuple-element", "bitcast"}


def _pool_sized_ops(hlo: str, pool_shape):
    """(name, opcode, dims) of every materialized instruction whose
    result has a ``ROWS``-long axis and at least one layer's elements (a
    pool, or one layer of it) and is neither plumbing nor a scatter into
    the whole pool.  Instructions inside a fusion are not materialized;
    the fusion's own result is.  A layer-sized result is a copy whatever
    makes it: the program is handed whole pools only."""
    min_elems = math.prod(pool_shape[1:])
    ops_in, fused, found = {}, set(), []
    comp = None
    for line in hlo.splitlines():
        head = _COMP.match(line)
        if head:
            comp = head.group(1)
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        name, dims, op, rest = m.groups()
        ops_in.setdefault(comp, set()).add(op)
        callee = re.search(r"calls=%?([\w.\-]+)", rest)
        callee = callee and callee.group(1)
        if op == "fusion":
            fused.add(callee)
        dims = [int(d) for d in dims.split(",") if d]
        if ROWS in dims and math.prod(dims) >= min_elems:
            found.append((comp, name, op, dims, callee))
    in_place = lambda op, dims, callee: dims == list(pool_shape) and (
        op == "scatter" or op == "fusion" and "scatter" in ops_in[callee])
    return [(name, op, dims) for comp, name, op, dims, callee in found
            if comp not in fused and op not in _PLUMBING
            and not in_place(op, dims, callee)]


def _engine_programs(layout, segments, one_chip):
    """The engine's decode (greedy and hardened), bucket admission and
    prefill chunk, lowered for the described chip at a small model."""
    kvp, gp, hd = LAYOUTS[layout]
    cfg = tiny_dense(num_layers=LAYERS, d_model=256, num_heads=kvp * gp,
                     num_kv_heads=kvp, head_dim=hd, d_ff=512,
                     vocab_size=512)
    bf16 = PrecisionPolicy.bf16()
    cut = LAYERS // segments
    plan = PartitionPlan(tuple(
        Segment(f"s{i}", i * cut, (i + 1) * cut, bf16)
        for i in range(segments)))
    eng = ContinuousBatchingEngine(None, cfg, plan, max_slots=SLOTS,
                                   prompt_len=BUCKET, max_len=MAX_LEN,
                                   block_size=PAGE, prefill_chunk=2 * PAGE)

    def place(tree, dtype=None):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, dtype if dtype and x.dtype == jnp.float32
                else x.dtype, sharding=one_chip), tree)
    params = place(jax.eval_shape(
        lambda: T.model_init(jax.random.PRNGKey(0), cfg)), jnp.bfloat16)
    caches = place(jax.eval_shape(lambda: T.init_paged_decode_cache(
        cfg, SLOTS, BLOCKS, PAGE, max_blocks=eng.table_width)))
    scratch = place(jax.eval_shape(lambda: eng._prefill_cache))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    admit = jax.ShapeDtypeStruct((SLOTS,), jnp.bool_, sharding=one_chip)
    return {
        "decode": lambda: eng._decode.lower(params, i32(SLOTS, 1), caches),
        "decode_hardened": lambda: eng._decode_h.lower(
            params, i32(SLOTS, 1), caches),
        "admit": lambda: eng._admit_step.lower(
            params, i32(SLOTS, BUCKET), scratch, caches, admit,
            f32(SLOTS), i32(SLOTS), i32(SLOTS), False),
        "chunk": lambda: eng._chunk_step.lower(
            params, i32(1, 2 * PAGE), caches, i32(), i32(), f32(1), i32(1),
            i32(1), False),
    }, caches


@pytest.mark.parametrize("segments", [1, 2])
@pytest.mark.parametrize("program", ["decode", "decode_hardened", "admit",
                                     "chunk"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_engine_step_keeps_the_pool_in_place(one_chip, no_persistent_cache,
                                             monkeypatch, layout, program,
                                             segments):
    """The pool is donated and written in place: no copy, slice or
    update of the pool or of one layer of it, and less scratch memory
    than one layer's pool, in every program that takes it."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)  # the chip's path
    progs, caches = _engine_programs(layout, segments, one_chip)
    compiled = progs[program]().compile()
    pool = caches["sub_0"].k_pool
    assert pool.shape[:3] == (LAYERS, ROWS, PAGE)
    hlo = compiled.as_text()
    if program.startswith("decode"):
        assert "tpu_custom_call" in hlo          # the Pallas kernel is in
    assert _pool_sized_ops(hlo, pool.shape) == []
    mem = compiled.memory_analysis()
    layer_bytes = math.prod(pool.shape[1:]) * pool.dtype.itemsize
    assert mem.temp_size_in_bytes < layer_bytes
    # both pools of every sublayer are aliased to the program's output
    assert (mem.alias_size_in_bytes
            >= 2 * len(caches) * pool.size * pool.dtype.itemsize)
