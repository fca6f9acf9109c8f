"""Declarative contracts for every Pallas kernel in ``src/repro/kernels``.

One entry per ``pl.pallas_call`` wrapper.  The kernel pass
(``passes/kernels.py``) checks each call site against its contract
*without compiling anything* — grid rank, scalar-prefetch count,
index-map arity and return rank, in-bounds discipline on table lookups,
``pl.when`` tail guards, dimension semantics, divisibility asserts, and
output dtype provenance.  Adding a kernel without a contract (or
leaving a stale contract behind) is itself a finding, so this table
stays the single authoritative inventory of device code.

``tail_guard`` is True when the kernel body must predicate work with
``pl.when`` (online-softmax init/finalize, accumulator init, length
masking).  ``rowwise_quant_pallas`` is the one full-tile kernel — every
grid step owns a complete row block (divisibility asserted in the
wrapper), so it has no tail to guard.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Tuple


@dataclass(frozen=True)
class KernelContract:
    module: str                    # repo-relative wrapper module
    kernel_fn: str                 # kernel body def the wrapper invokes
    grid_rank: int
    num_scalar_prefetch: int = 0
    tail_guard: bool = True        # body must use pl.when
    dimension_semantics: Tuple[str, ...] = ()
    divisibility_assert: bool = True   # wrapper asserts % block == 0
    out_dtypes: Tuple[str, ...] = ()   # source text of out_shape dtype(s)


KERNEL_CONTRACTS: Dict[str, KernelContract] = {
    "paged_attention_pallas": KernelContract(
        module="src/repro/kernels/paged_attention.py",
        kernel_fn="_paged_kernel",
        grid_rank=2,               # (sequence, table block); heads in-body
        num_scalar_prefetch=2,     # block_table + lengths ride ahead
        tail_guard=True,           # dead-block predication + init/finalize
        dimension_semantics=("parallel", "arbitrary"),
        divisibility_assert=False,  # pool rows are whole pages by layout
        out_dtypes=("q.dtype",),
    ),
    "flash_attention_pallas": KernelContract(
        module="src/repro/kernels/flash_attention.py",
        kernel_fn="_flash_kernel",
        grid_rank=3,
        tail_guard=True,           # causal skip + online-softmax guards
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        divisibility_assert=True,
        out_dtypes=("q.dtype",),
    ),
    "int8_matmul_pallas": KernelContract(
        module="src/repro/kernels/int8_matmul.py",
        kernel_fn="_int8_matmul_kernel",
        grid_rank=3,
        tail_guard=True,           # k==0 accumulator init
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        divisibility_assert=True,
        out_dtypes=("jnp.int32",),
    ),
    "rowwise_quant_pallas": KernelContract(
        module="src/repro/kernels/quant.py",
        kernel_fn="_quant_kernel",
        grid_rank=1,
        tail_guard=False,          # full row blocks — no tail exists
        dimension_semantics=("parallel",),
        divisibility_assert=True,
        out_dtypes=("jnp.int8", "jnp.float32"),
    ),
    "selective_scan_pallas": KernelContract(
        module="src/repro/kernels/selective_scan.py",
        kernel_fn="_selective_scan_kernel",
        grid_rank=3,
        tail_guard=True,           # chunk-0 state init
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        divisibility_assert=True,
        out_dtypes=("x.dtype",),
    ),
    "wkv_pallas": KernelContract(
        module="src/repro/kernels/wkv.py",
        kernel_fn="_wkv_kernel",
        grid_rank=3,
        tail_guard=True,           # chunk-0 state init
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        divisibility_assert=True,
        out_dtypes=("r.dtype",),
    ),
}
