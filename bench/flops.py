"""Operations and bytes the algorithm needs, from shapes alone.

Counted for a dense decoder with grouped-query attention and a SwiGLU
MLP, from a configuration file's sizes (Hugging Face key names).  Only
the work the algorithm needs is counted: a prompt's own tokens (not the
padding a server adds), keys and values of live tokens (not blocks
touched or slots left empty), the logits of the positions that yield a
token.  So the counts are the same whatever implements them.
"""
from __future__ import annotations

from typing import Dict, Iterable, NamedTuple, Tuple


class Dims(NamedTuple):
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int


def dims(cfg: Dict) -> Dims:
    heads = cfg["num_attention_heads"]
    return Dims(cfg["num_hidden_layers"], cfg["hidden_size"], heads,
                cfg["num_key_value_heads"],
                cfg.get("head_dim") or cfg["hidden_size"] // heads,
                cfg["intermediate_size"], cfg["vocab_size"])


def layer_matmul_params(d: Dims) -> int:
    """Weights one token multiplies by in one layer: q, k, v, o and the
    three SwiGLU projections."""
    return (d.d_model * d.heads * d.head_dim
            + 2 * d.d_model * d.kv_heads * d.head_dim
            + d.heads * d.head_dim * d.d_model
            + 3 * d.d_model * d.d_ff)


def head_flops(d: Dims) -> int:
    return 2 * d.d_model * d.vocab


def prefill_flops(d: Dims, n: int) -> int:
    """A prompt of ``n`` tokens, causal, ending in one row of logits."""
    attn = 4 * d.heads * d.head_dim * n * (n + 1) // 2
    return d.layers * (2 * layer_matmul_params(d) * n + attn) + head_flops(d)


def decode_flops(d: Dims, context: int) -> int:
    """One token that attends to ``context`` tokens (itself included)."""
    return (d.layers * (2 * layer_matmul_params(d)
                        + 4 * d.heads * d.head_dim * context)
            + head_flops(d))


def paged_attention_cost(d: Dims, contexts: Iterable[int],
                         kv_bytes: int = 2,
                         act_bytes: int = 2) -> Tuple[int, int]:
    """(FLOPs, bytes) of one decode step of paged attention over every
    layer, for sequences attending to ``contexts`` tokens each: every
    live token's key and value per KV head read once, the query read and
    the output written once."""
    flops = nbytes = 0
    for c in contexts:
        flops += 4 * d.heads * d.head_dim * c
        nbytes += (2 * c * d.kv_heads * d.head_dim * kv_bytes
                   + 2 * d.heads * d.head_dim * act_bytes)
    return d.layers * flops, d.layers * nbytes


def least_time_s(flops: float, nbytes: float, peaks: Dict) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
