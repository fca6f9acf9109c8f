"""FLOP and byte counts from shapes, for both kernel layouts of the
benchmark's configurations."""
import json

import pytest

from bench import flops
from bench.tests.helpers import REPO


def cfg(name):
    return json.loads((REPO / f"bench/configs/{name}.json").read_text())


def test_dims_of_both_layouts():
    s = flops.dims(cfg("stablelm-1.6b"))
    m = flops.dims(cfg("mistral-7b"))
    assert (s.heads, s.kv_heads, s.head_dim) == (32, 32, 64)   # group 1
    assert (m.heads, m.kv_heads, m.head_dim) == (32, 8, 128)   # group 4
    assert flops.layer_matmul_params(m) == (4096 * 4096 * 2
                                            + 2 * 4096 * 1024
                                            + 3 * 4096 * 14336)


@pytest.mark.parametrize("name", ["stablelm-1.6b", "mistral-7b"])
def test_paged_attention_counts_live_tokens_only(name):
    d = flops.dims(cfg(name))
    f, b = flops.paged_attention_cost(d, [100, 7])
    per_tok_kv = 2 * d.kv_heads * d.head_dim * 2          # K and V, bf16
    q_out = 2 * d.heads * d.head_dim * 2
    assert b == d.layers * (107 * per_tok_kv + 2 * q_out)
    assert f == d.layers * 4 * d.heads * d.head_dim * 107
    # an empty slot costs nothing, whatever the implementation walks
    assert flops.paged_attention_cost(d, []) == (0, 0)


def test_grouped_query_reads_a_quarter_of_the_kv():
    s = flops.dims(cfg("stablelm-1.6b"))
    m = flops.dims(cfg("mistral-7b"))
    kv = lambda d: flops.paged_attention_cost(d, [1000])[1] / d.layers
    # stablelm: 32 KV heads x 64; mistral: 8 x 128 -> half the bytes
    assert kv(m) == pytest.approx(kv(s) / 2, rel=0.01)


def test_prefill_is_causal_and_decode_grows_with_context():
    d = flops.dims(cfg("mistral-7b"))
    mm = 2 * flops.layer_matmul_params(d)
    attn = 4 * d.heads * d.head_dim
    assert flops.prefill_flops(d, 3) == (d.layers * (3 * mm + attn * 6)
                                         + flops.head_flops(d))
    assert (flops.decode_flops(d, 11) - flops.decode_flops(d, 10)
            == d.layers * attn)


def test_roofline_takes_the_binding_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_time_s(1000, 50, peaks) == 10.0     # compute
    assert flops.least_time_s(10, 500, peaks) == 50.0      # memory
