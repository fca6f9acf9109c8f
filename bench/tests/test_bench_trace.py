"""The trace reducer on a small trace recorded on a TPU v5e
(``record_trace.py``), and the readers that use a reduced trace."""
import gzip
import json

import pytest

from bench.cells import load_module
from bench.record import Record, Req
from bench.tests.helpers import FIXTURES, REPO
from bench.trace import Summary, breakdown, idle_gaps, op_name, reduce


def reader(name):
    return load_module(REPO / f"bench/metrics/{name}.py", name)


@pytest.fixture(scope="module")
def chip_trace():
    from jax.profiler import ProfileData
    raw = gzip.decompress(
        (FIXTURES / "chip_trace.xplane.pb.gz").read_bytes())
    meta = json.loads((FIXTURES / "chip_trace.json").read_text())
    return reduce(ProfileData.from_serialized_xspace(raw), meta["sync"],
                  meta["end"]), meta


def test_chip_trace_reduces_to_one_busy_device(chip_trace):
    s, meta = chip_trace
    assert meta["device_kind"] == "TPU v5 lite"
    assert s.devices == 1
    assert 0 < s.busy_s < s.window_s
    # device ops were moved onto the host clock of the sync mark
    assert all(b > s.start and a < s.end for _, a, b, _ in s.ops)
    names = {n for n, *_ in s.ops}
    assert reader("kernel.paged_attention_roofline").KERNEL in names
    assert "while" in names and not any(" " in n for n in names)


def test_op_names_are_base_names():
    assert op_name("%paged_attention_pallas.6 = bf16[4,2] custom-call(%a.1)"
                   ) == "paged_attention_pallas"
    assert op_name("%constant_dynamic-slice_fusion.17 = bf16[1] fusion()"
                   ) == "constant_dynamic-slice_fusion"
    assert op_name("copy") == "copy"


def test_chip_trace_breakdown_names_ops_and_gaps(chip_trace):
    s, _ = chip_trace
    b = breakdown(s)
    assert "while" not in [n for n, _ in b["device_ops"]]
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert b["device_ops"] == sorted(b["device_ops"], key=lambda x: -x[1])
    gaps = sum(t for _, t in b["idle_gaps"])
    assert gaps <= s.window_s - s.busy_s + 1e-9
    assert all(isinstance(n, str) and n for n, _ in b["idle_gaps"])


def test_idle_gaps_are_attributed_to_the_host_span_around_them():
    s = Summary(0.0, 10.0, [("a", 1.0, 2.0, 0), ("b", 1.5, 3.0, 0),
                            ("k_paged", 6.0, 7.0, 0)],
                [("bench.pump", 0.5, 9.0), ("bench.submit", 3.5, 5.5)])
    s.busy_s, s.devices = 3.0, 1
    assert idle_gaps(s) == [(0.0, 1.0), (3.0, 6.0), (7.0, 10.0)]
    b = breakdown(s)
    assert b["idle_gaps"][0] == ["bench.submit", 3.0]
    assert ["untraced host", 1.0] not in b["idle_gaps"]
    assert [n for n, _ in b["device_ops"]] == ["b", "a", "k_paged"]
    assert reader("device.idle_share").read(
        Record(0, 10, {}, 4, {}, {}, trace=s)) == pytest.approx(70.0)


def test_roofline_share_of_a_synthetic_step():
    cfg = json.loads((REPO / "bench/configs/mistral-7b.json").read_text())
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # one decode step from 1.0 to 1.1 s for two requests of 100 tokens
    # with 4 tokens out before it; the kernel ran 2 ms inside the step
    reqs = {i: Req(i, 100, 128, 50, 0.0, stamps=[0.5, 0.6, 0.7, 0.8])
            for i in (1, 2)}
    s = Summary(0.9, 1.2, [("paged_attention_pallas", 1.02, 1.022, 0),
                           ("fusion", 1.03, 1.05, 0)], [])
    rec = Record(0.0, 2.0, reqs, 4, cfg, peaks,
                 stages=[("decode_step", 1.0, 1.1, [1, 2], {})], trace=s)
    from bench import flops
    f, b = flops.paged_attention_cost(flops.dims(cfg), [104, 104])
    want = 100 * flops.least_time_s(f, b, peaks) / 0.002
    got = reader("kernel.paged_attention_roofline").read(rec)
    assert got == pytest.approx(want)
    rec.trace = None
    assert reader("kernel.paged_attention_roofline").read(rec) is None
