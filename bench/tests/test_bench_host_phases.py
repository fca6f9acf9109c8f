"""The readers of the engine's host phases on synthetic records, and a
traced run of a fixture cell on the CPU that reports every one of them.

On the CPU, XLA runs its ops on host threads and the trace has no
device plane; the traced run counts the ops of those threads as one
device's, so that the device readers have something to read."""
import pytest

from bench import harness, trace
from bench.cells import load_module
from bench.record import Record
from bench.stats import union_length
from bench.tests.helpers import REPO, make_root
from bench.trace import Summary

SEED = 2 ** 31 + 1313
NEW = ("step.host_ms", "step.host_ms.chat", "device.idle_host_share",
       "device.idle_host_share.chat", "router.batch_hold_s")


def reader(name):
    return load_module(REPO / f"bench/metrics/{name}.py", name)


def record(stages, t0=0.0, close=10.0, trace=None):
    return Record(t0, close, {}, 4, {}, {}, stages=stages, trace=trace)


def test_idle_in_a_fetch_waits_and_idle_outside_any_stage_is_host():
    # device busy 1-2, 3-6 and 7-10: idle 0-1, 2-3 and 6-7
    s = Summary(0.0, 10.0, [("a", 1.0, 2.0, 0), ("b", 3.0, 6.0, 0),
                            ("c", 7.0, 10.0, 0)], [])
    s.busy_s, s.devices = 7.0, 1
    stages = [("decode_prepare", 0.0, 1.0, [], {"step": 0}),
              ("decode_sync", 1.0, 2.0, [], {"step": 0}),
              ("decode_fetch", 2.0, 3.0, [], {"step": 0}),
              ("admit", 6.5, 6.75, [1], {})]
    share = reader("device.idle_host_share").read
    # 2-3 in the fetch and 6.5-6.75 in the admission are waits; 0-1 in
    # a host phase, 6-6.5 and 6.75-7 outside every stage are host work
    assert share(record(stages, trace=s)) == pytest.approx(
        100 * 1.75 / 3.0)
    # a program that times no decode_sync has nothing to split by
    assert share(record([st for st in stages if st[0] != "decode_sync"],
                        trace=s)) is None
    assert share(record(stages)) is None


def test_host_ms_skips_step_pairs_with_an_admission_or_profiler_between():
    def step(n, t0, sync):
        return [("decode_step", t0, t0 + sync + 0.001, [1], {"step": n}),
                ("decode_sync", t0 + 0.0005, t0 + 0.0005 + sync, [],
                 {"step": n})]
    stages = (step(0, 1.0, 0.020) + step(1, 1.030, 0.022)
              + [("prefill_chunk", 1.060, 1.080, [2], {"chunk": 0})]
              + step(2, 1.100, 0.024) + step(3, 1.135, 0.020))
    # pairs 0-1 (30 ms less 20) and 2-3 (35 ms less 24); 1-2 holds a chunk
    assert reader("step.host_ms").read(record(stages)) == pytest.approx(
        (10 + 11) / 2)
    # the harness started the profiler inside pair 0-1: its pause is not
    # the program's host time
    s = Summary(1.010, 1.2, [], [])
    assert reader("step.host_ms").read(record(stages, trace=s)) == \
        pytest.approx(11)
    # no decode_sync stage: a program without the phase split
    assert reader("step.host_ms").read(record(
        [st for st in stages if st[0] != "decode_sync"])) is None


def test_batch_hold_ignores_a_batch_cut_by_the_close():
    stages = [("batch", 1.0, 5.0, [1, 2], {}), ("batch", 5.0, 8.0, [3], {}),
              ("batch", 8.0, 12.0, [4], {})]
    hold = reader("router.batch_hold_s").read
    assert hold(record(stages, t0=2.0, close=10.0)) == pytest.approx(3.5)
    assert hold(record(stages[2:], t0=2.0, close=10.0)) is None


@pytest.fixture
def cpu_ops_as_device(monkeypatch):
    reduce = trace.reduce

    def with_cpu_ops(pd, sync_perf, end_perf):
        s = reduce(pd, sync_perf, end_perf)
        if s is None or s.devices:
            return s
        sync_ns = next(ev.start_ns for plane in pd.planes
                       for line in plane.lines for ev in line.events
                       if ev.name == trace.SYNC)
        for plane in pd.planes:
            for line in plane.lines:
                if not line.name.startswith("tf_XLA"):
                    continue
                for ev in line.events:
                    a = sync_perf + (ev.start_ns - sync_ns) * 1e-9
                    b = a + ev.duration_ns * 1e-9
                    if "::" not in ev.name and b > sync_perf \
                            and a < end_perf:
                        s.ops.append((trace.op_name(ev.name), a, b, 0))
        s.devices = 1 if s.ops else 0
        s.busy_s = union_length((max(a, sync_perf), min(b, end_perf))
                                for _, a, b, _ in s.ops)
        return s
    monkeypatch.setattr(trace, "reduce", with_cpu_ops)


def test_traced_fixture_cell_reports_every_host_phase_metric(
        tmp_path, cpu_ops_as_device):
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        out = harness.run(make_root(tmp_path), "tiny.tiny-chat", SEED, 3.0,
                          True, require_chip=False)
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert out["correct"] is True, out["checks"]
    for name in NEW:
        assert out["metrics"][name]["value"] is not None, name
    assert out["metrics"]["step.host_ms"]["value"] > 0
    assert 0 <= out["metrics"]["device.idle_host_share"]["value"] <= 100
    assert out["metrics"]["router.batch_hold_s"]["value"] > 0
