"""The harness on the CPU at a small size, with its look for a chip
skipped: cells, mixes and metrics found by name from files alone, the
refusals, and the comparison that decides ``correct`` coming out false
for the program's own int8 path and for a broken served path."""
import json
import os
import subprocess
import sys

import jax
import pytest

from bench import harness
from bench.cells import CellError, load_cell
from bench.tests.helpers import REPO, make_root

SEED = 2 ** 31 + 4242


@pytest.fixture
def root(tmp_path):
    """A benchmark root with the fixture cells and one extra metric, all
    added as files and entries; JAX's cache settings restored after."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield make_root(tmp_path, extra_metric="engine.decode_steps")
    for k, v in saved.items():
        jax.config.update(k, v)
    cc.reset_cache()


def run(root, workload="tiny.tiny-closed", trace=False, **kw):
    # long enough that requests finish under a loaded test run: the
    # checks compare finished requests only
    return harness.run(root, workload, SEED, 3.0, trace,
                       require_chip=False, **kw)


def test_fixture_cell_and_metric_found_by_name(root):
    traced = run(root, trace=True)
    assert traced["correct"] is True, traced["checks"]
    assert traced["metrics"]["engine.decode_steps"]["value"] > 0
    for name in ("step.decode_ms", "engine.slot_occupancy", "step.mfu",
                 "router.queue_wait_p50_s", "engine.prefill_ms_per_ktok"):
        assert traced["metrics"][name]["value"] > 0
    # a metric split by cells is read by the reader it was split from
    assert (traced["metrics"]["step.decode_ms.chat"]["value"]
            == traced["metrics"]["step.decode_ms"]["value"])
    plain = run(root, "tiny.tiny-chat")
    assert plain["correct"] is True, plain["checks"]
    assert plain["attempted"] > 0 and plain["failed"] == 0
    assert set(plain["metrics"]) == {"itl_p99_ms", "itl_p95_ms",
                                     "ttft_p50_s", "output_tokens_per_s",
                                     "setup_s"}
    assert plain["metrics"]["ttft_p50_s"]["value"] > 0
    assert list(plain)[-1] == "checks"
    assert plain["device"]["kind"] == "cpu"


def test_config_without_its_source_is_refused(root):
    path = root / "bench/configs/tiny.json"
    for key in ("source", "reduced", "deployment"):
        cfg = json.loads((REPO / "bench/tests/fixtures/tiny.json")
                         .read_text())
        cfg.pop(key, None)
        path.write_text(json.dumps(cfg))
        with pytest.raises(CellError, match=key):
            load_cell(root, "tiny.tiny-closed")


@pytest.fixture
def altered_tokens(monkeypatch):
    """Every decoded token altered where the engine produces it."""
    from repro.runtime import serve
    init = serve.ContinuousBatchingEngine.__init__

    def broken_init(self, *a, **k):
        init(self, *a, **k)
        decode = self._decode

        def off_by_one(p, toks, caches):
            nxt, caches = decode(p, toks, caches)
            return (nxt + 1) % self.cfg.vocab_size, caches
        self._decode = off_by_one
    monkeypatch.setattr(serve.ContinuousBatchingEngine, "__init__",
                        broken_init)


@pytest.fixture
def lost_token(monkeypatch):
    """The third token of every request never reaches its stream."""
    from repro.runtime import serve
    emit = serve.ContinuousBatchingEngine._emit
    seen = {}

    def lossy(self, rid, tok):
        seen[rid] = seen.get(rid, 0) + 1
        if seen[rid] != 3:
            emit(self, rid, tok)
    monkeypatch.setattr(serve.ContinuousBatchingEngine, "_emit", lossy)


def test_altered_tokens_are_not_correct(root, altered_tokens):
    out = run(root)
    assert out["correct"] is False
    assert out["checks"]["served_gap_std"]["value"] > 1.0


def test_lost_token_is_not_correct(root, lost_token):
    out = run(root)
    assert out["correct"] is False
    assert out["checks"]["bad_streams"]["value"] > 0


def test_int8_control_is_not_correct(root):
    """The control: the program with its own int8 serving path switched
    on (``plan="mpai"``: int8 backbone, bf16 last layer)."""
    out = run(root, pool_overrides={"plan": "mpai"})
    assert out["correct"] is False, out["checks"]


def test_int8_reference_control_is_not_correct(root):
    """The other control: the reference computed through int8 names the
    token scored at each served position."""
    out = run(root, int8_reference=True)
    assert out["correct"] is False, out["checks"]


def _env():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_no_tpu_exits_nonzero_without_a_result():
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "stablelm-1.6b.chat", "--seed", str(SEED),
                        "--seconds", "1", "--trace", "0"], cwd=REPO,
                       env=_env(), capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no CPU fallback" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    root = make_root(tmp_path)           # bench/ and BENCHMARK.json only
    code = ("import sys; sys.path.insert(0, '.'); from bench import "
            "harness; print(harness.run('.', 'tiny.tiny-chat', 1, 1.0, "
            "False, require_chip=False))")
    p = subprocess.run([sys.executable, "-c", code], cwd=root, env=_env(),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
