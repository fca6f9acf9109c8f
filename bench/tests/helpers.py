"""A throwaway benchmark root for CPU tests: a copy of ``bench/`` and
``BENCHMARK.json`` with the fixture configuration, traffic mixes and
limits added as files and entries, and a peaks row for the CPU so that
the harness runs there once its look for a chip is skipped."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
FIXTURES = Path(__file__).resolve().parent / "fixtures"
if str(REPO / "src") not in sys.path:
    sys.path.insert(0, str(REPO / "src"))

TINY_CELLS = {"tiny.tiny-chat": "tiny-chat", "tiny.tiny-closed": "tiny-closed"}


def make_root(tmp: Path, extra_metric: str = None) -> Path:
    shutil.copytree(REPO / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(FIXTURES / "tiny.json", tmp / "bench/configs/tiny.json")
    bench["configs"].append({"name": "tiny", "source": "test fixture",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "CPU tests"})
    limits = json.loads((FIXTURES / "tiny-limits.json").read_text())
    for name, traffic in TINY_CELLS.items():
        shutil.copy(FIXTURES / f"{traffic}.json",
                    tmp / f"bench/traffic/{traffic}.json")
        (tmp / f"bench/limits/{name}.json").write_text(json.dumps(limits))
        bench["workloads"].append({"name": name, "config": "tiny",
                                   "traffic": traffic, "chips": 1,
                                   "why": "CPU tests"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if "workloads" in m:
                m["workloads"].append(name)
    if extra_metric:
        shutil.copy(FIXTURES / f"{extra_metric}.py",
                    tmp / f"bench/metrics/{extra_metric}.py")
        bench["per_layer"].append({
            "name": extra_metric, "unit": "1", "better": "higher",
            "source": "program_counter", "layer": "engine scheduler",
            "moves": "itl_p99_ms"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    peaks = json.loads((tmp / "bench/peaks.json").read_text())
    peaks["devices"]["cpu"] = {"bf16_flops_per_s": 1e12,
                               "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
    (tmp / "bench/peaks.json").write_text(json.dumps(peaks))
    return tmp
