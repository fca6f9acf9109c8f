#!/usr/bin/env python3
"""Record the small chip trace that ``test_bench_trace.py`` reduces.

    python3 bench/tests/record_trace.py [OUT_DIR]    # on a TPU

Serves the fixture model (``fixtures/tiny.json``) through the facade,
profiles about 50 ms of decode steps with the harness's own profiler,
and writes ``chip_trace.xplane.pb.gz``, with the host clock readings
the reduction needs in ``chip_trace.json``, into OUT_DIR (by default
``fixtures/``).
"""
from __future__ import annotations

import glob
import gzip
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
FIX = Path(__file__).resolve().parent / "fixtures"


def main(argv) -> int:
    import jax

    from bench import mix, weights
    from bench.cells import load_module
    from bench.trace import Profiler
    from repro.configs.base import ModelConfig
    from repro.models import transformer as T
    from repro.serving import FleetSpec, PoolSpec

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace: needs a TPU")
    cfg = json.loads((FIX / "tiny.json").read_text())
    ref = load_module(ROOT / "bench/reference/dense.py", "dense")
    mcfg = ModelConfig(**ref.program_kwargs(cfg))
    params = weights.make(jax.eval_shape(lambda: T.model_init(
        jax.random.PRNGKey(0), mcfg)), "bfloat16", 1)
    pool = dict(max_slots=4, prompt_len=32, max_new=64, max_window=4,
                max_wait_s=0.0, block_size=8)
    client = FleetSpec(pools=[PoolSpec("lm", ("tpu_v5e_bf16",),
                                       backend="engine", **pool)],
                       workload="transformer", seq_len=32).build(
        model=(mcfg, params), warm=False)
    client.submit(mix.prompt_tokens(1, 0, 8, mcfg.vocab_size), max_new=2)
    client.drain()                               # compile
    prof = Profiler(0.0, 0.0)
    ex = client.router.pools["lm"].executor
    relay, stop_at = ex.on_token, [None]

    def on_token(rid, tok, step):
        now = time.perf_counter()
        if stop_at[0] is None:
            prof.start()
            stop_at[0] = time.perf_counter() + 0.05
        elif now > stop_at[0] and not prof.done:
            prof.stop()
        relay(rid, tok, step)
    ex.on_token = on_token
    for i in range(4):
        client.submit(mix.prompt_tokens(1, i + 1, 8 + i, mcfg.vocab_size),
                      max_new=64)
    with jax.profiler.TraceAnnotation("bench.pump"):
        client.drain()
    prof.stop()
    path = glob.glob(f"{prof.dir}/**/*.xplane.pb", recursive=True)[0]
    raw = Path(path).read_bytes()
    out = Path(argv[0]) if argv else FIX
    out.mkdir(parents=True, exist_ok=True)
    (out / "chip_trace.xplane.pb.gz").write_bytes(gzip.compress(raw, 9))
    (out / "chip_trace.json").write_text(json.dumps(
        {"sync": prof.sync, "end": prof.end,
         "device_kind": jax.devices()[0].device_kind}))
    print(f"record_trace: {len(raw)} bytes raw, "
          f"{(out / 'chip_trace.xplane.pb.gz').stat().st_size} gzipped")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
