"""Serving launcher: batched requests against an MPAI-partitioned model,
through the ``repro.serving`` facade.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b --smoke \
        --plan mpai --requests 16

Throughput note: tokens/s is reported *decode-only* (sampled decode
tokens over wall time inside decode steps), the same definition
``benchmarks/decode_bench.py`` uses — the old launcher divided total
tokens (prompt handling included) by end-to-end wall time, which mixed
prefill-window idle time into the number.
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.serving import FleetSpec, PoolSpec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--plan", default="mpai", choices=["bf16", "mpai"])
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="write a Chrome trace_event JSON of the run "
                         "(open in Perfetto / chrome://tracing)")
    args = ap.parse_args()
    enable_compile_cache()

    spec = FleetSpec(
        pools=[PoolSpec("serve", ("tpu_v5e_bf16",), backend="engine",
                        capacity=1, max_window=args.max_batch,
                        max_wait_s=0.0, max_slots=args.max_batch,
                        prompt_len=16, max_new=args.max_new,
                        plan=args.plan if args.plan == "mpai" else None)],
        workload="transformer", arch=args.arch, smoke=args.smoke,
        seq_len=16)
    client = spec.build()
    if args.trace:
        client.enable_tracing()

    rng = np.random.default_rng(0)
    vocab = client.engines["serve"].cfg.vocab_size
    handles = [client.submit(
        rng.integers(0, vocab, rng.integers(2, 16)).astype(np.int32),
        slo="offline", max_new=args.max_new)
        for _ in range(args.requests)]
    client.drain()

    pool = client.telemetry["pools"]["serve"]
    served = sum(h.admitted and not h.telemetry["dropped"]
                 for h in handles)
    print(f"served {served} requests / {pool['tokens_generated']} tokens "
          f"in {pool['batches']} batches, {pool['busy_s']:.2f}s busy "
          f"({pool['decode_tokens_per_s']:.1f} decode tok/s, "
          f"occupancy p50 {pool['slot_occupancy']['p50']})")
    if args.trace:
        from repro.obs import export_chrome_trace
        trace = export_chrome_trace(client, args.trace)
        print(f"wrote {len(trace['traceEvents'])} trace events to "
              f"{args.trace}")


if __name__ == "__main__":
    main()
