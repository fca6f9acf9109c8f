"""Benchmark orchestrator — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full]

Prints ``name,us_per_call,derived`` CSV rows:
  * fig2_*       — Fig. 2 accelerator-throughput reproduction (cost model)
  * table1_*     — Table I UrsoNet latency (cost model) + measured
                   accuracy deltas (fp32 / PTQ / QAT / MPAI)
  * partition_*  — partition-point Pareto sweep (the paper's §IV
                   methodology, implemented)
  * micro_*      — precision-path microbenchmarks
  * roofline_*   — per-(arch x shape) roofline terms from dry-run artifacts
  * router_*     — fleet-router dispatch throughput / SLO violations /
                   failover (synthetic open-loop traffic through the
                   repro.serving facade), plus router_lm_serving:
                   engine-backed routed decode vs the windowed baseline
  * decode_*     — continuous-batching engine vs windowed baseline
                   (tokens/s, inter-token p50/p99, slot occupancy)
  * orbit_*      — orbit-aware fleet controller: eclipse-transition
                   energy cap (capped vs uncapped budget ratio) and live
                   LM pool autoscaling with graceful retirement
  * coproc_*     — co-processing prefill: chunked paged prefill vs the
                   windowed baseline (output equality + prefix-sharing
                   savings) and the disaggregated prefill->decode
                   two-pool fleet vs the unified engine pool
  * obs_*        — flight-recorder overhead: decode tokens/s with
                   per-request span tracing on vs off (``--trace PATH``
                   additionally writes the traced run as Chrome
                   trace_event JSON for Perfetto / chrome://tracing)
  * chaos_*      — radiation-hardened data plane: hardening (per-block
                   digests + fused decode-path verify + scrub) decode
                   overhead vs hardening-off, and a seeded SEU campaign
                   (kv_bitflip / slot_stall / handoff_loss / pool fault)
                   gated on zero corrupted tokens and exactly-once
                   accounting

``--check`` turns invariants into failures across the serving benches:
fleetlint static findings (wall clocks in virtual-clock code, host
syncs in jitted functions, allocator bypasses — see
``src/repro/analysis/``) abort before any bench runs, and
truncated open-loop traces (the ``max_s`` safety net fired, so the
trace silently shrank), chunked-prefill output mismatches, token loss
at the co-processing handoff, mis-attributed per-stage energy, orphan
trace spans, and flight-recorder overhead above 3% all abort the run
instead of printing a smaller number.
"""
from __future__ import annotations

import argparse
import warnings


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="longer QAT training for Table I accuracy rows")
    ap.add_argument("--skip-accuracy", action="store_true",
                    help="cost-model rows only (fast CI mode)")
    ap.add_argument("--check", action="store_true",
                    help="fail on truncated traces / completeness / "
                         "equality violations in the serving benches")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="write the obs bench's traced serving run as "
                         "Chrome trace_event JSON (pool lanes, engine "
                         "stages, counter tracks — open in Perfetto / "
                         "chrome://tracing); see the Observability "
                         "quickstart in ROADMAP.md")
    args, _ = ap.parse_known_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (chaos_bench, coproc_bench, decode_bench,
                            fig2_throughput, obs_bench, orbit_bench,
                            partition_sweep, precision_micro,
                            roofline_bench, router_bench, table1_ursonet)

    if args.check:
        # fleetlint first: a benchmark number measured on a tree with a
        # wall-clock read in virtual-clock code or a host sync in the
        # fused dispatch is already fiction, so static findings abort
        # before any bench spends minutes producing it
        from repro.analysis import run_lint
        report = run_lint()
        if not report.clean:
            for f in report.findings:
                print(f"fleetlint: {f.path}:{f.line}: {f.code} "
                      f"{f.message}")
            for key in report.stale_suppressions:
                print(f"fleetlint: stale suppression {key!r}")
            for path, err in report.parse_errors:
                print(f"fleetlint: {path}: parse error: {err}")
            raise SystemExit(
                f"--check: {len(report.findings)} fleetlint finding(s); "
                f"fix or reason-suppress in "
                f"src/repro/analysis/baseline.json before benchmarking")
        # any open_loop truncation inside a bench is a hard failure:
        # a trace cut by the max_s safety net undercounts the offered
        # load, so every ratio gated downstream would be fiction
        warnings.filterwarnings(
            "error", message=".*open_loop truncated.*",
            category=RuntimeWarning)

    fig2_throughput.main()
    partition_sweep.main()
    precision_micro.main()
    if args.skip_accuracy:
        for r in table1_ursonet.latency_rows():
            print(f"table1_latency_{r['processor']},0,"
                  f"model_ms={r['model_ms']:.0f};paper_ms={r['paper_ms']}")
    else:
        table1_ursonet.main(steps=600 if args.full else 250)
    roofline_bench.main()
    router_bench.main(n=200 if not args.full else 400)
    decode_bench.main(smoke=not args.full)
    orbit_bench.main(smoke=not args.full, check=args.check)
    coproc_bench.main(smoke=not args.full, check=args.check,
                      min_ratio=1.0 if args.check else 0.0)
    obs_bench.main(smoke=not args.full, check=args.check,
                   trace_out=args.trace)
    chaos_bench.main(smoke=not args.full, check=args.check)


if __name__ == "__main__":
    main()
