#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (compile cache, weights from the seed, the fleet, warm-up of the
cell's own programs) is timed as ``setup_s``; then the cell's traffic is
served for ``--seconds`` on the wall clock.  With ``--trace 0`` the
result carries the cell's end-to-end metrics; with ``--trace 1`` its
per-layer metrics, read from stage stamps, counters and a profiler trace
of part of the window.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
``breakdown`` (traced runs) and ``checks``, the numbers compared with
their limits, which also end standard error.  Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="open loop: offer this rate instead of the "
                         "traffic file's (for finding the knee)")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the raw .xplane.pb into this directory")
    args = ap.parse_args(argv)
    from bench import harness
    from bench.cells import CellError
    try:
        result = harness.run(ROOT, args.workload, args.seed, args.seconds,
                             bool(args.trace), rate=args.rate,
                             keep_trace=args.keep_trace)
    except CellError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
