#!/usr/bin/env python3
"""Run one cell with a control in the program's place.  The comparison
that decides ``correct`` has to call it not correct; its
``served_gap_std`` is the upper reading the cell's limit is set below.
The benchmark's own runs never do this.

    python3 bench/control.py --workload <cell> --seed <n> --seconds <s> \
        --path program|reference

``program``: the program's own int8 serving path (``plan="mpai"``: int8
weights and activations in every layer but the last) serves the window.
``reference``: where that path does not fit on the chip, the window is
served as usual, and at each position of the sampled requests the
reference computed through int8 names the token that is scored.
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
    ap.add_argument("--path", choices=("program", "reference"),
                    default="program")
    args = ap.parse_args(argv)
    from bench import harness
    kw = ({"pool_overrides": {"plan": "mpai"}} if args.path == "program"
          else {"int8_reference": True})
    result = harness.run(ROOT, args.workload, args.seed, args.seconds,
                         False, **kw)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
