#!/usr/bin/env python3
"""Readings that the correctness limits are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds <n>...

For each seed, in one process: one run of the cell as ``bench/run.py``
makes it (its window at the cell's own load, then the comparison), and on
the same sampled requests the control: the reference computed with every
matrix product in float8 e4m3 (``bench/reference.py``), whose own argmax
stands in for the served token.  Prints one JSON line per seed with the
program's widest gap and verdict, and the control's, judged by the same
limit.  Exits non-zero where a control comes out correct: the limit then
does not separate the program from the control.  The benchmark's runs
never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    from bench import run

    control_passed = []
    for seed in args.seeds:
        out = run.run(ROOT, args.workload, seed, args.seconds, False,
                      control=True)
        c = out["checks"]
        print("calibrate " + json.dumps({
            "workload": args.workload, "seed": seed,
            "program_gap": c["widest_gap"]["value"],
            "correct": out["correct"],
            "control_gap": c["control_gap"]["value"],
            "control_correct": out["control_correct"],
            "tokens": c["tokens_checked"]["value"],
            "metrics": out["metrics"], "device": out["device"]}), flush=True)
        if out["control_correct"]:
            control_passed.append(seed)
    if control_passed:
        print(f"calibrate: the control came out correct on seeds "
              f"{control_passed}", file=sys.stderr)
    return 1 if control_passed else 0


if __name__ == "__main__":
    sys.exit(main())
