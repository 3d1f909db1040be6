"""Readings for a cell's correctness limit: the program's widest gap and
the control's, seed after seed, in one process (set-up is long, so one
process reads them all). Benchmark runs do not run this.

    python bench/calibrate.py --workload <name> --seeds 11 12 13 --seconds 30

Each run judges the control's tokens in place of the served ones through
the harness's own verdict, and the served tokens through the same verdict.
Prints one JSON line per seed with both verdicts, both gaps and the run's
end-to-end metrics, then the largest program gap and the smallest control
gap.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from run import compile_cache, run_cell


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    args = ap.parse_args()
    compile_cache()
    rows = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        res = run_cell(args.workload, seed, args.seconds, bool(args.trace),
                       t_start=t0, control=True,
                       log=lambda m: print(m, file=sys.stderr, flush=True))
        prog = res["program"]
        row = {"seed": seed, "correct": prog["correct"],
               "gap": prog["compared"]["widest_gap"]["value"],
               "control_correct": res["correct"],
               "control_gap": res["compared"]["widest_gap"]["value"],
               "metrics": {k: v["value"] for k, v in res["metrics"].items()},
               "attempted": res["attempted"], "failed": res["failed"],
               "memory_peak_bytes": res["device"]["memory_peak_bytes"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"max_gap": max(r["gap"] for r in rows),
                      "min_control_gap": min(r["control_gap"] for r in rows),
                      "program_all_correct": all(r["correct"] for r in rows),
                      "control_none_correct": not any(r["control_correct"]
                                                      for r in rows),
                      "seeds": len(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
