#!/usr/bin/env python3
"""Read the two ends a cell's `correct` limit is set between.

    python3 benchmarks/chip/limits.py --workload <name> --seeds 1,2,3 \
        --seconds <s> [--control int8] [--rehearse]

One process, one seed after another: each seed builds the cell as a
run does (weights from the seed, engine, warm-up, lead-in), measures a
window of `--seconds` at the cell's own load, frees the engine and
compares the sample of served tokens with the plain reference: the
LOWER reading. With `--control`, the same prompts and tokens are also
judged with the reference computed in the lower precision in the
program's place: the UPPER reading. One JSON line per seed; the last
line gives the largest lower and the smallest upper reading, and on
how many seeds the program and the control read `correct` (the
control has to read it on none).
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

import run as harness


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    lower, upper, verdicts = [], [], []
    for seed in (int(s) for s in args.seeds.split(",")):
        driver, run = harness.drive(
            ["--workload", args.workload, "--seed", str(seed), "--seconds",
             str(args.seconds)] + (["--rehearse"] if args.rehearse else []))
        if driver is None:
            return 1
        correct, compared = driver.check()
        rec = {"seed": seed, "correct": bool(correct), "failed": run["failed"],
               "program": {k: v["value"] for k, v in compared.items()},
               "program_widest_gap": driver.widest_gap,
               "program_gap_mean": driver.gap_mean,
               "end_to_end": run["end_to_end"]}
        lower.append(compared)
        if args.control:
            ctrl_correct, ctrl = driver.check(control=args.control)
            rec["control_correct"] = bool(ctrl_correct)  # has to be false
            rec["control"] = {k: v["value"] for k, v in ctrl.items()}
            rec["control_widest_gap"] = driver.widest_gap
            rec["control_gap_mean"] = driver.gap_mean
            upper.append(ctrl)
        verdicts.append((rec["correct"], rec.get("control_correct")))
        print(json.dumps(rec), flush=True)
        del driver, run
        gc.collect()
    keys = sorted(lower[0])
    print(json.dumps({
        "seeds": len(lower),
        "program_correct": sum(1 for c in verdicts if c[0]),
        "control_correct": (sum(1 for c in verdicts if c[1])
                            if upper else None),
        "lower_max": {k: max(c[k]["value"] for c in lower) for k in keys},
        "upper_min": ({k: min(c[k]["value"] for c in upper) for k in keys}
                      if upper else None)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
