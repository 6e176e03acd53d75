#!/usr/bin/env python3
"""Find an open-loop cell's knee once: the same cell at several rates.

    python3 benchmarks/chip/sweep.py --workload <cell> --rates 4,6,8,10 \
        --seconds <s> --seed <n> [--rehearse]

One process, one rate after another, each a whole set-up and window
with the file's rate replaced. One JSON line per rate. The knee is the
highest rate with no failures and no growing backlog (the second
half's first-token times no worse than the first's, few requests
unfinished at the close); the cell's file then fixes 0.8 x the knee.
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    for rate in (float(r) for r in args.rates.split(",")):
        def at_rate(ctx, driver, rate=rate):
            ctx.workload["traffic"]["rate_per_s"] = rate

        driver, run = harness.drive(
            ["--workload", args.workload, "--seed", str(args.seed),
             "--seconds", str(args.seconds)]
            + (["--rehearse"] if args.rehearse else []), at_rate)
        if driver is None:
            return 1
        print(json.dumps({"rate_per_s": rate, "attempted": run["attempted"],
                          "failed": run["failed"],
                          "end_to_end": run["end_to_end"],
                          "notes": run["notes"]}), flush=True)
        del driver, run
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
