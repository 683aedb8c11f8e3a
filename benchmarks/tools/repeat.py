#!/usr/bin/env python3
"""Run one cell several times, one process a run, and report the spread.

    python benchmarks/tools/repeat.py --workload <cell> --seeds 11,12,13 \
        [--seconds S] [--trace 0|1] [--out chiprun_out/<file>.jsonl]

For the builder and the reviewer, not for the driver: it is how the bounds
in BENCHMARK.json were read (PERF.md section 2). The spread of a metric is
the distance between the first and third quartile of its runs
(``statistics.quantiles(values, n=4)``) as a share of their median. This
process never touches JAX, so each run gets the chip to itself.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def spread(values):
    if len(values) < 2:
        return None
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    seconds = args.seconds or bench["run_seconds"]
    lines = []
    for seed in args.seeds.split(","):
        t0 = time.perf_counter()
        p = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
             "--workload", args.workload, "--seed", seed,
             "--seconds", str(seconds), "--trace", str(args.trace)],
            cwd=ROOT, capture_output=True, text=True)
        wall = time.perf_counter() - t0
        out = p.stdout.strip().splitlines()
        rec = {"seed": int(seed), "rc": p.returncode, "wall_s": wall}
        try:
            rec["line"] = json.loads(out[-1])
            if "metrics" not in rec["line"]:
                rec["line"] = None      # the run died before its result line
        except (IndexError, ValueError):
            rec["line"] = None
        rec["log"] = out[:-1][-40:]
        if p.returncode or rec["line"] is None:
            rec["stderr"] = p.stderr[-3000:]
        lines.append(rec)
        brief = rec["line"] and {k: v["value"] for k, v in
                                 rec["line"]["metrics"].items()}
        print(json.dumps({"seed": int(seed), "rc": p.returncode,
                          "wall_s": round(wall, 1),
                          "correct": rec["line"] and rec["line"]["correct"],
                          "failed": rec["line"] and rec["line"]["failed"],
                          "attempted": rec["line"] and rec["line"]["attempted"],
                          "metrics": brief}), flush=True)
        if rec.get("stderr"):
            print(rec["stderr"], flush=True)
        for l in rec["log"]:
            if '"compared"' in l or '"check"' in l or "tokens_in_window" in l \
                    or '"steps"' in l or (args.trace and "device_ops_full" in l):
                print("   ", l[:1800], flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.join(ROOT, args.out)), exist_ok=True)
        with open(os.path.join(ROOT, args.out), "a") as f:
            for rec in lines:
                f.write(json.dumps(dict(rec, workload=args.workload,
                                        seconds=seconds, trace=args.trace)) + "\n")
    good = [r["line"] for r in lines if r["line"]]
    names = sorted({k for l in good for k in l["metrics"]})
    for n in names:
        vals = [l["metrics"][n]["value"] for l in good if n in l["metrics"]]
        print(json.dumps({"metric": n, "n": len(vals),
                          "median": statistics.median(vals),
                          "min": min(vals), "max": max(vals),
                          "spread_iqr_over_median": spread(vals)}), flush=True)
    if good and args.trace:
        print(json.dumps({"breakdown": good[-1].get("breakdown"),
                          "device": good[-1]["device"]}), flush=True)
    return 0 if all(r["rc"] == 0 for r in lines) else 1


if __name__ == "__main__":
    sys.exit(main())
