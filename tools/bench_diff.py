"""bench_diff — noise-aware regression gate over bench artifacts.

Compares the RATIO metrics of two bench records (the bench-variance
policy: absolute tok/s on this host is weather, ratios are signal) and
exits nonzero naming every metric that moved past its noise band in the
worse direction. Records from different backends compare nothing — every
row is skipped with the reason, and the verdict is "incomparable" (exit
0: there is no evidence of regression, and pretending a TPU-vs-CPU MFU
ratio is evidence would be worse than silence).

Usage::

    # diff two artifacts (driver round files or raw bench payloads)
    python tools/bench_diff.py BENCH_r04.json BENCH_r06.json

    # gate a candidate against the checked-in pinned baseline
    python tools/bench_diff.py tools/bench_baseline.json new_round.json

    # re-pin the baseline from an artifact (newest BENCH_r* by default)
    python tools/bench_diff.py --pin tools/bench_baseline.json \
        [from_artifact.json]

    # widen/narrow every band (relative, e.g. 0.4 = ±40%)
    python tools/bench_diff.py --band 0.4 A.json B.json

``main(argv)`` is importable and returns the exit code — tests and the
bench's own verdict row call it in-process.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from paddle_tpu.observability.sentry import baselines as bl  # noqa: E402


def _pin(out_path: str, from_path: str = None, quiet: bool = False) -> int:
    src = from_path or bl.newest_round_artifact(_REPO)
    if src is None:
        print("bench_diff: no BENCH_r*.json artifact to pin from",
              file=sys.stderr)
        return 2
    record = bl.load_record(src)
    pinned = bl.pin_baseline(record, source=os.path.basename(src))
    if not pinned["metrics"]:
        print(f"bench_diff: {src} carries no ratio metrics to pin",
              file=sys.stderr)
        return 2
    with open(out_path, "w", encoding="utf-8") as f:
        json.dump(pinned, f, indent=2, sort_keys=True)
        f.write("\n")
    if not quiet:
        print(f"pinned {len(pinned['metrics'])} ratio metrics from "
              f"{src} -> {out_path}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="bench_diff",
        description="diff bench artifacts over ratio metrics with "
                    "noise-aware bands; nonzero exit names regressions")
    ap.add_argument("base", nargs="?",
                    help="baseline: pinned bench_baseline.json or any "
                         "bench artifact")
    ap.add_argument("cand", nargs="?",
                    help="candidate artifact")
    ap.add_argument("--band", type=float, default=None,
                    help="override every per-metric relative band")
    ap.add_argument("--pin", metavar="OUT",
                    help="write a pinned baseline to OUT from BASE (or "
                         "the newest BENCH_r*.json) and exit")
    ap.add_argument("--json", action="store_true",
                    help="emit the summary as one JSON line instead of "
                         "the table")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)

    if args.pin:
        return _pin(args.pin, from_path=args.base, quiet=args.quiet)
    if not args.base or not args.cand:
        ap.error("need BASE and CAND artifacts (or --pin OUT)")
    try:
        base = bl.load_record(args.base)
        cand = bl.load_record(args.cand)
    except (OSError, ValueError) as e:
        print(f"bench_diff: {e}", file=sys.stderr)
        return 2
    diff = bl.diff_records(base, cand, band_override=args.band)
    if args.json:
        print(json.dumps(diff.summary(), sort_keys=True))
    elif not args.quiet:
        print(diff.format())
    if diff.regressions:
        print("bench_diff: REGRESSED past the noise band: "
              + ", ".join(diff.regressions), file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
