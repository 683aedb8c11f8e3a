#!/usr/bin/env python3
"""The engine's books beside the device's own record, from one traced run.

    python benchmarks/tools/books.py .bench_out/<cell>/trace

For the builder and the reviewer, not for the driver: it is the check that
``prefill_stream_share.*``, ``tick_stream_ms.decode`` and
``stream_unattributed_share.*`` (``engine.stats()``'s ``stream_*`` sums,
``inference/serving.py::_StreamBooks``) say what the chip did. Every
``serving::drain`` span carries what the books said at it (``chained``: 1
a run closed here, of ``interval_us`` with ``ticks`` ticks and
``admit_calls`` admission programs; 2 the stamp was late and the run stays
open; 0 the time since the last stamp that closed anything was given up),
and the span ends at the stamp, so each run lies beside the ``XLA
Modules`` events of the same ``.xplane.pb``, on the same clock. ``books``
replays the books' rule over the traced window from the spans alone;
``device`` is what the modules' line gives for the same window: the two
should agree, and a closed run should be busy from end to end. The reader
of those four span stats; this process runs no program.
"""

from __future__ import annotations

import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import reduce  # noqa: E402

RECENT = 8          # _StreamBooks.RECENT: clean ticks the baseline averages
TICK = r"^jit_run\("


def check(events, tick: str = TICK) -> dict | None:
    """The books against the device over the traced window of ``events``
    (``reduce.read_xplane``): None where the program emits no books."""
    window = reduce.window_of(events)
    drains = sorted((e for e in events if e.plane == reduce.HOST_PLANE
                     and e.name == "serving::drain"
                     and "interval_us" in e.stats), key=lambda e: e.end_ns)
    if window is None or not drains:
        return None
    lo, hi = window
    width = hi - lo
    rx = re.compile(tick)
    mods = [(e.start_ns, e.end_ns, bool(rx.search(e.name))) for e in events
            if reduce.DEVICE_PLANE.match(e.plane)
            and e.line == reduce.MODULES_LINE]
    inside = [(max(a, lo), min(b, hi), t) for a, b, t in mods
              if b > lo and a < hi]
    runs = [b - a for a, b, t in mods if t and a >= lo and b <= hi]

    def busy(a, b):
        return reduce._length(reduce.union(
            (max(s, a), min(e, b)) for s, e, _ in mods if e > a and s < b))

    kinds = {k: {"n": 0, "s": 0.0}
             for k in ("clean", "admit", "given_up", "left_open")}
    tick_ns = ticks = admit_ns = gap_ns = unattributed_ns = 0.0
    closed_ns = closed_busy_ns = admit_dev_ns = 0.0
    clipped = 0
    # the engine's baseline was running before the trace began: a run the
    # trace shows no clean tick before is taken at the first one after it
    first = next((d.stats["interval_us"] * 1e3 / d.stats.get("ticks", 1)
                  for d in drains if d.stats["chained"] == 1
                  and not d.stats["admit_calls"]), None)
    recent, booked, worst, late = [], None, [], []
    for d in drains:
        length, stamp = d.stats["interval_us"] * 1e3, d.end_ns
        k, calls = d.stats.get("ticks", 1), d.stats["admit_calls"]
        kind = ("given_up", "admit" if calls else "clean",
                "left_open")[d.stats["chained"]]
        # the books had summed the stream up to ``booked``: a drain that
        # closes a run or gives the time up sums it up to its own stamp
        since = stamp - booked if booked is not None else length
        if kind != "left_open":
            booked = stamp
        base = sum(recent) / len(recent) if recent else first
        if kind == "clean":
            recent = (recent + [length / k])[-RECENT:]
        if stamp - max(length, since) < lo or stamp > hi:
            continue
        kinds[kind]["n"] += 1
        kinds[kind]["s"] += length / 1e9
        if kind in ("given_up", "left_open"):
            span = since if kind == "given_up" else length
            late.append([d.stats.get("block"), kind, span / 1e6, calls,
                         d.dur_ns / 1e6, (d.start_ns - stamp + span) / 1e6,
                         100.0 * busy(stamp - span, stamp) / max(span, 1.0)])
            if kind == "given_up":
                unattributed_ns += since
            continue
        a = stamp - length
        closed_ns += length
        closed_busy_ns += busy(a, stamp)
        gap_ns += max(since - length, 0.0)
        if kind == "clean":
            tick_ns += length
            ticks += k
        elif base is None:
            unattributed_ns += length
        else:
            took = max(length - k * base, 0.0)
            clipped += length < k * base
            dev = sum(min(e, stamp) - max(s, a) for s, e, t in mods
                      if not t and e > a and s < stamp)
            admit_ns += took
            admit_dev_ns += dev
            worst.append([abs(took - dev) / 1e6, length / 1e6, dev / 1e6,
                          k * base / 1e6])
    pct = lambda ns: 100.0 * ns / width                       # noqa: E731
    return {
        "window_s": width / 1e9,
        "books": {
            "prefill_stream_share": pct(admit_ns),
            "tick_stream_ms": tick_ns / ticks / 1e6 if ticks else None,
            "stream_gap_share": pct(gap_ns),
            "stream_unattributed_share": pct(unattributed_ns)},
        "device": {
            "other_modules_share": pct(sum(b - a for a, b, t in inside
                                           if not t)),
            "tick_module_ms": sum(runs) / len(runs) / 1e6 if runs else None,
            "tick_module_runs": len(runs),
            "idle_share": pct(width - busy(lo, hi))},
        # the drains by what the books said at them (``chained`` 1 with or
        # without admission work, 0, 2) and the seconds of their intervals
        "intervals": kinds,
        "closed_busy_share": (100.0 * closed_busy_ns / closed_ns
                              if closed_ns else None),
        "admit_s_books_vs_device": [admit_ns / 1e9, admit_dev_ns / 1e9],
        # admission runs shorter than their own ticks' baseline: where the
        # books' "not below 0" engaged (a bias upward, if it ever does)
        "admit_clipped": clipped,
        # every drain that closed nothing: [block, what the books said, ms
        # given up or left open, admit_calls, ms the host sat in this drain
        # (near 0: the block was ready before it looked, a late stamp), ms
        # from that span's start to the look, % of it the device was busy]
        "not_closed": late[:16],
        # [|books - device|, run, admission modules inside, its ticks at
        # the recent clean tick] in ms, the five widest apart
        "admit_worst_ms": sorted(worst, reverse=True)[:5],
    }


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    out = check(reduce.read_xplane(argv[0]))
    if out is None:
        print("BOOKS " + json.dumps(None))
        return 1
    print("BOOKS " + json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
