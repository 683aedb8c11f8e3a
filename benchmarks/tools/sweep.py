#!/usr/bin/env python3
"""Find an open-loop cell's knee: one set-up, one window per offered rate.

    python benchmarks/tools/sweep.py --workload <cell> --rates 3,4,5,6 --seconds 25

For the builder, once, when a cell is defined (PERF.md records the table):
the knee is the highest rate at which no backlog is left at the window's
close and no request goes unanswered; the cell then runs at four fifths of
it. Each rate is the same mix with ``rate_rps`` replaced, from the seed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=4242)
    args = ap.parse_args(argv)
    from benchmarks import reduce, run, serve, traffic
    cell, config, mix, _, _ = run.resolve(args.workload,
                                          os.path.join(ROOT, "BENCHMARK.json"))
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or os.path.join(ROOT, ".jax_cache"))
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    ctx = run.Context(config, mix, args.seed, args.seconds, False,
                      os.path.join(ROOT, ".bench_out", "sweep"))
    model, eng, _ = serve.build(ctx)
    for rate in (float(x) for x in args.rates.split(",")):
        ctx.samples.clear()
        ctx.counters.clear()
        sched = traffic.serving_schedule(dict(mix, rate_rps=rate), args.seed,
                                         args.seconds, config["vocab_size"],
                                         config["engine"]["max_len"])
        e2e, attempted, failed, _ = serve.measure(ctx, eng, sched, args.seconds)
        while eng.has_work():           # drain before the next rate
            eng.step()
        eng.take_finished()
        s = ctx.samples
        print(json.dumps({
            "rate_rps": rate, "attempted": attempted, "failed": failed,
            "queued_at_close": ctx.counters["queued_at_close"],
            "ttft_p50_s": reduce.percentile(s["ttft_s"], 50),
            "ttft_p90_s": e2e["ttft_p90_s"],
            "ttft_max_s": max(s["ttft_s"], default=None),
            "queue_wait_p90_ms": reduce.percentile(s["queue_wait_ms"], 90),
            "occupancy_mean_pct": sum(s["occupancy_pct"]) / max(len(s["occupancy_pct"]), 1),
            "tokens_per_s": ctx.counters["tokens_in_window"] / args.seconds,
            "decode_ticks_s": ctx.counters["decode_ticks"] / args.seconds,
            "step_ms_p50": reduce.percentile(s["step_ms"], 50)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
