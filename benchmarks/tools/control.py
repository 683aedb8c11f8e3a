#!/usr/bin/env python3
"""Read the numbers a cell's limits are set from: sound runs and the control.

    python benchmarks/tools/control.py --workload <cell> --seeds 1,2,3 \
        [--seconds S] [--quant fp8] [--out chiprun_out/<file>.jsonl]

For the builder and the reviewer (PERF.md section 2 has the readings); the
benchmark's own runs never run it. The control is the plain reference put in
the program's place, with every matrix multiplication's inputs rounded to
the precision below the configuration's (bf16 -> float8_e4m3).

* serving: ONE set-up; per seed new seeded weights go into the same engine,
  a short window runs at the cell's own load, and for the sampled requests
  both the served tokens' gaps (the sound reading) and the gaps of the
  tokens the control puts first are read against the float32 reference.
* training: per seed the float32 reference's first steps and the control's;
  the control is held to the program's limits (the program's own readings
  come from ordinary runs of the cell).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--quant", default="fp8")
    ap.add_argument("--out")
    ap.add_argument("--benchmark-file",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--allow-cpu", action="store_true",
                    help="tests only: the readings of a CPU run set no limit")
    args = ap.parse_args(argv)
    from benchmarks import check, program, run, serve, traffic, weights
    cell, config, mix, _, _ = run.resolve(args.workload, args.benchmark_file)
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or os.path.join(ROOT, ".jax_cache"))
    if jax.devices()[0].platform != "tpu" and not args.allow_cpu:
        print("control: no TPU", file=sys.stderr)
        return 2
    seeds = [int(s) for s in args.seeds.split(",")]
    records = []
    if config["kind"] == "serve":
        ctx = run.Context(config, mix, seeds[0], args.seconds, False,
                          os.path.join(ROOT, ".bench_out", "control"))
        model, eng, _ = serve.build(ctx)
        names = {n: program.canonical(config["program"]["param_names"], n)
                 for n, _ in model.named_parameters()}
        samples = {}
        for seed in seeds:          # phase 1: the program's windows
            for a in jax.tree.leaves(dict(model.raw_parameters())):
                a.delete()          # two sets of weights do not fit
            program.install(model, names, weights.make_all(seed, config))
            eng._params = model.raw_parameters()     # the engine's own copy
            sched = traffic.serving_schedule(mix, seed, args.seconds,
                                             config["vocab_size"],
                                             config["engine"]["max_len"])
            _, _, _, served = serve.measure(ctx, eng, sched, args.seconds)
            for rid in ctx.unfinished:
                eng.cancel(rid)
            eng.take_finished()
            samples[seed] = check.sample_served(
                served, seed, config["check"]["sample_requests"])
        # phase 2: the engine freed, the reference and the control
        for a in jax.tree.leaves((eng.pools, dict(model.raw_parameters()))):
            a.delete()
        del eng, model
        for seed in seeds:
            t0 = time.perf_counter()
            sound, control = check.reference_gaps(config, seed, samples[seed],
                                                  args.quant)
            records.append({
                "seed": seed, "requests": len(samples[seed]),
                "tokens": int(sound.size),
                "sound": {"gap_max": float(sound.max()), "gap_mean": float(sound.mean())},
                "control": {"gap_max": float(control.max()), "gap_mean": float(control.mean()),
                            "tokens_changed": int((control > 0).sum())},
                "control_correct": all(n["ok"] for n in check.gap_numbers(config, control)),
                "reference_s": round(time.perf_counter() - t0, 1)})
            print(json.dumps(records[-1]), flush=True)
    else:
        steps = 3
        for seed in seeds:
            t0 = time.perf_counter()
            low = check.reference_training(config, mix, seed, steps, args.quant)
            first = {}      # the control's first gradient, kept on the host

            def keep(grads):
                first.update({n: np.asarray(g) for n, g in grads.items()})
                return grads
            check.reference_training(config, mix, seed, 1, args.quant, keep)
            ref = check.reference_training(config, mix, seed, steps,
                                           first_grads=first)
            numbers = check.training_numbers(config, low, ref)
            records.append({"seed": seed, "reference_losses": ref[0],
                            "control_losses": low[0],
                            "control": {n["name"]: n["value"] for n in numbers},
                            "control_correct": all(n["ok"] for n in numbers),
                            "reference_s": round(time.perf_counter() - t0, 1)})
            print(json.dumps(records[-1]), flush=True)
    if args.out:
        with open(os.path.join(ROOT, args.out), "a") as f:
            for rec in records:
                f.write(json.dumps(dict(rec, workload=args.workload,
                                        quant=args.quant)) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
