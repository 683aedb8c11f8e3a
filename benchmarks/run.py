#!/usr/bin/env python3
"""Run one cell of the benchmark once and print one JSON line.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: the cell in BENCHMARK.json,
its configuration's file and through it the configuration's family
(``families/``: leaves, reference, required work), ``traffic/<traffic>.json``
and, in a traced run, every ``layer_metrics/<name>.json`` that lists the
cell. One process per run: load, warm this cell's shapes, measure, check,
print. Without the chips the cell asks for, or on a device missing from
``peaks.json``, it exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()           # set-up is timed from process start

import argparse
import contextlib
import glob
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TRACE_SECONDS = 8.0        # a traced run traces the last so much of its window


def load_json(path):
    with open(path) as f:
        return json.load(f)


def resolve(workload: str, benchmark_file: str):
    """(cell, configuration, traffic mix, per-layer metric files)."""
    from benchmarks import families, traffic
    bench = load_json(benchmark_file)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no cell {workload!r} in {benchmark_file}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(os.path.join(ROOT,
                                    entry["file"]))
    config["chips"] = cell["chips"]
    families.of(config)     # a missing or unknown family fails here, loudly
    mix = traffic.load(cell["traffic"])
    metrics = []
    for path in sorted(glob.glob(os.path.join(HERE, "layer_metrics", "*.json"))):
        spec = load_json(path)
        if workload in spec["workloads"]:
            metrics.append(spec)
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    return cell, config, mix, metrics, e2e


class Context:
    """What a run gathers, and the window's bookkeeping."""

    def __init__(self, config, mix, seed, seconds, trace, out_dir):
        self.config, self.mix, self.seed, self.seconds = config, mix, seed, seconds
        self.trace = trace
        self.samples, self.counters, self.shapes = {}, {}, {}
        self.setup_s = None
        self.window_s = seconds         # the measured window's length
        self.unfinished = []            # engine ids a window left in flight
        self.compiles_in_window = 0
        self._in_window = False
        self._trace_dir = os.path.join(out_dir, "trace")
        self._tracing = None            # None: not yet, True: on, False: done

    def log(self, **kw):
        print(json.dumps(kw, default=float), flush=True)

    def stage(self, name):
        """Where set-up's time goes: seconds since process start."""
        self.log(stage=name, t=round(time.perf_counter() - T_START, 2))

    def span(self, name):
        if self.trace:
            import jax
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def count_program(self, prefix, opened, closed):
        """The program's own counters over the window, as
        ``<prefix>.<key>``: every numeric key of its ``stats()``, at the
        window's close less at its opening (a gauge such as ``queued``
        means little as a difference: PERF.md section 3)."""
        for k, v in closed.items():
            if (isinstance(v, (int, float)) and not isinstance(v, bool)
                    and isinstance(opened.get(k), (int, float))):
                self.counters[f"{prefix}.{k}"] = v - opened[k]

    def on_compile(self, event, secs, **_):
        if self._in_window and event.endswith("backend_compile_duration"):
            self.compiles_in_window += 1

    def open_window(self):
        if self.setup_s is None:        # a tool may open several windows
            self.setup_s = time.perf_counter() - T_START
        self._in_window = True

    def close_window(self):
        self._in_window = False
        self._stop_trace()

    def tick(self, now):
        """A traced run traces the END of its window, so that stopping the
        profiler (seconds, while it writes) falls outside the window."""
        if self.trace and self._tracing is None and now >= max(
                0.0, self.seconds - TRACE_SECONDS):
            import jax
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0     # bm:: spans need only the host tracer
            jax.profiler.start_trace(self._trace_dir, profiler_options=options)
            self._tracing = True

    def _stop_trace(self):
        if self._tracing:
            import jax
            jax.profiler.stop_trace()
            self._tracing = False

    def events(self):
        from benchmarks import reduce
        return reduce.read_xplane(self._trace_dir) if self._tracing is False else []


def run_cell(workload, seed, seconds, trace, benchmark_file=None,
             require_chip=True):
    """Drive one run; returns the result line as a dict. ``require_chip``
    False is for tests only: it skips the look for a chip, nothing else."""
    benchmark_file = benchmark_file or os.path.join(ROOT, "BENCHMARK.json")
    cell, config, mix, metric_specs, e2e_specs = resolve(workload,
                                                         benchmark_file)
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    out_dir = os.path.join(ROOT, ".bench_out", workload)
    os.makedirs(out_dir, exist_ok=True)
    import jax
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    if require_chip:
        if device["platform"] != "tpu" or device["count"] != cell["chips"]:
            print(f"benchmark: cell {workload} needs {cell['chips']} TPU "
                  f"chip(s), jax found {device}", file=sys.stderr)
            raise SystemExit(2)
        if device["kind"] not in peaks:
            print(f"benchmark: no peaks for device kind {device['kind']!r} "
                  f"in peaks.json", file=sys.stderr)
            raise SystemExit(2)
    elif device["count"] > cell["chips"]:
        devs = devs[:cell["chips"]]

    from benchmarks import reduce, serve, train
    ctx = Context(config, mix, seed, seconds, trace, out_dir)
    jax.monitoring.register_event_duration_secs_listener(ctx.on_compile)
    ctx.log(cell=workload, seed=seed, seconds=seconds, trace=trace,
            device=device, cache_dir=cache)
    ctx.stage("imports_done")
    res = {"serve": serve, "train": train}[config["kind"]].run(ctx)

    numbers = list(res["numbers"])
    numbers.append({"name": "compiles_in_window",
                    "value": ctx.compiles_in_window, "limit": 0,
                    "ok": ctx.compiles_in_window == 0})
    for n in numbers:
        ctx.log(compared=n["name"], value=n["value"], limit=n["limit"],
                ok=n["ok"])
    e2e = dict(res["e2e"], setup_s=ctx.setup_s)
    units = {m["name"]: m["unit"] for m in e2e_specs}
    out = {"correct": all(n["ok"] for n in numbers),
           "attempted": res["attempted"], "failed": res["failed"],
           "metrics": {}, "device": dict(
               device, memory_peak_bytes=int(res["memory_peak_bytes"]))}
    if not trace:
        out["metrics"] = {k: {"value": e2e[k], "unit": units[k]}
                          for k in units if e2e.get(k) is not None}
        return out
    events = ctx.events()
    summary = reduce.device_summary(events)
    if summary:
        out["device"].update(busy_s=summary["busy_s"],
                             window_s=summary["window_s"])
        out["breakdown"] = {"device_ops": reduce.top_ops(events),
                            "idle_gaps": reduce.idle_gaps_by_span(events)}
        ctx.log(trace_events=len(events), modules=reduce.top_modules(events),
                device_ops_full=reduce.top_ops(events, n=40, full=True))
    rctx = {"samples": ctx.samples, "counters": ctx.counters,
            "events": events, "model": config, "shapes": ctx.shapes,
            "peaks": peaks.get(device["kind"], {}), "e2e": e2e,
            "window_s": res["window_s"],
            "memory_peak_bytes": res["memory_peak_bytes"]}
    for spec in metric_specs:
        value = reduce.REDUCERS[spec["reducer"]](rctx, spec)
        if value is not None:
            out["metrics"][spec["name"]] = {"value": value,
                                            "unit": spec["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
