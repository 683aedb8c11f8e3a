#!/usr/bin/env python
"""Op-cost calibration probe (ISSUE 9): measured latencies for the cost
observatory's OpCostDB.

Times the canonical-registry graphs (``paddle_tpu.analysis.graphs`` — the
REAL compiled train/serving entrypoints at micro sizes) and their dominant
dot shapes, interleaved min-of-rounds per the bench-variance policy (this
host's absolute numbers are noisy; mins over interleaved rounds and the
ratios built from them are the signal), and persists the results into the
:class:`OpCostDB` next to the kernel TuneDB, keyed by op signature +
device kind — so calibration survives restarts and the sharding planner
(ROADMAP item 3) reads measured latencies instead of guesses.

Each record carries BOTH sides of the observatory: the measured seconds
and the analytical flop/byte attribution of the same graph
(``observability/costs`` analyzer — the one flop definition), so a
consumer can derive measured MFU, roofline headroom, and
predicted-over-measured drift from the DB alone.

Usage::

    JAX_PLATFORMS=cpu python tools/op_cost_probe.py --calibrate
    python tools/op_cost_probe.py --calibrate --graphs fused_ce,train_step_k1
    python tools/op_cost_probe.py --calibrate --db /tmp/op_cost_db.json

Prints one JSON summary line. ``calibrate()`` / ``measure_graphs()`` are
importable — tools/obs_smoke.py's cost leg and the cost observatory's
tests drive them in-process.
"""

import argparse
import json
import os
import sys
import time

# NO platform forcing here (unlike graph_lint, a CPU CI gate): this tool
# exists to calibrate the accelerator the process actually has — forcing
# cpu would silently record laptop latencies under `...|cpu|...` keys on
# a TPU host. Force CPU explicitly when that's what you want:
# `JAX_PLATFORMS=cpu python tools/op_cost_probe.py --calibrate`.

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

#: graphs cheap enough for CI legs (obs_smoke) — the full registry is the
#: default for an explicit calibration run
CI_GRAPHS = ("fused_ce", "train_step_k1")

_DTYPES = {"f32": "float32", "bf16": "bfloat16", "f16": "float16",
           "f64": "float64"}


def _copy_args(args):
    """Fresh device copies of a graph's example args — donated buffers
    are consumed per call, so every timed call gets its own set."""
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(jnp.copy, args)


def _block(out):
    import jax
    for leaf in jax.tree_util.tree_leaves(out):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()
            break
    else:
        return
    # block on the LAST leaf too (pytrees may finish out of order)
    leaves = [l for l in jax.tree_util.tree_leaves(out)
              if hasattr(l, "block_until_ready")]
    if leaves:
        leaves[-1].block_until_ready()


def measure_graphs(names=None, rounds: int = 3, iters: int = 4,
                   verbose: bool = False, warmup: int = 1):
    """Build + analyze + time canonical graphs.

    Returns ``{name: {"t_s", "flops", "bytes", "comm_bytes",
    "predicted_s", "mfu_measured", "device_kind"}}``; graphs the
    environment can't host (``GraphSkipped``) are reported under
    ``"_skipped"``. Timing: ``warmup`` untimed executions per graph
    first (the first run of a freshly compiled donated-buffer program
    can re-specialize layouts — keep it off the clock), then per round
    each graph runs ``iters`` back-to-back calls on fresh arg copies
    (amortizes dispatch), rounds interleave across graphs so every leg
    sees the same host contention, a gc fence precedes each timed
    window (a collection pause inside a short window skews small
    graphs disproportionately), and the MIN round wins (discards
    spikes)."""
    import gc
    import paddle_tpu.analysis as A
    from paddle_tpu.analysis.hlo import parse_hlo
    from paddle_tpu.observability import costs

    names = list(names or A.graph_names())
    spec = costs.device_spec()
    built, skipped = {}, []
    for name in names:
        try:
            g = A.build_graph(name)
        except A.GraphSkipped:
            skipped.append(name)
            continue
        if g.example_args is None:
            skipped.append(name)
            continue
        rep = costs.attribute_costs(parse_hlo(g.compiled.as_text()),
                                    spec=spec)
        built[name] = (g, rep)
        if verbose:
            print(f"op_cost_probe: built {name} "
                  f"({rep.total_flops:.3g} flops)", file=sys.stderr)

    # per-graph dispatch floor: a NULL executable lowered on the SAME
    # argument pytree (XLA DCEs the body) pays the same per-call host
    # cost — flatten, aval checks, enqueue — with ~zero device work.
    # Subtracting it (`t_s - dispatch_floor_s`) yields the pure graph
    # time the roofline prediction models; the floor is reported
    # separately so consumers choose which convention they need.
    import jax
    import jax.numpy as jnp
    nulls = {}
    for name, (g, _rep) in built.items():
        try:
            nulls[name] = jax.jit(
                lambda *a: jnp.int32(0)).lower(*g.example_args).compile()
        except Exception:
            nulls[name] = None

    for name, (g, _rep) in built.items():
        for _ in range(max(0, warmup)):
            _block(g.compiled(*_copy_args(g.example_args)))
        if nulls[name] is not None:
            _block(nulls[name](*_copy_args(g.example_args)))

    best = {name: float("inf") for name in built}
    floor = {name: float("inf") for name in built}
    for _ in range(max(1, rounds)):
        for name, (g, _rep) in built.items():      # interleaved legs
            arg_sets = [_copy_args(g.example_args)
                        for _ in range(max(1, iters))]
            gc.collect()
            out = None
            t0 = time.perf_counter()
            for a in arg_sets:
                out = g.compiled(*a)
            _block(out)
            dt = (time.perf_counter() - t0) / max(1, iters)
            best[name] = min(best[name], dt)
            if nulls[name] is None:
                floor[name] = 0.0
                continue
            arg_sets = [_copy_args(g.example_args)
                        for _ in range(max(1, iters))]
            out = None
            t0 = time.perf_counter()
            for a in arg_sets:
                out = nulls[name](*a)
            _block(out)
            floor[name] = min(floor[name],
                              (time.perf_counter() - t0) / max(1, iters))

    out = {}
    for name, (g, rep) in built.items():
        t = best[name]
        out[name] = {
            "t_s": t,
            "dispatch_floor_s": min(floor[name], t),
            "flops": rep.total_flops,
            "bytes": rep.total_bytes,
            "comm_bytes": rep.total_comm_bytes,
            "predicted_s": rep.predicted_step_s,
            "mfu_measured": (rep.total_flops / (t * spec.peak_flops)
                             if t > 0 else 0.0),
            "device_kind": spec.kind,
        }
    if skipped:
        out["_skipped"] = skipped
    # the full CostReports ride along for in-process consumers
    # (calibrate's dominant-dot sweep) — not JSON, callers pop it
    out["_reports"] = {name: rep for name, (g, rep) in built.items()}
    return out


def _time_dot(m, k, n, dtype: str, rounds: int, iters: int) -> float:
    import jax
    import jax.numpy as jnp
    dt = getattr(jnp, _DTYPES.get(dtype, "float32"))
    a = jnp.zeros((m, k), dt)
    b = jnp.zeros((k, n), dt)
    f = jax.jit(lambda a, b: a @ b)
    _block(f(a, b))                                # compile off the clock
    best = float("inf")
    for _ in range(max(1, rounds)):
        out = None
        t0 = time.perf_counter()
        for _ in range(max(1, iters)):
            out = f(a, b)
        _block(out)
        best = min(best, (time.perf_counter() - t0) / max(1, iters))
    return best


def calibrate(graphs=None, rounds: int = 3, iters: int = 4,
              db_path=None, top_dots: int = 3, save: bool = True,
              verbose: bool = False):
    """Measure graphs + their dominant dot shapes and persist the
    OpCostDB. Returns the summary (including the db path and the recorded
    keys, so callers can assert reload hits)."""
    from paddle_tpu.observability import costs

    db = costs.OpCostDB(db_path) if db_path \
        else costs.get_op_cost_db()
    spec = costs.device_spec()
    measured = measure_graphs(graphs, rounds=rounds, iters=iters,
                              verbose=verbose)
    reports = measured.pop("_reports", {})
    recorded = []
    now = time.strftime("%Y-%m-%dT%H:%M:%S")
    dot_shapes = {}
    for name, rec in measured.items():
        if name == "_skipped":
            continue
        key = costs.OpCostDB.graph_key(name, spec.kind)
        db.record(key, {**{k: v for k, v in rec.items()
                           if k != "device_kind"},
                        "captured_at": now, "rounds": rounds,
                        "iters": iters})
        recorded.append(key)
        rep = reports.get(name)
        if rep is not None:
            for d in costs.dominant_dots(rep, top=top_dots):
                dot_shapes[(d["m"], d["k"], d["n"], d["dtype"])] = d

    for (m, k, n, dtype), d in sorted(dot_shapes.items(),
                                      key=lambda kv: -kv[1]["flops"]):
        if dtype not in _DTYPES:
            continue
        try:
            t = _time_dot(m, k, n, dtype, rounds, iters)
        except Exception:
            continue
        key = costs.OpCostDB.dot_key(m, k, n, dtype, spec.kind)
        db.record(key, {"t_s": t, "flops": 2.0 * m * k * n,
                        "captured_at": now})
        recorded.append(key)

    if save:
        db.save()
    return {"db_path": db.path, "recorded": recorded,
            "graphs": measured, "device_kind": spec.kind}


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--calibrate", action="store_true",
                    help="measure + persist the OpCostDB (without it the "
                         "probe only measures and prints)")
    ap.add_argument("--graphs", default=None,
                    help="comma-separated canonical graph subset")
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--iters", type=int, default=4)
    ap.add_argument("--db", default=None,
                    help="OpCostDB path (default: op_cost_db.json "
                         "beside the shipped tune DB)")
    args = ap.parse_args(argv)
    graphs = ([g.strip() for g in args.graphs.split(",") if g.strip()]
              if args.graphs else None)
    if args.calibrate:
        out = calibrate(graphs, rounds=args.rounds, iters=args.iters,
                        db_path=args.db, verbose=True)
    else:
        measured = measure_graphs(graphs, rounds=args.rounds,
                                  iters=args.iters, verbose=True)
        measured.pop("_reports", None)
        out = {"graphs": measured}
    return out


if __name__ == "__main__":
    print(json.dumps(main(), default=float))
