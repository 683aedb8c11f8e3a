#!/usr/bin/env python
"""Sharding-planner CLI (ISSUE 11): rank 5D parallel configs for a mesh.

Enumerates legal ``(dp, fsdp, tp, pp, sep)`` configs over the declared
mesh (``fsdp`` = ZeRO-3 as GSPMD specs, ISSUE 18),
prunes HBM-infeasible ones, prices each survivor by compiling and
attributing its real train-step graph (``paddle_tpu.distributed.
auto_parallel.planner``), and prints the ranked table — predicted step
time, predicted MFU, HBM high-water, comm seconds — with the winner's
GSPMD plan. Exits nonzero (2) on an infeasible mesh: more devices than
exist, or no legal config survives.

Usage::

    python tools/plan.py --mesh 4x2 --model llama-micro --top 5
    python tools/plan.py --mesh 2x2 --model llama-micro --json
    python tools/plan.py --mesh 4x2 --validate          # measure + rank
    python tools/plan.py --mesh 4x2 --out plan.json     # plan artifact
    python tools/plan.py --mesh 4x2 --config dp2_tp2    # price one
    python tools/plan.py --mesh 4x2 --config dp2_fsdp2_tp2  # ZeRO-3
    python tools/plan.py --mesh 2x2 --virtual-devices 8 # laptop smoke

``--validate`` additionally EXECUTES every ranked config (interleaved
min-of-rounds) and reports predicted-vs-measured rank agreement + the
top1-in-measured-top2 verdict — the acceptance bar rides this mode. ``main(argv)`` is importable and returns
the exit code (the tier-1 smoke test drives it in-process).
"""

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

MODELS = ("llama-micro", "llama-tiny", "moe-micro")


def _model_cfg(name: str):
    from paddle_tpu.models import LlamaConfig
    if name == "llama-micro":
        # the canonical-graph micro size (analysis/graphs.py): cheap to
        # compile per config, census signatures unambiguous
        return LlamaConfig(vocab_size=320, hidden_size=64,
                           intermediate_size=96, num_hidden_layers=2,
                           num_attention_heads=4, num_key_value_heads=2,
                           max_position_embeddings=128)
    if name == "llama-tiny":
        return LlamaConfig.tiny()
    if name == "moe-micro":
        # the MoE canonical-graph size: unlocks the ep axis (ISSUE 20)
        # in enumeration and accepts epN --config segments
        from paddle_tpu.models.moe_lm import MoEConfig
        return MoEConfig(vocab_size=320, hidden_size=64,
                         intermediate_size=96, moe_intermediate_size=48,
                         num_hidden_layers=2, num_attention_heads=4,
                         num_key_value_heads=2, num_experts=4,
                         num_experts_per_tok=2, num_shared_experts=1,
                         first_k_dense_replace=1, capacity_factor=None,
                         max_position_embeddings=128)
    raise SystemExit(f"plan: unknown --model {name!r}; known: "
                     f"{', '.join(MODELS)}")


def _parse_mesh(text: str) -> int:
    """'4x2' → 8 devices (the declared physical grid; the planner
    searches logical factorizations of its size)."""
    try:
        dims = [int(t) for t in text.lower().replace("*", "x").split("x")]
        n = 1
        for d in dims:
            if d < 1:
                raise ValueError
            n *= d
        return n
    except ValueError:
        raise SystemExit(f"plan: bad --mesh {text!r} (want e.g. 4x2)")


def main(argv=None) -> int:
    ap_ = argparse.ArgumentParser(
        prog="plan", description=__doc__.split("\n")[0])
    ap_.add_argument("--mesh", default=None,
                     help="declared device grid, e.g. 4x2 (product = "
                          "device count)")
    ap_.add_argument("--devices", type=int, default=None,
                     help="device count (alternative to --mesh)")
    ap_.add_argument("--model", default="llama-micro",
                     help=f"model preset: {', '.join(MODELS)}")
    ap_.add_argument("--batch", type=int, default=8,
                     help="global batch the plan targets")
    ap_.add_argument("--seq", type=int, default=64,
                     help="sequence length the plan targets")
    ap_.add_argument("--top", type=int, default=5,
                     help="rows of the ranked table to print")
    ap_.add_argument("--config", default=None,
                     help="price ONE config (e.g. dp2_tp2 or "
                          "dp2_fsdp2_tp2) instead of enumerating")
    ap_.add_argument("--drift", default="warn",
                     choices=("warn", "refuse", "ignore"),
                     help="what to do when the cost-model drift gauge "
                          "is out of band")
    ap_.add_argument("--hbm-budget-gb", type=float, default=None,
                     help="override the per-chip HBM budget (GiB)")
    ap_.add_argument("--validate", action="store_true",
                     help="execute every ranked config and report "
                          "predicted-vs-measured rank agreement")
    ap_.add_argument("--json", action="store_true",
                     help="emit the full report as JSON on stdout")
    ap_.add_argument("--out", default=None,
                     help="persist the plan artifact (ranked table + "
                          "chosen GSPMD plan) to this path")
    ap_.add_argument("--virtual-devices", type=int, default=None,
                     help="force N virtual CPU devices (set BEFORE jax "
                          "initializes; laptop/CI smoke)")
    args = ap_.parse_args(argv)

    if args.virtual_devices:
        if "jax" in sys.modules:
            import jax
            if jax.device_count() < args.virtual_devices:
                print("plan: --virtual-devices must be set before jax "
                      "initializes", file=sys.stderr)
                return 2
        else:
            os.environ["JAX_PLATFORMS"] = "cpu"
            flags = os.environ.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (
                    flags + f" --xla_force_host_platform_device_count="
                            f"{args.virtual_devices}").strip()

    import jax
    from paddle_tpu.distributed import auto_parallel as ap_mod

    if args.mesh:
        n = _parse_mesh(args.mesh)
    elif args.devices:
        n = args.devices
    else:
        n = jax.device_count()

    cfgs = None
    if args.config:
        cfgs = [ap_mod.ParallelConfig.parse(args.config)]
    budget = (args.hbm_budget_gb * 2 ** 30
              if args.hbm_budget_gb is not None else None)
    try:
        report = ap_mod.plan(
            _model_cfg(args.model), n_devices=n,
            mesh_shape=args.mesh or str(n),
            global_batch=args.batch, seq_len=args.seq, configs=cfgs,
            drift=args.drift, hbm_budget_bytes=budget,
            keep_builds=args.validate, model_name=args.model)
    except (ap_mod.InfeasibleMeshError,
            ap_mod.StaleCostModelError) as e:
        print(f"plan: {type(e).__name__}: {e}", file=sys.stderr)
        return 2

    if args.validate:
        report.validation = ap_mod.validate_rank_order(report)

    if args.out:
        report.save(args.out)
    if args.json:
        print(json.dumps(report.as_dict(), sort_keys=True,
                         default=float))
    else:
        print(f"plan: {n} devices ({report.device['kind']}), model "
              f"{args.model}, batch {args.batch} x seq {args.seq}")
        print(report.table(top=args.top))
        chosen = report.chosen
        print(f"\nchosen: {chosen.config}  predicted "
              f"{chosen.predicted_step_s * 1e3:.3f} ms/step, MFU "
              f"{chosen.predicted_mfu:.4f}")
        if report.notes:
            for nrow in report.notes:
                print(f"note: {nrow}")
        if report.validation:
            v = report.validation
            print(f"validate: agreement={v['agreement']:.3f} "
                  f"top1_in_measured_top2="
                  f"{bool(v['top1_is_measured_top2'])} "
                  f"(predicted {v.get('predicted_best')}, measured "
                  f"{v.get('measured_best')})")
        if args.out:
            print(f"plan artifact -> {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
