#!/usr/bin/env python
"""Checkpoint reshard CLI (ISSUE 15): re-lay a committed checkpoint for a
different mesh, offline.

Reads the source step's recorded ``_PLAN.json`` (so the source layout is
never guessed), derives the target plan from the same spec table with the
new axis sizes, validates feasibility (every sharded dim must divide by
the product of its mesh axes — checked against orbax metadata, no payload
read), and either reports (``--dry-run``) or writes a fully-committed
resharded checkpoint under ``--out`` via CheckpointManager (manifest +
``_COMMITTED`` + the new ``_PLAN.json``).

Usage::

    python tools/reshard.py --from ckpts/ --mesh 2x2 --out ckpts_2x2/
    python tools/reshard.py --from ckpts/step_400 --config dp2_tp2 --dry-run
    python tools/reshard.py --from ckpts/ --mesh 2x2 --dry-run \
        --virtual-devices 8                        # laptop smoke

Exit codes: 0 ok, 1 usage/source errors, 2 infeasible target (an axis
that does not divide a parameter dim, more devices than exist, or a
source with no recorded plan to derive the spec table from) — the same
nonzero-2 contract as ``tools/plan.py``. ``main(argv)`` is importable
and returns the exit code (the tier-1 smoke test drives it in-process).
"""

import argparse
import os
import re
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

_STEP_RE = re.compile(r"^step_(\d+)$")


def _parse_target_axes(mesh: str, config: str):
    """--mesh AxB (dp×tp) or --config dp2_tp2[_pp1_sep1] → axes dict."""
    from paddle_tpu.distributed.auto_parallel import ParallelConfig
    if config:
        cfg = ParallelConfig.parse(config)
    elif mesh:
        dims = [int(t) for t in mesh.lower().replace("*", "x").split("x")]
        if not dims or any(d < 1 for d in dims) or len(dims) > 2:
            raise SystemExit(f"reshard: bad --mesh {mesh!r} (want e.g. 2x2 "
                             f"= dp x tp)")
        cfg = ParallelConfig(dp=dims[0], tp=dims[1] if len(dims) > 1 else 1)
    else:
        raise SystemExit("reshard: need --mesh or --config")
    return cfg, {"dp": cfg.dp, "fsdp": 1, "tp": cfg.tp, "pp": cfg.pp,
                 "sep": cfg.sep}


def _resolve_step_dir(src: str, step):
    """--from accepts a checkpoint root or a step dir directly."""
    src = os.path.abspath(os.path.expanduser(src))
    m = _STEP_RE.match(os.path.basename(src))
    if m and os.path.isdir(src):
        return src, int(m.group(1))
    from paddle_tpu.checkpoint import latest_step
    s = int(step) if step is not None else latest_step(src)
    if s is None:
        return None, None
    return os.path.join(src, f"step_{s}"), s


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="reshard", description=__doc__.split("\n")[0])
    ap.add_argument("--from", dest="src", required=True,
                    help="checkpoint root (newest committed step) or a "
                         "step_N dir")
    ap.add_argument("--step", type=int, default=None,
                    help="pick a specific step under the root")
    ap.add_argument("--mesh", default=None,
                    help="target grid dp x tp, e.g. 2x2")
    ap.add_argument("--config", default=None,
                    help="target config, e.g. dp2_tp2 (full 4D form)")
    ap.add_argument("--out", default=None,
                    help="root to write the resharded checkpoint under "
                         "(required unless --dry-run)")
    ap.add_argument("--dry-run", action="store_true",
                    help="validate + report only; reads metadata, not "
                         "payload bytes")
    ap.add_argument("--virtual-devices", type=int, default=None,
                    help="force N virtual CPU devices (set before jax "
                         "import; smoke/testing)")
    args = ap.parse_args(argv)

    if args.virtual_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{args.virtual_devices}").strip()
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if not args.dry_run and not args.out:
        print("reshard: --out is required without --dry-run",
              file=sys.stderr)
        return 1

    import jax
    from paddle_tpu.distributed.auto_parallel import ShardingPlan
    from paddle_tpu.resilience import reshard as rs

    sdir, step = _resolve_step_dir(args.src, args.step)
    if sdir is None or not os.path.isdir(sdir):
        print(f"reshard: no committed checkpoint under {args.src!r}",
              file=sys.stderr)
        return 1
    saved = rs.read_plan(sdir)
    if saved is None:
        print(f"reshard: {sdir} has no recorded ShardingPlan "
              f"(_PLAN.json missing or single-device) — there is no "
              f"spec table to derive a target layout from; re-save "
              f"under a plan (Trainer.apply_plan + CheckpointManager) "
              f"or re-plan from the model", file=sys.stderr)
        return 2

    cfg, axes = _parse_target_axes(args.mesh, args.config)
    target = ShardingPlan(
        config_str=str(cfg), axes=axes, batch_spec=saved.batch_spec,
        param_specs=saved.param_specs,
        sequence_parallel=saved.sequence_parallel,
        notes=f"resharded offline from {saved.config_str} step_{step}")

    need = 1
    for v in axes.values():
        need *= v
    have = len(jax.devices())
    if need > have:
        print(f"reshard: target {cfg} needs {need} devices, only {have} "
              f"exist", file=sys.stderr)
        return 2

    md = rs.saved_tree(sdir)
    try:
        rs.check_feasible(md, target)
    except rs.ReshardError as e:
        print(f"reshard: infeasible: {e}", file=sys.stderr)
        return 2

    sharded = sum(1 for _n, spec, _s in rs._iter_spec_leaves(
        md, target.param_specs) if any(e is not None for e in tuple(spec)))
    print(f"reshard: {sdir} [{saved.config_str}] -> {cfg} "
          f"({need} devices, {sharded} sharded leaves): feasible")
    if args.dry_run:
        return 0

    # the same lazy per-shard path the elastic resume uses: the target
    # tree (shapes/dtypes from the checkpoint's own metadata) carries the
    # NEW shardings, so each device reads exactly its new shard's bytes
    like = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(tuple(x.shape), x.dtype), md)
    hm = target.build_mesh()
    placed = rs.load_resharded(sdir, like, target, mesh=hm,
                               source_plan=saved)
    from paddle_tpu.resilience import CheckpointManager
    mgr = CheckpointManager(args.out, plan=target)
    mgr.save(step, placed, force=True)
    print(f"reshard: committed {mgr.step_dir(step)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
