"""Checkpoint resharding: load under a DIFFERENT ShardingPlan (ISSUE 15).

Reference analogue: python/paddle/distributed/checkpoint/load_state_dict.py
reshards flat-param shard files when the load-time parallel topology differs
from save-time. Here the storage engine (orbax/tensorstore) already knows how
to serve arbitrary byte ranges, so resharding collapses into two concerns this
module owns:

* **provenance** — the ``ShardingPlan`` active at save time rides inside the
  committed step dir as ``_PLAN.json`` (hashed into the manifest like every
  other file), so a loader on a different mesh never guesses the source
  layout;
* **feasibility + placement** — before touching bytes, every parameter's
  sharded dims are checked against the TARGET plan's axis sizes (a tp-shrink
  that leaves uneven attention-head remainders is rejected with an error
  naming the axis, not a cryptic GSPMD crash three layers down), then the
  tree is restored with the target plan's PartitionSpecs: per-shard lazily
  through orbax (each device reads exactly its new shard's byte ranges —
  peak host memory stays bounded by one shard), falling back to host-side
  assembly + ``jax.device_put`` when the lazy path is unavailable.

The elastic-resume flow (distributed/elastic.py) calls this through
``CheckpointManager.restore`` whenever the saved plan's axes differ from the
live one; ``tools/reshard.py`` exposes the same machinery offline.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

from ..observability.metrics import REGISTRY as _REG

__all__ = ["PLAN_NAME", "ReshardError", "write_plan", "read_plan",
           "effective_axes", "plans_equivalent", "saved_tree",
           "check_feasible", "load_resharded", "place_tree"]

PLAN_NAME = "_PLAN.json"
_PLAN_SCHEMA = "pt-ckpt-plan-v1"


class ReshardError(RuntimeError):
    """The target plan cannot legally host this checkpoint (permanent:
    retrying or falling back to an older step cannot fix an indivisible
    axis — the caller must pick a different mesh)."""


# -- plan sidecar -------------------------------------------------------------

def write_plan(step_dir: str, plan, step: int) -> str:
    """Record the active plan (or the implicit single-device plan, as
    ``null``) inside the step dir. Called by CheckpointManager before the
    manifest is built, so the file is hashed like every other payload."""
    payload = {
        "schema": _PLAN_SCHEMA,
        "step": int(step),
        "implicit_single_device": plan is None,
        "plan": plan.as_dict() if plan is not None else None,
    }
    path = os.path.join(step_dir, PLAN_NAME)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(json.dumps(payload, sort_keys=True).encode())
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return path


def read_plan(step_dir: str):
    """The ShardingPlan recorded at save time, or None (implicit
    single-device plan, a pre-plan checkpoint, or no sidecar at all)."""
    path = os.path.join(step_dir, PLAN_NAME)
    if not os.path.isfile(path):
        return None
    with open(path, "rb") as f:
        payload = json.loads(f.read())
    raw = payload.get("plan")
    if raw is None:
        return None
    from ..distributed.auto_parallel.emit import ShardingPlan
    return ShardingPlan.from_dict(raw)


def effective_axes(plan) -> Dict[str, int]:
    """Mesh axes that actually partition anything (size > 1). Two plans
    with the same effective axes hold identical shard layouts even if one
    carries extra size-1 axes."""
    if plan is None:
        return {}
    return {k: int(v) for k, v in plan.axes.items() if int(v) > 1}


def plans_equivalent(a, b) -> bool:
    """True when a checkpoint written under ``a`` loads under ``b`` without
    resharding (same effective axis sizes)."""
    return effective_axes(a) == effective_axes(b)


# -- feasibility --------------------------------------------------------------

def saved_tree(step_dir: str) -> Dict[str, Any]:
    """The checkpoint's own tree, each leaf the array's metadata (``shape``,
    ``dtype``) and no payload read: what ``check_feasible`` walks offline.
    orbax hands the tree over inside ``StepMetadata.item_metadata``; the
    wrapper itself is ONE leaf, over which the walk finds nothing."""
    import orbax.checkpoint as ocp
    return ocp.StandardCheckpointer().metadata(step_dir).item_metadata.tree


def _iter_spec_leaves(tree: Dict[str, Any], param_specs: Dict[str, Any]
                      ) -> Iterator[Tuple[str, Any, Tuple[int, ...]]]:
    """Yield (matched name, spec, shape) for every leaf the plan's spec
    table covers — matching full "/"-path, final key, then any path
    component (innermost wins), the same resolution order the restore
    target uses, so feasibility is checked for exactly the leaves that
    will be resharded (params AND their optimizer slots)."""
    import numpy as np
    from jax.tree_util import tree_flatten_with_path
    leaves, _ = tree_flatten_with_path(tree)
    for path, x in leaves:
        keys = [str(getattr(p, "key", p)) for p in path]
        full = "/".join(keys)
        name, spec = None, None
        if full in param_specs:
            name, spec = full, param_specs[full]
        elif keys and keys[-1] in param_specs:
            name, spec = keys[-1], param_specs[keys[-1]]
        else:
            for k in reversed(keys[:-1]):
                if k in param_specs:
                    name, spec = k, param_specs[k]
                    break
        if spec is None:
            continue
        shape = tuple(x.shape) if hasattr(x, "shape") else tuple(
            np.shape(x))
        yield name, spec, shape


def _axis_factor(entry, axes: Dict[str, int]) -> Tuple[int, List[str]]:
    names = list(entry) if isinstance(entry, (tuple, list)) else [entry]
    factor, used = 1, []
    for a in names:
        if a is None:
            continue
        factor *= int(axes.get(a, 1))
        used.append(str(a))
    return factor, used


def check_feasible(like_tree: Dict[str, Any], plan) -> None:
    """Raise ReshardError if any parameter dim the target plan shards is
    not divisible by the product of the mesh axes on that dim."""
    if plan is None:
        return
    axes = {k: int(v) for k, v in plan.axes.items()}
    for name, spec, shape in _iter_spec_leaves(like_tree, plan.param_specs):
        entries = tuple(spec)
        if len(entries) > len(shape):
            continue                      # restore replicates these anyway
        for d, entry in enumerate(entries):
            if entry is None:
                continue
            factor, used = _axis_factor(entry, axes)
            if factor > 1 and shape[d] % factor != 0:
                ax = "+".join(used)
                raise ReshardError(
                    f"target plan {plan.config_str!r} cannot shard "
                    f"'{name}': dim {d} of shape {tuple(shape)} has size "
                    f"{shape[d]}, not divisible by axis {ax}={factor} "
                    f"(remainder {shape[d] % factor}) — e.g. a tp shrink "
                    f"that does not divide the attention heads leaves "
                    f"uneven head remainders; pick an axis size that "
                    f"divides {shape[d]}")


# -- load ---------------------------------------------------------------------

def place_tree(tree: Dict[str, Any], plan, mesh) -> Dict[str, Any]:
    """Host-side assembly path: place an already-loaded (host or
    replicated) tree onto ``mesh`` per the plan's spec table via
    ``jax.device_put`` — unmatched leaves replicate."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from jax.tree_util import tree_map_with_path
    m = getattr(mesh, "mesh", mesh)
    specs = plan.param_specs if plan is not None else {}

    def one(path, x):
        keys = [str(getattr(p, "key", p)) for p in path]
        full = "/".join(keys)
        spec = specs.get(full)
        if spec is None and keys:
            spec = specs.get(keys[-1])
        if spec is None:
            for k in reversed(keys[:-1]):
                if k in specs:
                    spec = specs[k]
                    break
        if spec is None:
            spec = PartitionSpec()
        shape = tuple(getattr(x, "shape", ()) or ())
        if len(tuple(spec)) > len(shape):
            spec = PartitionSpec()
        return jax.device_put(x, NamedSharding(m, spec))

    return tree_map_with_path(one, tree)


def load_resharded(step_dir: str, like_tree: Dict[str, Any], target_plan,
                   *, mesh=None, devices=None,
                   source_plan=None) -> Dict[str, Any]:
    """Load the checkpoint at ``step_dir`` (written under ``source_plan``,
    read from its ``_PLAN.json`` when not given) placed per
    ``target_plan`` on ``mesh``. Feasibility is validated up front; the
    restore itself goes per-shard through orbax (bounded peak memory),
    with host-side assembly + device_put as the fallback path."""
    from .. import checkpoint as _ckpt
    t0 = time.perf_counter()
    if source_plan is None:
        source_plan = read_plan(step_dir)
    hm = mesh
    if hm is None:
        hm = target_plan.build_mesh(devices)
    m = getattr(hm, "mesh", hm)
    try:
        check_feasible(like_tree, target_plan)
        spec_tree = dict(target_plan.param_specs)
        try:
            tree = _ckpt.load_state_dict(step_dir, like_tree, mesh=m,
                                         spec_tree=spec_tree)
        except ReshardError:
            raise
        except Exception:
            # lazy per-shard path failed (e.g. incompatible on-disk
            # layout metadata): assemble host-side, then re-place
            raw = _ckpt.load_state_dict(step_dir, like_tree)
            tree = place_tree(raw, target_plan, m)
    except Exception as e:
        if _REG.enabled:
            _REG.counter("pt_elastic_reshard_failures_total",
                         "resharded restores that failed").inc(
                error=type(e).__name__)
        raise
    if _REG.enabled:
        src = source_plan.config_str if source_plan is not None else "none"
        _REG.counter("pt_elastic_reshards_total",
                     "cross-plan checkpoint restores").inc(
            source=src, target=target_plan.config_str)
        _REG.histogram("pt_elastic_reshard_seconds",
                       "resharded restore duration", "s").observe(
            time.perf_counter() - t0)
    return tree
