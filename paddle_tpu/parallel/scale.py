"""Scale-fit analysis: does a (model, mesh, batch) configuration fit HBM?

Reference analogue: the auto-tuner's memory pruner
(python/paddle/distributed/auto_tuner/prune.py prune_by_memory_estimation)
— but computed from the ACTUAL abstract parameter tree (shapes + sharding
annotations) rather than a closed-form heuristic, so it can be asserted
against per-parameter NamedShardings. Built on jax.sharding.AbstractMesh:
no devices, no weights (construct the model under paddle_tpu.LazyGuard).

HBM sizes: v5e 16 GB, v5p 95 GB, v4 32 GB (public TPU specs).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import AbstractMesh, NamedSharding, PartitionSpec

HBM_GB = {"v5e": 16.0, "v5p": 95.0, "v4": 32.0, "v6e": 32.0}

_OPT_BYTES_PER_PARAM = 12  # AdamW fp32 master + m + v


def abstract_mesh(axes: Dict[str, int]) -> AbstractMesh:
    """AbstractMesh from {'pp': 4, 'fsdp': 2, 'tp': 8} — no devices needed."""
    names = tuple(axes.keys())
    return AbstractMesh(tuple(int(axes[n]) for n in names), names)


def clean_spec(sharding: Optional[Tuple], axes: Dict[str, int]) -> PartitionSpec:
    """Drop axes not present (or size-1) in the mesh — delegates to the ONE
    implementation in parallel.api (AbstractMesh satisfies its interface)."""
    from .api import _clean_spec
    if sharding is None:
        return PartitionSpec()
    return _clean_spec(sharding, abstract_mesh(axes))


def param_plan(model, axes: Dict[str, int]):
    """Yields (name, param, spec, local_shape) for every parameter, where
    local_shape is the per-device shard under the cleaned spec."""
    mesh = abstract_mesh(axes)
    for name, p in model.named_parameters():
        spec = clean_spec(p.sharding, axes)
        sh = NamedSharding(mesh, spec)
        local = sh.shard_shape(tuple(p.value.shape))
        yield name, p, spec, local


def train_state_bytes(model, axes: Dict[str, int], *, seq_len: int,
                      microbatch_size: int, recompute: str = "full",
                      vocab_size: Optional[int] = None,
                      hidden_size: Optional[int] = None,
                      num_layers: Optional[int] = None) -> Dict[str, float]:
    """Per-device training-state memory (bytes) for the model on a mesh.

    params/grads use each parameter's own dtype; optimizer state is fp32
    master + two moments (12 B/param, reference AMP-O2 master-weight
    profile); activations follow the Megatron per-layer formula scaled by
    microbatch, tp and sequence sharding, with ``recompute`` choosing how
    many layers stay live (full = 1 live layer + boundary saves,
    none = all local layers).
    """
    cfg = getattr(model, "cfg", None)
    vocab = vocab_size or getattr(cfg, "vocab_size", 0)
    h = hidden_size or getattr(cfg, "hidden_size", 0)
    layers = num_layers or getattr(cfg, "num_hidden_layers", 0)

    p_bytes = g_bytes = o_bytes = 0.0
    n_params = 0
    for name, p, spec, local in param_plan(model, axes):
        n_local = int(np.prod(local)) if local else 1
        n_total = int(np.prod(p.value.shape)) if p.value.shape else 1
        n_params += n_total
        itemsize = np.dtype(p.value.dtype).itemsize
        p_bytes += n_local * itemsize
        g_bytes += n_local * itemsize
        o_bytes += n_local * _OPT_BYTES_PER_PARAM

    tp = axes.get("tp", 1)
    sp = axes.get("sep", 1)
    pp = axes.get("pp", 1)
    b, s = microbatch_size, seq_len
    layers_local = max(layers / pp, 1)
    # Megatron activation-memory formula, bf16 profile: ~34*s*b*h bytes per
    # layer for one microbatch; tensor and sequence parallel both divide it.
    act_layer = s * b * h * 34 / (tp * sp)
    # pipeline keeps up to R = min(M, 2*pp-1) microbatch stage-inputs live
    # per stage (the 1F1B ring in parallel/schedules.py); pp=1 holds 1.
    micro = getattr(model, "num_microbatches", 1) or 1
    in_flight = min(micro, 2 * pp - 1) if pp > 1 else 1
    boundary = s * b * h * 2 / sp            # one bf16 stage/layer input
    if recompute == "full":
        # 1 live layer + per-layer remat boundaries for the microbatch in
        # backward + the pipeline ring of stage inputs
        act = act_layer + layers_local * boundary + in_flight * boundary
    elif recompute == "selective":
        act = act_layer * max(layers_local / 4, 1) + in_flight * boundary
    else:
        # no recompute: the pipeline ring holds FULL residuals for every
        # in-flight microbatch (schedules.py remat=False residual ring)
        act = act_layer * layers_local * in_flight
    # logits buffer (fp32 CE) on the last stage
    act += s * b * (vocab / tp) * 4

    total = p_bytes + g_bytes + o_bytes + act
    return {"params": p_bytes, "grads": g_bytes, "optimizer": o_bytes,
            "activations": act, "total": total, "n_params": n_params,
            "total_gb": total / 1e9}


def fits(model, axes: Dict[str, int], *, seq_len: int, microbatch_size: int,
         device: str = "v5p", recompute: str = "full",
         headroom: float = 0.85) -> Tuple[bool, Dict[str, float]]:
    """(fits, breakdown): per-device state must stay under
    headroom * HBM."""
    br = train_state_bytes(model, axes, seq_len=seq_len,
                           microbatch_size=microbatch_size,
                           recompute=recompute)
    budget = HBM_GB[device] * 1e9 * headroom
    br["budget_gb"] = budget / 1e9
    br["device"] = device
    return br["total"] <= budget, br


__all__ = ["abstract_mesh", "clean_spec", "param_plan", "train_state_bytes",
           "fits", "HBM_GB"]
