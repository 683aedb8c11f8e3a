"""Sharding planner: enumerate, price, and emit the fastest 5D config.

The reference stack's ``auto_parallel`` layer picks hybrid-parallel
placements for the user; this module is its TPU-native reproduction on
top of the pricing stack PRs 8–9 built:

1. **Enumerate** (:func:`enumerate_configs`) — every legal
   ``(dp, fsdp, tp, pp, sep)`` factorization of the declared device
   mesh, legality meaning model divisibility (heads/layers/sequence/
   batch/hidden per axis) rather than taste. ``fsdp`` is ZeRO-3 as
   GSPMD specs (ISSUE 18): params + AdamW slots + grads sharded over
   the axis, XLA inserting all-gather-on-use / reduce-scatter — no
   reducer machinery.
2. **Prune** — the closed-form per-chip HBM model
   (:mod:`memory_model`): params + optimizer slots + grads + activations
   under remat must fit BEFORE a config earns a compile.
3. **Price** (:func:`price_config`) — each survivor's candidate graph is
   actually compiled (the real ``Trainer`` step over the real sharded
   model on the real mesh) and attributed: per-op compute/HBM roofline
   from :func:`attribute_costs`, per-mesh-axis comm from the PR 8
   collective census priced by :func:`price_census`, measured dot
   latencies and the per-dispatch host floor from the :class:`OpCostDB`
   where calibration exists. There is deliberately no second "model of
   the model": the planner prices the HLO XLA will run.
4. **Emit** (:mod:`emit`) — the winner becomes a concrete GSPMD plan
   (``Mesh`` axis sizes + per-parameter ``PartitionSpec`` + batch spec)
   the trainer consumes directly; the full ranked table persists as a
   plan artifact (``PlanReport.save``).

The cost model watches itself: before trusting its tables, :func:`plan`
consults the ``pt_step_time_predicted_over_measured`` drift gauge
(PR 10) and the OpCostDB calibration age — ``drift="warn"`` annotates
the report, ``drift="refuse"`` raises :class:`StaleCostModelError`.

Prediction convention: serialized upper bound, like the analyzer —
``compute⊕hbm roofline + priced comm + per-collective launch floor +
dispatch floor``. Absolute seconds are only as good as the device
tables; the acceptance bar is therefore RANK ORDER against measured
step times (:func:`validate_rank_order` over the MULTICHIP dryrun
scenarios / ``tools/plan.py --validate``), not absolute error.
"""

from __future__ import annotations

import json
import math
import time
import warnings
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = [
    "ParallelConfig", "PricedGraph", "PricedConfig", "PlanReport",
    "StaleCostModelError", "InfeasibleMeshError", "enumerate_configs",
    "price_compiled", "price_config", "plan", "rank_agreement",
    "check_drift", "measure_compiled", "validate_rank_order",
    "ep_imbalance",
]

# per-collective launch floor (seconds): tiny-payload collectives are
# latency-bound, not bandwidth-bound, so bytes ÷ bw alone would call a
# 60-collective graph free. Kept SMALL by design — on the CPU tier the
# virtual-device emulation makes per-collective cost pure noise while the
# per-op compute/byte attribution tracks measured ordering (verified on
# the dp8/dp4tp2/pp2 candidate sweep), so the floor must stay below the
# compute signal; on TPU the ICI launch overhead is ~µs.
COLLECTIVE_FLOOR_S = {"cpu": 2e-6, "default": 1e-6}

#: OpCostDB graph records older than this are stale for drift purposes
CALIBRATION_MAX_AGE_S = 14 * 24 * 3600.0

#: acceptable band for the pt_step_time_predicted_over_measured gauge —
#: wide because the serialized roofline legitimately over/under-shoots
#: on overlap-heavy (TPU) or dispatch-heavy (CPU tier) programs; outside
#: it the cost tables themselves are suspect
DRIFT_BAND = (0.2, 5.0)


class StaleCostModelError(RuntimeError):
    """The drift gauge says the cost tables disagree with reality beyond
    the band — a plan ranked with them would be noise."""


class InfeasibleMeshError(RuntimeError):
    """No legal config fits the declared mesh (wrong device count, or
    every factorization failed the HBM model)."""


@dataclass(frozen=True)
class ParallelConfig:
    """One point in the 5D search space (axis vocabulary of
    ``parallel/mesh.py AXES_ORDER``; ``fsdp`` is ZeRO-3 expressed as
    GSPMD specs — params/slots/grads sharded over the axis, batch over
    ``dp×fsdp`` — ``ep`` (ISSUE 20) shards experts over a subgroup of
    the data ranks: it divides ``dp`` rather than multiplying the device
    count, so ``size`` is ep-invariant)."""
    dp: int = 1
    tp: int = 1
    pp: int = 1
    sep: int = 1
    fsdp: int = 1
    ep: int = 1

    @property
    def size(self) -> int:
        # ep carves a subgroup out of dp — it never adds devices
        return self.dp * self.fsdp * self.tp * self.pp * self.sep

    def axes(self) -> Dict[str, int]:
        return {"dp": self.dp, "fsdp": self.fsdp, "tp": self.tp,
                "pp": self.pp, "sep": self.sep, "ep": self.ep}

    def __str__(self) -> str:
        # the fsdp/ep segments appear only when the axis is real — plan
        # artifacts, graph-budget pins and elastic sidecars from before
        # the axes existed keep parsing AND printing byte-identically
        fs = f"fsdp{self.fsdp}_" if self.fsdp > 1 else ""
        e = f"ep{self.ep}_" if self.ep > 1 else ""
        return f"dp{self.dp}_{fs}{e}tp{self.tp}_pp{self.pp}_sep{self.sep}"

    @staticmethod
    def parse(s: str) -> "ParallelConfig":
        """Inverse of ``str()`` (also accepts ``dp2xtp2`` / ``dp=2,tp=2``
        forms so the CLI stays forgiving)."""
        import re
        out = {"dp": 1, "tp": 1, "pp": 1, "sep": 1, "fsdp": 1, "ep": 1}
        # the lookbehind keeps the 'dp' inside 'fsdp4' (and the 'ep'
        # inside 'sep2') from matching as a degree of the shorter name
        for m in re.finditer(
                r"(?<![a-z])(fsdp|dp|tp|pp|sep|ep)\s*=?\s*(\d+)",
                s.lower()):
            out[m.group(1)] = int(m.group(2))
        return ParallelConfig(**out)


def _divisors(n: int) -> List[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def enumerate_configs(n_devices: int, model_cfg=None, *,
                      global_batch: int = 8, seq_len: int = 32,
                      max_pp: Optional[int] = None,
                      include_sep: bool = True,
                      include_pp: bool = True,
                      include_fsdp: bool = True,
                      include_ep: bool = True) -> List[ParallelConfig]:
    """Every legal ``(dp, fsdp, tp, pp, sep[, ep])`` with
    ``dp*fsdp*tp*pp*sep == n_devices``. Legality against ``model_cfg``
    (a LlamaConfig shape):

    * ``tp`` divides attention heads, KV heads, intermediate and vocab
      (column/row-parallel projections + vocab-parallel CE);
    * ``fsdp`` divides the hidden size (every projection/embedding is
      annotated with the axis on its H dimension) and, jointly with
      ``dp``, the global batch (batch spec is ``("dp","fsdp")``);
    * ``pp`` divides the layer count (stage stacking), and the
      per-data-rank batch must hold ≥2 microbatches;
    * ``sep`` divides the sequence (ring/GSPMD seq sharding) and the
      KV-head count (the ring exchanges head-sharded KV blocks);
    * ``dp`` divides the global batch;
    * ``ep`` (enumerated only for MoE models — ``model_cfg`` exposes
      ``num_experts``) divides ``dp`` (the expert subgroup is carved out
      of the data ranks, never extra devices) and the expert count, and
      composes with neither ``pp`` nor ``sep`` yet (stated exclusions,
      like pp×sep).

    Without a ``model_cfg`` only the factorization + batch constraints
    apply (the CLI's ``--no-model`` exploration mode); ep stays 1 there
    because its legality is inherently a model property.
    """
    out: List[ParallelConfig] = []
    for dp in _divisors(n_devices):
        if global_batch % dp:
            continue
        rest0 = n_devices // dp
        for fsdp in _divisors(rest0):
            if fsdp > 1 and not include_fsdp:
                continue
            if global_batch % (dp * fsdp):
                continue
            rest1 = rest0 // fsdp
            for tp in _divisors(rest1):
                rest2 = rest1 // tp
                for pp in _divisors(rest2):
                    if not include_pp and pp > 1:
                        continue
                    if max_pp is not None and pp > max_pp:
                        continue
                    sep = rest2 // pp
                    if sep > 1 and not include_sep:
                        continue
                    cfg = ParallelConfig(dp=dp, fsdp=fsdp, tp=tp, pp=pp,
                                         sep=sep)
                    if model_cfg is not None and not _legal(
                            cfg, model_cfg, global_batch, seq_len):
                        continue
                    out.append(cfg)
                    # ep variants: only meaningful for MoE models, and
                    # only dp-divisor degrees — size is ep-invariant so
                    # these share the same device factorization
                    if (include_ep and model_cfg is not None
                            and getattr(model_cfg, "num_experts", 0)):
                        import dataclasses as _dc
                        for ep in _divisors(dp):
                            if ep == 1:
                                continue
                            cfg_ep = _dc.replace(cfg, ep=ep)
                            if _legal(cfg_ep, model_cfg, global_batch,
                                      seq_len):
                                out.append(cfg_ep)
    # stable, human-sensible order: least exotic first
    out.sort(key=lambda c: (c.pp, c.sep, c.fsdp, c.tp, c.dp, c.ep))
    return out


def _legal(cfg: ParallelConfig, m, global_batch: int,
           seq_len: int) -> bool:
    if cfg.tp > 1:
        if (m.num_attention_heads % cfg.tp
                or m.num_key_value_heads % cfg.tp
                or m.intermediate_size % cfg.tp
                or m.vocab_size % cfg.tp):
            return False
    if cfg.fsdp > 1:
        # every fsdp annotation in models/llama.py lands on the hidden
        # dimension (qkv/gate_up dim0, o/down/embed dim1, lm_head dim0),
        # so H-divisibility is the whole sharding constraint; the batch
        # constraint comes from the ("dp","fsdp") batch spec
        if (m.hidden_size % cfg.fsdp
                or global_batch % (cfg.dp * cfg.fsdp)):
            return False
    if cfg.pp > 1:
        if m.num_hidden_layers % cfg.pp:
            return False
        # the pipe candidate compiles with num_microbatches=2, so the
        # per-data-rank (dp×fsdp) batch must split into 2 microbatches
        # exactly — a bare ">= 2" check admits configs whose build then
        # fails and reads as a misleading "compile failed" prune
        per_dp = global_batch // (cfg.dp * cfg.fsdp)
        if per_dp < 2 or per_dp % 2:
            return False
    if cfg.sep > 1:
        if seq_len % cfg.sep or m.num_key_value_heads % cfg.sep:
            return False
    if cfg.pp > 1 and cfg.sep > 1:
        # pipe stage stacking and the seq-parallel ring are separately
        # tested but their composition is not a supported scenario yet
        # (ROADMAP item 4) — don't emit plans we can't compile
        return False
    if cfg.tp > 1 and getattr(m, "num_experts", 0):
        # expert FFN weights carry the tp annotation on their
        # moe_intermediate dimension
        if getattr(m, "moe_intermediate_size", 0) % cfg.tp:
            return False
    if cfg.ep > 1:
        n_exp = int(getattr(m, "num_experts", 0) or 0)
        # the expert subgroup is carved out of the data ranks and must
        # split the expert set evenly across its members
        if not n_exp or n_exp % cfg.ep or cfg.dp % cfg.ep:
            return False
        # explicit composition exclusions, stated like pp×sep: neither
        # the pipe stage stacker nor the sep ring carries the expert
        # all-to-all yet
        if cfg.pp > 1 or cfg.sep > 1:
            return False
    return True


# ---------------------------------------------------------------------------
# pricing
# ---------------------------------------------------------------------------

def ep_imbalance(histogram, ep: int) -> float:
    """Bottleneck factor for the expert all-to-all from a MEASURED
    per-expert token histogram (ISSUE 20's routing-entropy term).

    With tokens uniformly spread over source ranks, the *fraction* of
    tokens crossing shards is 1−1/ep regardless of expert popularity —
    skew shows up instead on the bottleneck link: a2a completion time is
    set by the busiest destination shard. Group the histogram into
    ``ep`` contiguous expert shards (the ep-axis layout of the expert
    dimension); the factor is ``ep × max shard share`` — 1.0 when
    routing is balanced, → ep when one shard absorbs everything.
    Dividing the ep-axis bandwidth by this factor makes
    :func:`price_census` charge the busiest link's bytes."""
    import numpy as np
    h = np.asarray(histogram, dtype=float).ravel()
    if ep <= 1 or h.size == 0 or h.size % ep or h.sum() <= 0:
        return 1.0
    shard_share = h.reshape(ep, h.size // ep).sum(axis=1) / h.sum()
    return float(max(ep * shard_share.max(), 1.0))


@dataclass
class PricedGraph:
    """One compiled graph, priced: the component terms and their sum."""
    compute_s: float              # per-op max(flops/peak, bytes/hbm_bw)
    comm_s: float                 # priced census bytes ÷ per-axis bw
    collective_floor_s: float     # n_collectives × per-tier launch floor
    dispatch_s: float             # measured per-dispatch host floor
    dot_adjust_s: float           # measured-dot correction (OpCostDB)
    predicted_step_s: float
    census_counts: Dict[str, int]
    census_bytes: int
    priced_census: Dict
    total_flops: float
    total_bytes: float
    notes: List[str] = field(default_factory=list)

    def components(self) -> Dict[str, float]:
        return {"compute_s": self.compute_s, "comm_s": self.comm_s,
                "collective_floor_s": self.collective_floor_s,
                "dispatch_s": self.dispatch_s,
                "dot_adjust_s": self.dot_adjust_s,
                "predicted_step_s": self.predicted_step_s}


def _collective_floor(kind: str) -> float:
    return COLLECTIVE_FLOOR_S["cpu" if "cpu" in kind.lower() \
        else "default"]


def _db_dispatch_floor(db, kind: str) -> Tuple[float, List[str]]:
    """Measured per-dispatch host floor: the train-step graph's
    null-executable floor from the calibration probe, when this device
    kind has been calibrated."""
    notes: List[str] = []
    if db is None:
        return 0.0, notes
    from ...ops.pallas.autotune import OpCostDB
    rec = db.lookup(OpCostDB.graph_key("train_step_k1", kind))
    if not rec:
        notes.append(f"OpCostDB has no graph calibration for "
                     f"'{kind}' — dispatch floor 0, analytical only "
                     f"(run tools/op_cost_probe.py --calibrate)")
        return 0.0, notes
    return float(rec.get("dispatch_floor_s", 0.0)), notes


def price_compiled(compiled_or_text, mesh=None, *, spec=None,
                   bandwidths: Optional[Dict[str, float]] = None,
                   db=None, dispatch_floor_s: Optional[float] = None,
                   collective_floor_s: Optional[float] = None
                   ) -> PricedGraph:
    """Price ONE compiled graph (anything with ``as_text()``, or raw
    optimized-HLO text): the shared core under :func:`price_config`,
    the dryrun's rank-order validation, and the graph_lint planner
    budget.

    ``bandwidths`` maps mesh-axis name → bytes/s for the census pricing
    (axes it doesn't name fall back to ``spec.link_bw``); a synthetic
    table therefore yields EXACT arithmetic — the pricing-exactness
    tests pin that property.
    """
    from ...analysis.hlo import parse_hlo
    from ...analysis.collectives import collective_census
    from ...observability.costs import (attribute_costs, device_spec,
                                        price_census)
    spec = spec or device_spec()
    text = (compiled_or_text if isinstance(compiled_or_text, str)
            else compiled_or_text.as_text())
    mod = parse_hlo(text)
    report = attribute_costs(mod, spec=spec)
    census = collective_census(mod, mesh=mesh)
    priced = price_census(census, bandwidths=bandwidths, spec=spec)

    # compute/HBM roofline WITHOUT the comm term — comm is priced per
    # axis by the census (the analyzer's single link_bw verdict would
    # double-count it)
    compute_s = 0.0
    for o in report.ops:
        compute_s += max(o.flops / spec.peak_flops,
                         o.bytes / spec.hbm_bw)

    notes: List[str] = list(report.notes)
    # measured-dot correction: replace the analytical time of every dot
    # shape the calibration probe has measured on this device kind
    dot_adjust = 0.0
    if db is not None:
        from ...ops.pallas.autotune import OpCostDB
        for m_dim, k, n, dtype, count in report.dots:
            rec = db.lookup(OpCostDB.dot_key(m_dim, k, n, dtype,
                                             spec.kind))
            if rec and rec.get("t_s"):
                analytical = 2.0 * m_dim * k * n / spec.peak_flops
                dot_adjust += (float(rec["t_s"]) - analytical) * count
    if dispatch_floor_s is None:
        dispatch_floor_s, db_notes = _db_dispatch_floor(db, spec.kind)
        notes += db_notes
    if collective_floor_s is None:
        collective_floor_s = _collective_floor(spec.kind)
    n_coll = census["total_collectives"]
    floor_s = n_coll * collective_floor_s
    predicted = (max(compute_s + dot_adjust, 0.0)
                 + priced["total_comm_s"] + floor_s + dispatch_floor_s)
    return PricedGraph(
        compute_s=compute_s, comm_s=priced["total_comm_s"],
        collective_floor_s=floor_s, dispatch_s=dispatch_floor_s,
        dot_adjust_s=dot_adjust, predicted_step_s=predicted,
        census_counts=dict(census["counts"]),
        census_bytes=int(census["total_collective_bytes"]),
        priced_census=priced, total_flops=report.total_flops,
        total_bytes=report.total_bytes, notes=notes)


@dataclass
class CandidateBuild:
    """The concrete artifacts one priced config was compiled from —
    kept (``keep_builds=True``) so validation can EXECUTE the same
    program it priced."""
    model: object
    mesh: object
    trainer: object
    batch: Dict
    compiled: object


@dataclass
class PricedConfig:
    config: ParallelConfig
    feasible: bool
    memory: Optional[object] = None          # MemoryEstimate
    graph: Optional[PricedGraph] = None
    predicted_step_s: float = math.inf
    predicted_mfu: float = 0.0
    hbm_high_water_bytes: float = 0.0
    plan: Optional[object] = None            # emit.ShardingPlan
    measured_step_s: Optional[float] = None
    reason: str = ""
    build: Optional[CandidateBuild] = None

    def as_dict(self) -> Dict:
        out = {"config": str(self.config), "axes": self.config.axes(),
               "feasible": self.feasible,
               "predicted_step_s": self.predicted_step_s,
               "predicted_mfu": self.predicted_mfu,
               "hbm_high_water_bytes": self.hbm_high_water_bytes,
               "reason": self.reason}
        if self.memory is not None:
            out["memory"] = self.memory.as_dict()
        if self.graph is not None:
            out["components"] = self.graph.components()
            out["census_counts"] = self.graph.census_counts
            out["census_bytes"] = self.graph.census_bytes
        if self.measured_step_s is not None:
            out["measured_step_s"] = self.measured_step_s
        if self.plan is not None:
            out["plan"] = self.plan.as_dict()
        return out


def _build_candidate(model_cfg, cfg: ParallelConfig, devices,
                     global_batch: int, seq_len: int) -> CandidateBuild:
    """Compile the REAL trainer step for one config: sharded model on
    the real mesh — the same construction path as the MULTICHIP dryrun
    scenarios, so what the planner prices is what the trainer runs."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import paddle_tpu as pt
    from ...models import (LlamaForCausalLM, LlamaForCausalLMPipe,
                           MoEForCausalLM)
    from ...optimizer import AdamW
    from ...parallel import (HybridMesh, shard_layer,
                             shard_optimizer_state, shard_tensor,
                             param_spec_tree)
    from ...trainer import Trainer

    import dataclasses
    is_moe = bool(getattr(model_cfg, "num_experts", 0))
    if is_moe:
        mcfg = model_cfg
    else:
        mcfg = dataclasses.replace(model_cfg,
                                   sequence_parallel=cfg.sep > 1)
    pt.seed(0)
    if cfg.pp > 1:
        model = LlamaForCausalLMPipe(mcfg, num_stages=cfg.pp,
                                     num_microbatches=2)
    elif is_moe:
        model = MoEForCausalLM(mcfg)
    else:
        model = LlamaForCausalLM(mcfg)
    hm = HybridMesh.build(dp=cfg.dp, fsdp=cfg.fsdp, tp=cfg.tp,
                          pp=cfg.pp, sep=cfg.sep, ep=cfg.ep,
                          devices=list(devices)[:cfg.size])
    # on an ep mesh the batch shards over the full data submesh
    # dp×ep×fsdp (dp axis size is dp/ep there); ep==1 meshes have no
    # "ep" axis, so the spec must not name it
    data_axes = (("dp", "ep", "fsdp") if cfg.ep > 1
                 else ("dp", "fsdp"))
    with hm:
        shard_layer(model)
        tr = Trainer(model, AdamW(learning_rate=1e-3, parameters=model),
                     donate=False)
        tr.opt_state = shard_optimizer_state(tr.opt_state,
                                             param_spec_tree(model))
        rs = np.random.RandomState(0)
        ids = rs.randint(0, mcfg.vocab_size, (global_batch, seq_len + 1))
        batch = {"input_ids": shard_tensor(jnp.asarray(ids[:, :-1]),
                                           spec=P(data_axes, None)),
                 "labels": shard_tensor(jnp.asarray(ids[:, 1:]),
                                        spec=P(data_axes, None))}
        tr._ensure_built()
        args = (tr.params, tr.opt_state, batch, tr._lr_scalar(),
                tr._key_data())
        compiled = tr._step_jit.lower(*args).compile()
    return CandidateBuild(model=model, mesh=hm, trainer=tr, batch=batch,
                          compiled=compiled)


def price_config(config: ParallelConfig, model_cfg, *, devices=None,
                 global_batch: int = 8, seq_len: int = 32,
                 bandwidths: Optional[Dict[str, float]] = None,
                 spec=None, db=None,
                 dispatch_floor_s: Optional[float] = None,
                 collective_floor_s: Optional[float] = None,
                 hbm_budget_bytes: Optional[float] = None,
                 keep_build: bool = False,
                 check_memory: bool = True,
                 moe_histogram=None) -> PricedConfig:
    """Memory-gate, compile, attribute and price ONE config; emit its
    GSPMD plan. Infeasible configs return without paying a compile.

    ``moe_histogram`` — measured per-expert token counts. For ep>1
    configs the expert all-to-all is priced from it: the ep-axis
    bandwidth fed to ``price_census`` is divided by
    :func:`ep_imbalance`, so skewed routing raises the predicted price
    (the compile-only census cannot see data-dependent skew)."""
    import jax
    from ...observability.costs import device_spec
    from .memory_model import estimate_hbm
    from .emit import emit_plan

    spec = spec or device_spec()
    imb = 1.0
    if moe_histogram is not None and config.ep > 1:
        imb = ep_imbalance(moe_histogram, config.ep)
        bandwidths = dict(bandwidths or {})
        bandwidths["ep"] = bandwidths.get("ep", spec.link_bw) / imb
    mem = None
    if check_memory:
        mem = estimate_hbm(model_cfg, config, global_batch=global_batch,
                           seq_len=seq_len, budget_bytes=hbm_budget_bytes,
                           device_kind=spec.kind)
        if not mem.feasible:
            return PricedConfig(
                config=config, feasible=False, memory=mem,
                hbm_high_water_bytes=mem.total_bytes,
                reason=(f"HBM infeasible: needs "
                        f"{mem.total_bytes / 2**30:.2f} GiB/chip, budget "
                        f"{mem.budget_bytes / 2**30:.2f} GiB"))

    devices = list(devices) if devices is not None else list(jax.devices())
    if config.size > len(devices):
        return PricedConfig(
            config=config, feasible=False, memory=mem,
            reason=f"needs {config.size} devices, {len(devices)} "
                   f"available")

    build = _build_candidate(model_cfg, config, devices, global_batch,
                             seq_len)
    graph = price_compiled(build.compiled, mesh=build.mesh, spec=spec,
                           bandwidths=bandwidths, db=db,
                           dispatch_floor_s=dispatch_floor_s,
                           collective_floor_s=collective_floor_s)
    if imb > 1.0:
        graph.notes.append(
            f"ep all-to-all priced from measured routing histogram: "
            f"bottleneck imbalance ×{imb:.3f} on the ep axis")
    # MFU from the one model-flop definition (PaLM closed form is the
    # cross-paper headline; the planner's denominator is per-chip peak
    # over the WHOLE mesh for the global batch)
    tokens = global_batch * seq_len
    model_flops = build.model.flops_per_token(seq_len) * tokens
    mfu = model_flops / (config.size * spec.peak_flops
                         * graph.predicted_step_s) \
        if graph.predicted_step_s > 0 else 0.0
    sharding_plan = emit_plan(build.model, build.mesh, config)
    pc = PricedConfig(
        config=config, feasible=True, memory=mem, graph=graph,
        predicted_step_s=graph.predicted_step_s, predicted_mfu=mfu,
        hbm_high_water_bytes=(mem.total_bytes if mem is not None
                              else 0.0),
        plan=sharding_plan)
    if keep_build:
        pc.build = build
    return pc


# ---------------------------------------------------------------------------
# drift: the planner consults the cost model's own health signal
# ---------------------------------------------------------------------------

def check_drift(band: Tuple[float, float] = DRIFT_BAND,
                db=None, now: Optional[float] = None) -> Dict:
    """Is the cost model currently trustworthy?

    Two signals, both advisory by design (``plan(drift=...)`` decides
    what to do with them):

    * the live ``pt_step_time_predicted_over_measured`` gauge (PR 10) —
      any published component outside ``band`` means the roofline is
      actively disagreeing with the wall clock;
    * OpCostDB calibration age — graph records older than
      ``CALIBRATION_MAX_AGE_S`` (or absent for this device kind) can't
      anchor measured floors.

    Returns ``{"status": "ok"|"stale"|"uncalibrated", "ratios": {...},
    "notes": [...]}`` — "stale" is the refusal-grade verdict, absence of
    evidence ("uncalibrated") only warns.
    """
    from ...observability.metrics import REGISTRY
    ratios: Dict[str, float] = {}
    notes: List[str] = []
    status = "ok"
    try:
        for row in REGISTRY.collect():
            if row.get("name") != "pt_step_time_predicted_over_measured":
                continue
            comp = row.get("labels", {}).get("component", "?")
            v = float(row.get("value", 0.0))
            ratios[comp] = v
            if v and not (band[0] <= v <= band[1]):
                status = "stale"
                notes.append(
                    f"drift gauge component={comp}: predicted/measured "
                    f"= {v:.3g} outside [{band[0]}, {band[1]}] — "
                    f"recalibrate (tools/op_cost_probe.py --calibrate) "
                    f"before trusting this plan")
    except Exception:
        pass
    if status == "ok" and db is not None:
        from ...observability.costs import device_spec
        from ...ops.pallas.autotune import OpCostDB
        rec = db.lookup(OpCostDB.graph_key("train_step_k1",
                                           device_spec().kind))
        if rec is None:
            status = "uncalibrated"
            notes.append("no OpCostDB calibration for this device kind; "
                         "pricing is analytical-only")
        else:
            try:
                cap = time.mktime(time.strptime(rec["captured_at"],
                                                "%Y-%m-%dT%H:%M:%S"))
                age = (now if now is not None else time.time()) - cap
                if age > CALIBRATION_MAX_AGE_S:
                    status = "uncalibrated"
                    notes.append(f"OpCostDB calibration is "
                                 f"{age / 86400:.0f} days old")
            except (KeyError, ValueError):
                pass
    return {"status": status, "ratios": ratios, "notes": notes}


# ---------------------------------------------------------------------------
# the planner
# ---------------------------------------------------------------------------

@dataclass
class PlanReport:
    """The full planning result: ranked table + chosen plan + the drift
    verdict the ranking was produced under."""
    n_devices: int
    mesh_shape: str
    device: Dict
    model: str
    global_batch: int
    seq_len: int
    ranked: List[PricedConfig] = field(default_factory=list)
    pruned: List[PricedConfig] = field(default_factory=list)
    drift: Dict = field(default_factory=dict)
    notes: List[str] = field(default_factory=list)
    validation: Optional[Dict] = None

    @property
    def chosen(self) -> Optional[PricedConfig]:
        return self.ranked[0] if self.ranked else None

    def table(self, top: Optional[int] = None) -> str:
        rows = self.ranked[:top] if top else self.ranked
        lines = [f"{'config':<24} {'pred step':>12} {'pred MFU':>9} "
                 f"{'HBM GiB':>8} {'comm':>10} {'collectives':>11}"]
        for pc in rows:
            g = pc.graph
            lines.append(
                f"{str(pc.config):<24} "
                f"{pc.predicted_step_s * 1e3:>10.3f}ms "
                f"{pc.predicted_mfu:>9.4f} "
                f"{pc.hbm_high_water_bytes / 2**30:>8.3f} "
                f"{(g.comm_s * 1e6 if g else 0):>8.1f}us "
                f"{(sum(g.census_counts.values()) if g else 0):>11}")
        for pc in self.pruned:
            lines.append(f"{str(pc.config):<24} PRUNED: {pc.reason}")
        return "\n".join(lines)

    def as_dict(self) -> Dict:
        return {
            "schema": "pt-shard-plan-v1",
            "n_devices": self.n_devices, "mesh_shape": self.mesh_shape,
            "device": self.device, "model": self.model,
            "global_batch": self.global_batch, "seq_len": self.seq_len,
            "drift": self.drift, "notes": self.notes,
            "ranked": [pc.as_dict() for pc in self.ranked],
            "pruned": [pc.as_dict() for pc in self.pruned],
            "chosen": (str(self.chosen.config) if self.chosen else None),
            **({"validation": self.validation} if self.validation
               else {}),
        }

    def save(self, path: str) -> str:
        with open(path, "w", encoding="utf-8") as f:
            json.dump(self.as_dict(), f, indent=1, sort_keys=True,
                      default=float)
            f.write("\n")
        return path


def plan(model_cfg, *, n_devices: Optional[int] = None, devices=None,
         mesh_shape: str = "", global_batch: int = 8, seq_len: int = 32,
         configs: Optional[Sequence[ParallelConfig]] = None,
         bandwidths: Optional[Dict[str, float]] = None, spec=None,
         db=None, drift: str = "warn",
         hbm_budget_bytes: Optional[float] = None,
         dispatch_floor_s: Optional[float] = None,
         collective_floor_s: Optional[float] = None,
         keep_builds: bool = False,
         model_name: str = "llama",
         moe_histogram=None) -> PlanReport:
    """Enumerate → prune → price → rank → emit.

    ``drift`` — "warn" (annotate + warnings.warn), "refuse" (raise
    :class:`StaleCostModelError` when the drift gauge is out of band),
    or "ignore". Raises :class:`InfeasibleMeshError` when the mesh
    can't host any legal config (the CLI's nonzero-exit contract).
    """
    import jax
    from ...observability.costs import device_spec, get_op_cost_db

    if drift not in ("warn", "refuse", "ignore"):
        raise ValueError(f"drift must be warn|refuse|ignore, got "
                         f"{drift!r}")
    spec = spec or device_spec()
    if db is None:
        db = get_op_cost_db()
    devices = list(devices) if devices is not None else list(jax.devices())
    n = int(n_devices) if n_devices else len(devices)
    if n > len(devices):
        raise InfeasibleMeshError(
            f"mesh declares {n} devices but only {len(devices)} exist")

    drift_verdict = {"status": "ignored", "ratios": {}, "notes": []}
    if drift != "ignore":
        drift_verdict = check_drift(db=db)
        if drift_verdict["status"] == "stale":
            msg = "; ".join(drift_verdict["notes"])
            if drift == "refuse":
                raise StaleCostModelError(msg)
            warnings.warn(f"sharding planner: {msg}", RuntimeWarning,
                          stacklevel=2)

    cand = list(configs) if configs is not None else enumerate_configs(
        n, model_cfg, global_batch=global_batch, seq_len=seq_len)
    if not cand:
        raise InfeasibleMeshError(
            f"no legal (dp,fsdp,tp,pp,sep) factorization of {n} devices "
            f"for this model/batch (global_batch={global_batch}, "
            f"seq_len={seq_len})")

    report = PlanReport(
        n_devices=n, mesh_shape=mesh_shape or str(n),
        device=spec.as_dict(), model=model_name,
        global_batch=global_batch, seq_len=seq_len,
        drift=drift_verdict, notes=list(drift_verdict["notes"]))

    for cfg in cand:
        if cfg.size != n:
            report.pruned.append(PricedConfig(
                config=cfg, feasible=False,
                reason=f"size {cfg.size} != mesh {n}"))
            continue
        try:
            pc = price_config(
                cfg, model_cfg, devices=devices,
                global_batch=global_batch, seq_len=seq_len,
                bandwidths=bandwidths, spec=spec, db=db,
                dispatch_floor_s=dispatch_floor_s,
                collective_floor_s=collective_floor_s,
                hbm_budget_bytes=hbm_budget_bytes,
                keep_build=keep_builds,
                moe_histogram=moe_histogram)
        except Exception as e:       # a config that can't compile is
            pc = PricedConfig(       # pruned evidence, not a crash
                config=cfg, feasible=False,
                reason=f"compile failed: {type(e).__name__}: "
                       f"{str(e)[:200]}")
        (report.ranked if pc.feasible else report.pruned).append(pc)

    report.ranked.sort(key=lambda pc: pc.predicted_step_s)
    if not report.ranked:
        raise InfeasibleMeshError(
            "every candidate config was pruned:\n"
            + "\n".join(f"  {pc.config}: {pc.reason}"
                        for pc in report.pruned))
    return report


# ---------------------------------------------------------------------------
# rank-order validation (the acceptance bar)
# ---------------------------------------------------------------------------

def rank_agreement(predicted: Sequence[float],
                   measured: Sequence[float],
                   rel_eps: float = 0.05) -> float:
    """Pairwise (Kendall tau-b-style) concordance between two
    orderings: fraction of index pairs ordered the same way. 1.0 =
    identical order, 0.5 = uncorrelated, 0.0 = reversed.

    Pairs within ``rel_eps`` relative distance in EITHER list are
    statistical ties and drop out of the denominator (tau-b's tie
    handling): min-of-rounds ordering between two configs 1% apart is
    noise, and a cost model should be judged on the orderings it
    actually asserts."""
    assert len(predicted) == len(measured)
    n = len(predicted)
    if n < 2:
        return 1.0

    def _sign(a: float, b: float) -> int:
        if abs(a - b) <= rel_eps * max(abs(a), abs(b)):
            return 0
        return 1 if a > b else -1

    agree = total = 0
    for i in range(n):
        for j in range(i + 1, n):
            sp = _sign(predicted[i], predicted[j])
            sm = _sign(measured[i], measured[j])
            if sp == 0 or sm == 0:
                continue
            total += 1
            agree += (sp == sm)
    return agree / total if total else 1.0


def measure_compiled(compiled, args, *, rounds: int = 3, iters: int = 2,
                     warmup: int = 1) -> float:
    """Min-of-rounds per-call seconds for an undonated compiled program
    (the bench-variance policy: mins over interle-able rounds beat
    means on a noisy host)."""
    import jax

    def _block(out):
        leaves = [l for l in jax.tree_util.tree_leaves(out)
                  if hasattr(l, "block_until_ready")]
        if leaves:
            leaves[-1].block_until_ready()

    for _ in range(max(0, warmup)):
        _block(compiled(*args))
    best = float("inf")
    for _ in range(max(1, rounds)):
        out = None
        t0 = time.perf_counter()
        for _ in range(max(1, iters)):
            out = compiled(*args)
        _block(out)
        best = min(best, (time.perf_counter() - t0) / max(1, iters))
    return best


def validate_rank_order(report: PlanReport, *, rounds: int = 4,
                        iters: int = 2) -> Dict:
    """Execute every ranked config's OWN priced program and compare the
    predicted ordering with the measured one. Requires
    ``plan(keep_builds=True)``. Returns the verdict dict that
    ``tools/plan.py --validate`` and the dryrun print: pairwise agreement,
    whether the predicted winner lands in the measured top 2, and the
    per-config table.

    Rounds INTERLEAVE across configs (the op_cost_probe discipline): a
    host-contention spike then taxes every config's round equally
    instead of wholly landing on whichever config was being timed —
    sequential timing measurably scrambles the ordering on a shared
    host."""
    import gc
    import jax

    def _block(out):
        leaves = [l for l in jax.tree_util.tree_leaves(out)
                  if hasattr(l, "block_until_ready")]
        if leaves:
            leaves[-1].block_until_ready()

    rows, argsets = [], []
    for pc in report.ranked:
        if pc.build is None:
            continue
        tr, batch = pc.build.trainer, pc.build.batch
        args = (tr.params, tr.opt_state, batch, tr._lr_scalar(),
                tr._key_data())
        _block(pc.build.compiled(*args))              # warmup, off-clock
        rows.append(pc)
        argsets.append(args)
    best = [float("inf")] * len(rows)
    for _ in range(max(1, rounds)):
        for i, pc in enumerate(rows):
            gc.collect()
            out = None
            t0 = time.perf_counter()
            for _ in range(max(1, iters)):
                out = pc.build.compiled(*argsets[i])
            _block(out)
            best[i] = min(best[i],
                          (time.perf_counter() - t0) / max(1, iters))
    for pc, t in zip(rows, best):
        pc.measured_step_s = t
    if len(rows) < 2:
        return {"n_configs": len(rows), "agreement": 1.0,
                "top1_is_measured_top2": 1.0,
                "note": "fewer than 2 measurable configs"}
    pred = [pc.predicted_step_s for pc in rows]
    meas = [pc.measured_step_s for pc in rows]
    agreement = rank_agreement(pred, meas)
    pred_best = min(range(len(rows)), key=lambda i: pred[i])
    meas_rank = sorted(range(len(rows)), key=lambda i: meas[i])
    # "within the measured top 2", with a 10% near-tie tolerance at the
    # boundary: min-of-rounds ordering between statistical ties is
    # arbitrary, and a binary acceptance row must not flap on it
    top2_cut = meas[meas_rank[min(1, len(rows) - 1)]] * 1.10
    top1_ok = (pred_best in meas_rank[:2]
               or meas[pred_best] <= top2_cut)
    verdict = {
        "n_configs": len(rows),
        "agreement": round(agreement, 4),
        "top1_is_measured_top2": 1.0 if top1_ok else 0.0,
        "predicted_best": str(rows[pred_best].config),
        "measured_best": str(rows[meas_rank[0]].config),
        "table": [{"config": str(pc.config),
                   "predicted_s": pc.predicted_step_s,
                   "measured_s": pc.measured_step_s} for pc in rows],
    }
    report.validation = verdict
    return verdict
