"""Canonical-graph registry: the compiled entrypoints whose shape the
graph contracts pin.

Each builder constructs the REAL jitted program (the Trainer's step jit,
the serving engine's decode/spec tick, the prefix-hit admit dispatch, the
fused CE head) at a micro model size, lowers+compiles it for the current
backend, and returns it with its contract. Builders reach into the same
internals the runtime dispatches through (``Trainer._step_jit``,
``ContinuousBatchingEngine._build_decode``...), so a refactor that
changes what those paths compile changes exactly what the lint sees —
there is no parallel "model of the model" to drift.

Sizes are chosen so the banned-shape signatures are unambiguous
(B*S and V collide with no other dimension product) and a full
``build_all`` stays test-suite-cheap on CPU.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

from .contracts import BanRule, GraphContract

__all__ = ["BuiltGraph", "GraphSkipped", "REGISTRY", "build_graph",
           "graph_names"]

# micro-Llama the canonical graphs share. V=320 and B*S=40 are chosen so
# the banned-shape signature [*, V] x prod(*)==B*S collides with nothing:
# hidden=64, gate_up=2*96=192, qkv=128 — no other buffer has a 320 last
# dim (V=256 collided with the MLP's 2*intermediate and turned every
# gate_up activation into a false logits hit)
_B, _S = 2, 20
_VOCAB, _HIDDEN = 320, 64


class GraphSkipped(Exception):
    """Raised by a builder whose environment can't host the graph (e.g.
    the dp2xtp2 census graph on a single-device process)."""


@dataclass
class BuiltGraph:
    name: str
    compiled: object                   # jax.stages.Compiled
    contract: GraphContract
    mesh: Optional[object] = None
    #: the concrete arrays the graph was lowered on — lets the cost probe
    #: (tools/op_cost_probe.py) EXECUTE the canonical graph for measured
    #: timings (donation-safe: the probe copies per call)
    example_args: Optional[tuple] = None


def _micro_cfg():
    from ..models import LlamaConfig
    return LlamaConfig(vocab_size=_VOCAB, hidden_size=_HIDDEN,
                       intermediate_size=96, num_hidden_layers=2,
                       num_attention_heads=4, num_key_value_heads=2,
                       max_position_embeddings=128)


def _micro_model():
    import paddle_tpu as pt
    pt.seed(0)
    from ..models import LlamaForCausalLM
    return LlamaForCausalLM(_micro_cfg())


def _trainer():
    from ..optimizer import AdamW
    from ..trainer import Trainer
    model = _micro_model()
    tr = Trainer(model, AdamW(learning_rate=1e-4, parameters=model))
    tr._ensure_built()
    return tr


def _batch():
    import jax.numpy as jnp
    return {"input_ids": jnp.zeros((_B, _S), jnp.int32),
            "labels": jnp.zeros((_B, _S), jnp.int32)}


_TRAIN_CONTRACT_KW = dict(
    # the PR 5 property: no [B,S,V]/[B*S,V] logits buffer, any dtype
    ban_rules=(BanRule(_VOCAB, _B * _S, label="BSV-logits"),),
    require_aliased=("params", "opt_state"),
    max_host_transfers=0,
)


def build_train_step_k1() -> BuiltGraph:
    """Trainer._dispatch's per-step program: fused-CE loss + grads +
    AdamW update, params/opt_state donated."""
    tr = _trainer()
    args = (tr.params, tr.opt_state, _batch(), tr._lr_scalar(),
            tr._key_data())
    compiled = tr._step_jit.lower(*args).compile()
    return BuiltGraph("train_step_k1", compiled, GraphContract(
        "train_step_k1", notes="per-step trainer dispatch",
        **_TRAIN_CONTRACT_KW), example_args=args)


def build_train_step_k4() -> BuiltGraph:
    """The superstep: K=4 optimizer steps in one lax.scan dispatch
    (PR 2's no-per-step-host-work property rides on transfers==0)."""
    import jax.numpy as jnp

    from ..io.dataloader import stack_batches
    tr = _trainer()
    stack = stack_batches([_batch()] * 4)
    args = (tr.params, tr.opt_state, stack, jnp.zeros((4,), jnp.float32),
            tr._key_data())
    compiled = tr._superstep_jit.lower(*args).compile()
    return BuiltGraph("train_step_k4", compiled, GraphContract(
        "train_step_k4", notes="K=4 superstep scan",
        **_TRAIN_CONTRACT_KW), example_args=args)


def _engine(**kw):
    import jax.numpy as jnp

    from ..inference.serving import ContinuousBatchingEngine
    model = _micro_model()
    eng = ContinuousBatchingEngine(model, max_batch=2, page_size=8,
                                   max_len=64, **kw)
    eng._init_state(jnp.zeros((_VOCAB,), jnp.float32))
    return eng


def build_serving_tick() -> BuiltGraph:
    """The non-speculative decode tick (K=4 paged scan): pools donated,
    stop detection on device, zero host transfers."""
    import jax.numpy as jnp
    eng = _engine()
    eng._tables_dev = jnp.asarray(eng.tables)
    fn = eng._build_decode(4, any_sample=False, attn_impl="paged")
    args = eng._decode_args(False)
    compiled = fn.lower(*args).compile()
    return BuiltGraph("serving_tick", compiled, GraphContract(
        "serving_tick", require_aliased=("pools",),
        max_host_transfers=0,
        notes="decode_block=4 paged scan, spec off"), example_args=args)


def build_serving_tick_quant() -> BuiltGraph:
    """The quantized decode tick (ISSUE 17): int8 weights + int8 KV
    pages. Beyond the plain tick's contract (pools donated, zero host
    transfers), NO widened pool-shaped f32/bf16 buffer may materialize:
    dequant must stay fused into the attention read — per-sequence
    gather working sets are fine, a whole-pool dequant pass is the
    regression the ban exists for. num_pages is deliberately NOT
    max_batch*pages_per_seq so the pool shape cannot collide with the
    legitimate gathered working set's dims."""
    import jax.numpy as jnp

    from ..inference.serving import ContinuousBatchingEngine
    from ..quantization import quantize_model
    model = quantize_model(_micro_model(), kv_dtype="int8")
    eng = ContinuousBatchingEngine(model, max_batch=2, page_size=8,
                                   max_len=64, num_pages=24)
    eng._init_state(jnp.zeros((_VOCAB,), jnp.float32))
    eng._tables_dev = jnp.asarray(eng.tables)
    fn = eng._build_decode(4, any_sample=False, attn_impl="paged")
    args = eng._decode_args(False)
    compiled = fn.lower(*args).compile()
    hkv, npages, ps, hd = eng.pools[0][0].shape
    return BuiltGraph("serving_tick_quant", compiled, GraphContract(
        "serving_tick_quant", require_aliased=("pools",),
        max_host_transfers=0,
        ban_rules=(BanRule(hd, hkv * npages * ps, label="f32-pool",
                           dtype="f32"),
                   BanRule(hd, hkv * npages * ps, label="bf16-pool",
                           dtype="bf16")),
        notes="decode_block=4 paged scan, int8 weights + int8 KV"),
        example_args=args)


def build_serving_tick_spec() -> BuiltGraph:
    """The speculative tick (draft + (k+1)-wide verify + commit): pools
    AND the [B, max_len] history carry donated — un-donating either is a
    contract failure (the ISSUE 8 acceptance case)."""
    import jax.numpy as jnp
    eng = _engine(spec_k=3)
    fn = eng._build_spec_decode(3, any_sample=False)
    args = (eng._params, eng.pools, jnp.asarray(eng.tables),
            eng._base_key, eng._state, eng._knobs, eng._hist)
    compiled = fn.lower(*args).compile()
    return BuiltGraph("serving_tick_spec", compiled, GraphContract(
        "serving_tick_spec", require_aliased=("pools", "hist"),
        max_host_transfers=0,
        notes="spec_k=3 draft+verify tick"), example_args=args)


def build_prefix_admit() -> BuiltGraph:
    """The full-prompt-hit admit dispatch: COW of the boundary page fused
    with the single-token logits re-forward — ONE dispatch, pools
    donated."""
    import jax.numpy as jnp
    eng = _engine()
    fn = eng._tail_logits_fn()
    args = (eng._params, jnp.zeros((1, 1), jnp.int32),
            jnp.zeros((1,), jnp.int32), eng.pools,
            jnp.asarray(eng.tables[0:1]), jnp.int32(1),
            jnp.int32(2))
    compiled = fn.lower(*args).compile()
    return BuiltGraph("prefix_admit", compiled, GraphContract(
        "prefix_admit", require_aliased=("pools",),
        max_host_transfers=0,
        notes="prefix-hit COW + 1-token re-forward"), example_args=args)


def build_fused_ce() -> BuiltGraph:
    """The fused vocab-CE primitive, fwd+bwd, standalone: the op-level
    version of the train-step ban (no [N, V] block)."""
    import jax
    import jax.numpy as jnp

    from ..ops.pallas.fused_vocab_ce import fused_linear_cross_entropy
    N, H = 64, 32
    h = jnp.zeros((N, H), jnp.float32)
    w = jnp.zeros((H, _VOCAB), jnp.float32)
    lab = jnp.zeros((N,), jnp.int32)

    def loss(h, w):
        return fused_linear_cross_entropy(h, w, lab, block_n=16,
                                          block_v=64, impl="xla")

    compiled = jax.jit(
        jax.value_and_grad(loss, argnums=(0, 1))).lower(h, w).compile()
    return BuiltGraph("fused_ce", compiled, GraphContract(
        "fused_ce",
        ban_rules=(BanRule(_VOCAB, N, label="NV-logits"),),
        max_host_transfers=0,
        notes="lse_and_target fwd+bwd, xla impl"), example_args=(h, w))


def build_tp_fused_ce() -> BuiltGraph:
    """TP composition of the fused CE head on a dp=2 x tp=2 mesh: the
    collective census contract — exactly one pmax + the two psums (global
    LSE + target logit) over the tp axis, and NO all-gather (an implicit
    GSPMD reshard re-materializing a vocab shard). On jax 0.9.0 XLA's
    all-reduce combiner carries both psums — same dtype, same replica
    groups, both ready once the local pass is done — in ONE tuple
    all-reduce of two operands: two all-reduces, the same bytes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    if jax.device_count() < 4:
        raise GraphSkipped("needs >= 4 devices (dp=2 x tp=2 mesh); run "
                           "under XLA_FLAGS=--xla_force_host_platform_"
                           "device_count=8")
    from ..parallel import HybridMesh, shard_tensor
    from ..parallel.mp_layers import parallel_fused_linear_cross_entropy

    hm = HybridMesh.build(dp=2, tp=2, devices=jax.devices()[:4])
    B, S, H = 2, 16, _HIDDEN
    rs = np.random.RandomState(0)
    h = jnp.asarray(rs.randn(B, S, H).astype(np.float32))
    w = jnp.asarray(rs.randn(H, _VOCAB).astype(np.float32) * 0.1)
    lab = jnp.asarray(rs.randint(0, _VOCAB, (B, S)))
    with hm:
        h_s = shard_tensor(h, spec=P("dp", None, None))
        w_s = shard_tensor(w, spec=P(None, "tp"))
        lab_s = shard_tensor(lab, spec=P("dp", None))
        f = jax.jit(lambda h, w, l: parallel_fused_linear_cross_entropy(
            h, w, l, mesh=hm, block_n=8, block_v=64))
        compiled = f.lower(h_s, w_s, lab_s).compile()
    return BuiltGraph("tp_fused_ce", compiled, GraphContract(
        "tp_fused_ce",
        ban_rules=(BanRule(_VOCAB, B * S, label="global-logits"),),
        max_host_transfers=0,
        expect_collectives={"all-reduce[tp]": 2},
        notes="dp2xtp2 shard_map fused CE: pmax + one combined all-reduce "
              "of the 2 psums, 0 all-gather"),
        mesh=hm)


def build_planner() -> BuiltGraph:
    """The sharding planner's emit/price contract (ISSUE 11): price the
    dp2×tp2 micro-model config, then compile the train step THROUGH the
    emitted ``ShardingPlan`` (``Trainer.apply_plan`` — the consumer
    path) and require the emitted graph's collective census to EXACTLY
    match the priced census the planner ranked with. A pricing/emission
    divergence (plan says replicate, runtime shards — or vice versa)
    changes the census and fails CI like any other contract."""
    import jax

    if jax.device_count() < 4:
        raise GraphSkipped("needs >= 4 devices (dp=2 x tp=2 mesh); run "
                           "under XLA_FLAGS=--xla_force_host_platform_"
                           "device_count=8")
    import numpy as np
    import jax.numpy as jnp

    import paddle_tpu as pt
    from ..distributed.auto_parallel import (ParallelConfig,
                                             price_config)
    from ..models import LlamaForCausalLM
    from ..optimizer import AdamW
    from ..trainer import Trainer

    cfg = _micro_cfg()
    priced = price_config(ParallelConfig(dp=2, tp=2), cfg,
                          devices=jax.devices()[:4], global_batch=4,
                          seq_len=32, check_memory=False)

    pt.seed(0)
    model = LlamaForCausalLM(cfg)
    tr = Trainer(model, AdamW(learning_rate=1e-3, parameters=model),
                 donate=False)
    hm = tr.apply_plan(priced.plan, devices=jax.devices()[:4])
    rs = np.random.RandomState(0)
    ids = rs.randint(0, cfg.vocab_size, (4, 33))
    with hm:
        batch = priced.plan.shard_batch(
            {"input_ids": jnp.asarray(ids[:, :-1]),
             "labels": jnp.asarray(ids[:, 1:])}, hm)
        tr._ensure_built()
        args = (tr.params, tr.opt_state, batch, tr._lr_scalar(),
                tr._key_data())
        compiled = tr._step_jit.lower(*args).compile()
    return BuiltGraph("planner", compiled, GraphContract(
        "planner",
        expect_collectives=dict(priced.graph.census_counts),
        max_host_transfers=0,
        notes=f"emitted {priced.config} plan == priced census "
              f"(closed set)"),
        mesh=hm, example_args=args)


def build_train_step_fsdp() -> BuiltGraph:
    """The ZeRO-3 train step (ISSUE 18): price the fsdp2×tp2 micro
    config, then compile the step THROUGH the emitted plan
    (``Trainer.apply_plan``) and require the emitted census to EXACTLY
    match the priced one (closed set) — the fsdp axis's param
    all-gathers and grad reduce-scatters are part of that set, so a
    refactor that drops the sharding (silently replicating params) or
    doubles the gathers fails CI. The budget snapshot additionally pins
    ``exposed_comm_fraction``/``min_overlap_distance`` over the gather
    windows: a serialized all-gather regression is a budget diff, not a
    silent 2× step-time tax."""
    import jax

    if jax.device_count() < 4:
        raise GraphSkipped("needs >= 4 devices (fsdp=2 x tp=2 mesh); "
                           "run under XLA_FLAGS=--xla_force_host_"
                           "platform_device_count=8")
    import numpy as np
    import jax.numpy as jnp

    import paddle_tpu as pt
    from ..distributed.auto_parallel import (ParallelConfig,
                                             price_config)
    from ..models import LlamaForCausalLM
    from ..optimizer import AdamW
    from ..trainer import Trainer

    cfg = _micro_cfg()
    priced = price_config(ParallelConfig(fsdp=2, tp=2), cfg,
                          devices=jax.devices()[:4], global_batch=4,
                          seq_len=32, check_memory=False)

    pt.seed(0)
    model = LlamaForCausalLM(cfg)
    tr = Trainer(model, AdamW(learning_rate=1e-3, parameters=model),
                 donate=False)
    hm = tr.apply_plan(priced.plan, devices=jax.devices()[:4])
    rs = np.random.RandomState(0)
    ids = rs.randint(0, cfg.vocab_size, (4, 33))
    with hm:
        batch = priced.plan.shard_batch(
            {"input_ids": jnp.asarray(ids[:, :-1]),
             "labels": jnp.asarray(ids[:, 1:])}, hm)
        tr._ensure_built()
        args = (tr.params, tr.opt_state, batch, tr._lr_scalar(),
                tr._key_data())
        compiled = tr._step_jit.lower(*args).compile()
    return BuiltGraph("train_step_fsdp", compiled, GraphContract(
        "train_step_fsdp",
        expect_collectives=dict(priced.graph.census_counts),
        max_host_transfers=0,
        notes=f"emitted {priced.config} ZeRO-3 plan == priced census "
              f"(closed set, gather windows budget-pinned)"),
        mesh=hm, example_args=args)


def build_train_step_moe_ep() -> BuiltGraph:
    """The expert-parallel MoE train step (ISSUE 20): price the
    ep-pure dp2_ep2 micro config — the shard_map dispatch path, so the
    census carries the real ``all-to-all[ep]`` rows, not a GSPMD
    approximation — compile the step THROUGH the emitted plan and
    require the emitted census to EXACTLY match the priced one (closed
    set). A refactor that drops the expert all-to-all (silently
    replicating experts) or doubles it fails CI as a census diff."""
    import jax

    if jax.device_count() < 2:
        raise GraphSkipped("needs >= 2 devices (dp=2/ep=2 subgroup "
                           "mesh); run under XLA_FLAGS=--xla_force_"
                           "host_platform_device_count=8")
    import numpy as np
    import jax.numpy as jnp

    import paddle_tpu as pt
    from ..distributed.auto_parallel import (ParallelConfig,
                                             price_config)
    from ..models import MoEForCausalLM
    from ..models.moe_lm import MoEConfig
    from ..optimizer import AdamW
    from ..trainer import Trainer

    cfg = MoEConfig(vocab_size=_VOCAB, hidden_size=_HIDDEN,
                    intermediate_size=96, moe_intermediate_size=48,
                    num_hidden_layers=2, num_attention_heads=4,
                    num_key_value_heads=2, num_experts=4,
                    num_experts_per_tok=2, num_shared_experts=1,
                    first_k_dense_replace=1, capacity_factor=None,
                    max_position_embeddings=128)
    priced = price_config(ParallelConfig(dp=2, ep=2), cfg,
                          devices=jax.devices()[:2], global_batch=4,
                          seq_len=32, check_memory=False)

    pt.seed(0)
    model = MoEForCausalLM(cfg)
    tr = Trainer(model, AdamW(learning_rate=1e-3, parameters=model),
                 donate=False)
    hm = tr.apply_plan(priced.plan, devices=jax.devices()[:2])
    rs = np.random.RandomState(0)
    ids = rs.randint(0, cfg.vocab_size, (4, 33))
    with hm:
        batch = priced.plan.shard_batch(
            {"input_ids": jnp.asarray(ids[:, :-1]),
             "labels": jnp.asarray(ids[:, 1:])}, hm)
        tr._ensure_built()
        args = (tr.params, tr.opt_state, batch, tr._lr_scalar(),
                tr._key_data())
        compiled = tr._step_jit.lower(*args).compile()
    return BuiltGraph("train_step_moe_ep", compiled, GraphContract(
        "train_step_moe_ep",
        expect_collectives=dict(priced.graph.census_counts),
        max_host_transfers=0,
        notes=f"emitted {priced.config} expert-parallel plan == priced "
              f"census (closed set incl. all-to-all[ep])"),
        mesh=hm, example_args=args)


REGISTRY: Dict[str, Callable[[], BuiltGraph]] = {
    "train_step_k1": build_train_step_k1,
    "train_step_k4": build_train_step_k4,
    "serving_tick": build_serving_tick,
    "serving_tick_quant": build_serving_tick_quant,
    "serving_tick_spec": build_serving_tick_spec,
    "prefix_admit": build_prefix_admit,
    "fused_ce": build_fused_ce,
    "tp_fused_ce": build_tp_fused_ce,
    "planner": build_planner,
    "train_step_fsdp": build_train_step_fsdp,
    "train_step_moe_ep": build_train_step_moe_ep,
}


def graph_names() -> List[str]:
    return list(REGISTRY)


def build_graph(name: str) -> BuiltGraph:
    return REGISTRY[name]()
