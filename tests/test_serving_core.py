"""The contract between a model's core and the serving engine
(``paddle_tpu/models/serving_core.py``), held over the tiny configuration of
every core the engine serves: what is declared is there, the cache is a
pytree (empty where the core has none), the two hot programs have ONE arity
in and out, an empty state costs a compiled program nothing, and the
engine's refusals read the declared facts and still name the mode and the
core."""

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.inference.generation import generate_paged
from paddle_tpu.models.hybrid_lm import HybridConfig, HybridForCausalLM
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.moe_lm import MoEConfig, MoEForCausalLM
from paddle_tpu.models.serving_core import ServingCore

MOE = dict(capacity_factor=None, dtype="float32")
# name -> (model, it keeps a per-slot state, its tick's counters)
CASES = {
    "llama": (lambda: LlamaForCausalLM(LlamaConfig.tiny(dtype="float32")),
              False, 0),
    "moe-gqa": (lambda: MoEForCausalLM(MoEConfig.tiny(**MOE)), False, 2),
    "moe-mla": (lambda: MoEForCausalLM(MoEConfig.tiny(
        attention="mla", q_lora_rank=32, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16, **MOE)),
        False, 2),
    "moe-cca": (lambda: MoEForCausalLM(MoEConfig.tiny(
        attention="cca", head_dim=16, router="mlp", router_hidden_size=32,
        router_skip_choice=True, residual_scaling=True, rms_norm_eps=1e-5,
        **MOE)), True, 3),
    "hybrid-pages": (lambda: HybridForCausalLM(HybridConfig.tiny()), True, 3),
    "hybrid-no-page": (lambda: HybridForCausalLM(
        HybridConfig.tiny(pattern="p-m-")), True, 0),
}
OPTIONAL = ("prefill_chunk_paged", "decode_verify_paged")
SLOTS, PAGE, BUCKET = 2, 16, 32


@pytest.fixture(scope="module", params=list(CASES))
def case(request):
    build, stateful, n_counts = CASES[request.param]
    model = build().eval()
    return model, getattr(model, "model", model), stateful, n_counts


def test_every_member_of_the_contract_is_declared(case):
    _, core, stateful, n_counts = case
    assert isinstance(core, ServingCore)
    assert core.attention_kind in ("gqa", "mla", "cca", "hybrid")
    assert isinstance(core.tick_counters, tuple)
    assert len(core.tick_counters) == n_counts
    assert set(core.optional_programs) <= set(OPTIONAL)
    mine = lambda name: getattr(type(core), name) is not getattr(
        ServingCore, name)
    assert mine("prefill_paged") and mine("decode_step_paged")
    pools, tables = core.alloc_paged_caches(SLOTS, 4 * PAGE, PAGE)
    assert len(pools) == len(core.pool_layers())
    assert tables.shape == (SLOTS, 4)
    # an optional program is implemented exactly where it is declared
    assert [name for name in OPTIONAL if mine(name)] == list(
        core.optional_programs)
    state = core.alloc_slot_state(SLOTS)
    leaves = jax.tree.leaves(state)
    assert bool(leaves) == stateful
    assert all(a.shape[0] == SLOTS for a in leaves)
    assert core.expert_path(BUCKET) is None or len(
        core.expert_path(BUCKET)) == 2
    assert core.state_path(BUCKET, SLOTS) in (None, "kernel", "xla", "fused")


def test_the_two_hot_programs_have_one_arity(case):
    model, core, _, n_counts = case
    pools, tables = core.alloc_paged_caches(SLOTS, 4 * PAGE, PAGE)
    state = core.alloc_slot_state(SLOTS)
    with model._bind(model.raw_parameters()):
        pre = jax.eval_shape(
            core.prefill_paged, jnp.zeros((1, BUCKET), jnp.int32), pools,
            tables[:1], state, jnp.int32(1), jnp.int32(BUCKET - 3))
        tick = jax.eval_shape(
            core.decode_step_paged, jnp.zeros((SLOTS,), jnp.int32),
            jnp.zeros((SLOTS,), jnp.int32), pools, tables, state)
    shapes = lambda tree: [(a.shape, a.dtype) for a in jax.tree.leaves(tree)]
    hidden, new_pools, new_state = pre
    assert hidden.shape[:2] == (1, BUCKET)
    assert shapes(new_pools) == shapes(pools)
    assert shapes(new_state) == shapes(state)
    hidden, new_pools, new_state, counts = tick
    assert hidden.shape[:2] == (SLOTS, 1)
    assert shapes(new_pools) == shapes(pools)
    assert shapes(new_state) == shapes(state)
    if n_counts:
        assert (counts.shape, counts.dtype) == ((n_counts,), jnp.int32)
    else:
        assert counts is None


def test_a_tick_takes_the_leaves_of_its_state_and_nothing_for_none(case):
    model, _, stateful, _ = case
    eng = ContinuousBatchingEngine(model, max_batch=SLOTS, max_len=4 * PAGE,
                                   page_size=PAGE)
    eng._init_state(jnp.zeros((model.cfg.vocab_size,), jnp.float32))
    eng._tables_dev = jnp.asarray(eng.tables)
    args = eng._decode_args(False)
    run = jax.make_jaxpr(eng._build_decode(1, False, "paged"))(*args)
    state = jax.tree.leaves(eng.slot_state)
    assert bool(state) == stateful
    assert len(run.jaxpr.invars) == len(jax.tree.leaves(args[:6])) + len(state)
    assert eng.stats()["slot_state_bytes"] == sum(
        a.size * a.dtype.itemsize for a in state)


@pytest.mark.parametrize("mode", [dict(chunked_prefill=True),
                                  dict(prefix_cache=True), dict(spec_k=2)])
def test_a_refusal_names_the_mode_and_the_core(case, mode):
    model, core, stateful, _ = case
    build = lambda: ContinuousBatchingEngine(
        model, max_batch=SLOTS, max_len=4 * PAGE, page_size=PAGE, **mode)
    (name,) = mode
    if stateful:
        why = "snapshot of the per-slot state"
    elif not set(OPTIONAL) <= set(core.optional_programs):
        why = "needs a model whose core implements"
    else:
        assert build().core is core          # every program: every mode
        return
    with pytest.raises(ValueError, match=why) as refused:
        build()
    assert name in str(refused.value)
    assert type(core).__name__ in str(refused.value)


def test_the_engine_serves_a_declared_core_and_nothing_else():
    class Bare:
        pass
    with pytest.raises(TypeError, match="Bare is no ServingCore"):
        ContinuousBatchingEngine(Bare())


def test_generate_paged_refuses_a_core_with_slot_state_by_name():
    model = CASES["hybrid-no-page"][0]().eval()
    with pytest.raises(ValueError, match="HybridForCausalLM keeps per-slot"):
        generate_paged(model, jnp.zeros((2, 8), jnp.int32), max_new_tokens=2,
                       page_size=PAGE)
