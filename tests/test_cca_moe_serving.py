"""A compressed-convolutional-attention (CCA) model with top-1 experts behind
an MLP router through the program's normal paths, held to the benchmark
family's plain reference (``benchmarks/families/cca_moe.py`` ->
``refs/cca_moe.py``) at a tiny size on the CPU, in float32, on seeded random
weights (``benchmarks/weights.py``).

Tolerances, and why: program and reference compute the same float32
mathematics in different orders (a per-slot state against a shifted
sequence, a batched or sorted expert product against a scan over experts),
so logits of size ~0.8 agree to a few units of float32 rounding over three
layers: 2e-5 absolute, and a served token lies under the reference's best
by no more. The leave-one-out control shows what that tolerance is worth:
the reference with ONE term of the layer dropped (the convolutions, the q-k
mean, the value shift, the carried router state, the residual scales) puts
the served tokens' widest gap at 35 (the router's carried state, whose
experts weigh ~0.1 at this size) to 48,000 (the residual scales) times TOL.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import families, program, weights  # noqa: E402
from paddle_tpu.inference import ContinuousBatchingEngine  # noqa: E402
from paddle_tpu.inference.generation import GenerationConfig  # noqa: E402
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM  # noqa: E402
from paddle_tpu.models.moe_lm import MoEConfig, MoEForCausalLM  # noqa: E402
from paddle_tpu.parallel.moe import MoELayer  # noqa: E402

TOL = 2e-5
SEED = 7
ENGINE = dict(max_batch=4, max_len=96, page_size=16, num_pages=20)
TERMS = ("conv", "qk_mean", "v_shift", "router_state", "residual_scale")


def tiny_config():
    """ZAYA1-8B's configuration file with every size shrunk but the router's
    hidden size (at 16 the seeded biases would out-shout the tokens and
    every row would choose one expert) and with experts wide enough to be a
    visible share of the residual stream: the program is built from it exactly
    as ``benchmarks/program.build_engine`` builds the cell's."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "zaya1-8b.serve-1chip.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=128, moe_intermediate_size=512,
               num_attention_heads=4, num_key_value_heads=2, head_dim=32,
               num_experts=8, num_hidden_layers=3, vocab_size=256,
               max_position_embeddings=512, dtype="float32", engine=ENGINE)
    return cfg


@pytest.fixture(scope="module")
def zaya():
    """(config, model in eval mode with seeded weights, reference logits fn)."""
    cfg = tiny_config()
    model, names = program.build_model(cfg)
    program.install(model, names, weights.make_all(SEED, cfg))
    model.eval()
    family = families.of(cfg)
    get = lambda ns: weights.make_some(SEED, cfg, ns)

    def reference(ids, drop=()):
        """Reference logits [s, V] of one row of token ids."""
        ids = np.asarray(ids, np.int32)[None]
        s = ids.shape[1]
        with jax.default_matmul_precision("highest"):
            return np.asarray(family.logits_at(
                cfg, get, [(jnp.asarray(ids), np.zeros(s, int),
                            np.arange(s))], drop=drop)[0])
    return cfg, model, reference


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.int32)


def _engine(model, **kw):
    return ContinuousBatchingEngine(
        model, generation_config=GenerationConfig(do_sample=False),
        **dict(ENGINE, **kw))


def _gaps(reference, prompts, outs, drop=()):
    """By how much each served token's reference logit lies under the
    reference's best, over all requests."""
    gaps = []
    for p, t in zip(prompts, outs):
        ref = reference(np.concatenate([p, t[:-1]]), drop)[len(p) - 1:]
        gaps.append(ref.max(-1) - ref[np.arange(len(t)), t])
    return np.concatenate(gaps)


# -- the model against the reference -----------------------------------------

@pytest.mark.parametrize("mode", ["eval", "train"])
def test_forward_logits_match_the_reference(zaya, mode):
    """The full-sequence forward (``forward_inference`` in eval mode, the
    dropless training path otherwise), the head being the embedding."""
    _, model, reference = zaya
    ids = _ids(37)
    getattr(model, mode)()
    try:
        got = np.asarray(model(jnp.asarray(ids[None])))[0]
    finally:
        model.eval()
    want = reference(ids)
    assert np.abs(want).max() > 0.3
    assert np.abs(got - want).max() < TOL


def test_the_embedding_is_the_head(zaya):
    cfg, model, _ = zaya
    names = [n for n, _ in model.named_parameters()]
    assert "embed_tokens" in names and "lm_head" not in names
    assert "head" not in families.of(cfg).leaf_shapes(cfg)
    h = jax.random.normal(jax.random.key(0), (3, 128))
    assert np.allclose(np.asarray(model.logits(h)),
                       np.asarray(h @ model.embed_tokens.T), atol=1e-6)


@pytest.fixture(scope="module")
def served(zaya):
    """Six requests through a four-slot engine (two slots are used twice;
    every prompt is shorter than its bucket of whole pages)."""
    _, model, _ = zaya
    eng = _engine(model)
    prompts = [_ids(n, 10 + n) for n in (5, 17, 33, 40, 9, 20)]
    rids = [eng.submit(p, max_new_tokens=12) for p in prompts]
    out = eng.run()
    return eng, prompts, [out[r] for r in rids]


def test_engine_serves_the_references_tokens_with_slots_reused(zaya, served):
    """Prefill, then decode through the pages and the per-slot state: every
    served token has the reference's best logit to within TOL."""
    _, _, reference = zaya
    eng, prompts, outs = served
    assert all(len(t) == 12 for t in outs)
    assert _gaps(reference, prompts, outs).max() < TOL
    stats = eng.stats()
    assert stats["active"] == 0 and stats["free_pages"] == ENGINE["num_pages"]


def test_a_preempted_slot_is_rebuilt_by_its_prefill(zaya):
    """A pool too small for both sequences forces a preemption; the evicted
    request's state is not saved: its re-prefill (prompt + what it had
    generated) writes the slot's state anew, and its tokens are still the
    reference's."""
    _, model, reference = zaya
    eng = _engine(model, max_batch=2, num_pages=3)
    prompts = [_ids(14, 3), _ids(14, 4)]
    rids = [eng.submit(p, max_new_tokens=20) for p in prompts]
    out = eng.run()
    assert eng.preemptions >= 1
    assert _gaps(reference, prompts, [out[r] for r in rids]).max() < TOL


@pytest.mark.parametrize("term", TERMS)
def test_no_term_of_the_layer_can_be_left_out_inside_the_tolerance(
        zaya, served, term):
    """The leave-one-out control: against the reference WITHOUT ``term`` the
    same served tokens lie beyond the limit a sound run keeps (TOL on the
    widest gap; a sound run reads 0.0), by ten times or more."""
    _, _, reference = zaya
    _, prompts, outs = served
    gaps = _gaps(reference, prompts, outs, drop=(term,))
    assert gaps.max() > 10 * TOL, (term, gaps.max())


def test_the_state_lives_beside_the_pool_one_row_a_slot_a_layer(zaya, served):
    """K and V pages in the GQA layout in the compressed space, and beside
    them (c, c1, the shifted value half) for each slot: 2 x (Cq + Ck) +
    Ck / 2 numbers a slot a layer, whatever the sequence's length."""
    cfg, model, _ = zaya
    eng, _, _ = served
    assert eng.attention_kind == "cca" and len(eng.pools[0]) == 2
    for kp, vp in eng.pools:
        assert kp.shape == vp.shape == (2, ENGINE["num_pages"] + 1, 16, 32)
    assert len(eng.slot_state) == 3
    for c, c1, v in eng.slot_state:
        assert (c.shape, c1.shape, v.shape) == ((4, 192), (4, 192), (4, 32))
    family, stats = families.of(cfg), eng.stats()
    assert stats["slot_state_bytes"] == 4 * 3 * family.slot_state_bytes(cfg, 4)
    assert stats["kv_bytes_per_token"] == family.kv_bytes_per_token(cfg, 4)
    # at the published sizes, abstractly: 5,376 B a slot a layer in bf16
    big = MoEConfig(hidden_size=2048, num_attention_heads=8,
                    num_key_value_heads=2, head_dim=128, attention="cca",
                    dtype="bfloat16")
    from paddle_tpu.models.moe_lm import CompressedConvAttention
    state = jax.eval_shape(
        lambda: CompressedConvAttention(big).alloc_slot_state(128))
    assert [a.shape for a in state] == [(128, 1280), (128, 1280), (128, 128)]
    assert sum(a.size * a.dtype.itemsize for a in state) == 128 * 5376


def test_engine_counts_routed_and_skipped_rows_on_the_device(served):
    """``moe_assignments``: rows x top-1 over the three layers of every
    tick, the rows that chose no expert included; ``moe_skipped``: those."""
    eng, _, _ = served
    stats = eng.stats()
    ticks = stats["attn_paged_ticks"] + stats["attn_dense_ticks"]
    assert stats["moe_assignments"] == ticks * ENGINE["max_batch"] * 3
    assert 0 < stats["moe_skipped"] < stats["moe_assignments"]
    assert (stats["moe_assignments"] - stats["moe_skipped"]) / 8 <= \
        stats["moe_peak_load"] <= stats["moe_assignments"]


# -- the router ---------------------------------------------------------------

def _mlp_layer(skip=True):
    layer = MoELayer(16, 8, 6, top_k=1, capacity_factor=None, dtype="float32",
                     router="mlp", router_hidden_size=32, skip_choice=skip)
    key = jax.random.key(3)
    for i, (name, p) in enumerate(layer.named_parameters()):
        if name.startswith("router_") and p.value.ndim == 2:
            # wide enough that the choice follows the token, not a bias
            p.value = jax.random.normal(jax.random.fold_in(key, i),
                                        p.value.shape) * 0.5
    return layer


def test_the_skip_choice_runs_no_expert_and_the_weight_is_the_probability():
    """Against the router written out by hand: the state is handed on after
    the addition; a row whose argmax is the last output gets 0, every other
    row its expert's SwiGLU times the softmax's probability."""
    layer = _mlp_layer().eval()
    x = jax.random.normal(jax.random.key(1), (1, 40, 16))
    prev = jax.random.normal(jax.random.key(2), (1, 40, 32))
    r = layer.router_state(x, prev)
    assert np.allclose(np.asarray(r), np.asarray(
        x @ layer.router_down + layer.router_down_bias
        + layer.router_state_gate * prev), atol=1e-6)
    got, load = layer.forward_inference(x, r)
    n = r[0] * jax.lax.rsqrt(jnp.mean(r[0] ** 2, -1, keepdims=True) + 1e-5)
    h = jax.nn.gelu(n @ layer.router_w1 + layer.router_b1, approximate=False)
    h = jax.nn.gelu(h @ layer.router_w2 + layer.router_b2, approximate=False)
    p = np.asarray(jax.nn.softmax(h @ layer.router_w3, -1))
    assert p.shape == (40, 7)
    choice = p.argmax(-1)
    assert 0 < (choice == 6).sum() < 40
    assert np.array_equal(np.asarray(load), np.bincount(choice, minlength=7)[:6])
    for t in range(40):
        if choice[t] == 6:
            want = np.zeros(16, np.float32)
        else:
            e = choice[t]
            g, u = np.split(np.asarray(x[0, t] @ layer.experts.w_gate_up[e]), 2)
            want = p[t, e] * np.asarray(
                (jax.nn.silu(g) * u) @ layer.experts.w_down[e])
        assert np.allclose(np.asarray(got[0, t]), want, atol=1e-6), t


def test_a_prompts_sorted_rows_equal_the_router_written_out():
    """ZAYA-like: top-1 behind the MLP router, the skip choice in it; 300
    rows, more than ``DENSE_ROWS``, so they are sorted to their experts,
    the skipped ones behind every expert's run, run the loop over an
    expert's rows and come back by a gather. Row by row against the chosen
    expert's SwiGLU times the softmax's probability, 0 for a skipped row."""
    layer = _mlp_layer().eval()
    x = jax.random.normal(jax.random.key(1), (1, 300, 16))
    r = layer.router_state(x)
    assert layer.inference_path(300) == ("loop", 128)
    got, load = layer.forward_inference(x, r)
    text = str(jax.make_jaxpr(layer.forward_inference)(x, r))
    assert "while" in text and "ragged_dot" not in text
    n = r[0] * jax.lax.rsqrt(jnp.mean(r[0] ** 2, -1, keepdims=True) + 1e-5)
    h = jax.nn.gelu(n @ layer.router_w1 + layer.router_b1, approximate=False)
    h = jax.nn.gelu(h @ layer.router_w2 + layer.router_b2, approximate=False)
    p = np.asarray(jax.nn.softmax(h @ layer.router_w3, -1))
    choice = p.argmax(-1)
    assert 10 < (choice == 6).sum() < 290
    assert np.array_equal(np.asarray(load),
                          np.bincount(choice, minlength=7)[:6])
    g, u = jnp.split(jnp.einsum("td,edf->etf", x[0],
                                layer.experts.w_gate_up), 2, -1)
    every = np.asarray(jnp.einsum("etf,efd->etd", jax.nn.silu(g) * u,
                                  layer.experts.w_down))
    want = np.stack([np.zeros(16, np.float32) if c == 6
                     else p[t, c] * every[c, t]
                     for t, c in enumerate(choice)])
    assert np.abs(want).max() > 1e-4
    assert np.abs(np.asarray(got[0]) - want).max() < 1e-6


@pytest.mark.parametrize("skip", [True, False])
def test_both_inference_paths_and_the_training_path_agree(monkeypatch, skip):
    """Every expert over every row (at most DENSE_ROWS rows), rows sorted
    to their experts (the loop over an expert's rows) and the differentiated
    dropless path (``ragged_dot``)
    compute one result under the MLP router, skipped rows and all."""
    layer = _mlp_layer(skip)
    x = jax.random.normal(jax.random.key(4), (2, 24, 16))
    r = layer.router_state(x)
    trained, aux = layer(x, r)
    layer.eval()
    dense, load_a = layer.forward_inference(x, r)
    monkeypatch.setattr(MoELayer, "DENSE_ROWS", 0)
    sorted_, load_b = layer.forward_inference(x, r)
    assert np.array_equal(np.asarray(load_a), np.asarray(load_b))
    assert int(load_a.sum()) == 48 if not skip else 0 < int(load_a.sum()) < 48
    assert float(aux) == 0.0
    for other in (sorted_, trained):
        assert np.abs(np.asarray(dense) - np.asarray(other)).max() < 1e-6
    g = jax.grad(lambda v: jnp.sum(MoELayer.forward(
        layer.train(), v, layer.router_state(v))[0] ** 2))(x)
    assert np.isfinite(np.asarray(g)).all() and np.abs(np.asarray(g)).max() > 0


def test_the_mlp_router_is_refused_off_the_dropless_path():
    with pytest.raises(ValueError, match="capacity_factor=None"):
        MoELayer(16, 8, 6, top_k=1, router="mlp", router_hidden_size=8)
    with pytest.raises(ValueError, match="router_hidden_size"):
        MoELayer(16, 8, 6, top_k=1, capacity_factor=None, router="mlp")
    with pytest.raises(ValueError, match="skip_choice"):
        MoELayer(16, 8, 6, top_k=1, capacity_factor=None, skip_choice=True)
    with pytest.raises(ValueError, match="router_state"):
        _mlp_layer().eval().forward_inference(jnp.zeros((1, 2, 16)))
    with pytest.raises(ValueError, match="router='mlp' normalises"):
        MoEConfig(router="mlp", router_hidden_size=8, rms_norm_eps=1e-6)


# -- what the engine refuses, and what it leaves alone -------------------------

@pytest.mark.parametrize("knob,needs", [
    ({"chunked_prefill": True}, "chunked_prefill=True needs a snapshot of the per-slot state"),
    ({"prefix_cache": True}, "prefix_cache=True needs a snapshot of the per-slot state"),
    ({"spec_k": 2}, "spec_k=2 needs a snapshot of the per-slot state"),
])
def test_engine_refuses_by_name_what_needs_a_snapshot_of_slot_state(
        zaya, knob, needs):
    with pytest.raises(ValueError, match=needs):
        _engine(zaya[1], **knob)


@pytest.mark.parametrize("call", ["serialize_pages", "adopt_pages"])
def test_handoff_refuses_a_model_with_slot_state_by_name(served, call):
    eng = served[0]
    arg = _ids(32) if call == "serialize_pages" else {"fmt": "pt-kv-pages-v2"}
    with pytest.raises(ValueError, match="handoff .*per-slot state"):
        getattr(eng, call)(arg)


@pytest.mark.parametrize("build", [
    lambda: MoEForCausalLM(MoEConfig.tiny(capacity_factor=None,
                                          dtype="float32")),
    lambda: LlamaForCausalLM(LlamaConfig.tiny(dtype="float32")),
], ids=["routed-gqa", "dense-gqa"])
def test_a_model_without_slot_state_builds_the_programs_it_built(build):
    """A core whose state is all in its pages hands the engine an EMPTY
    pytree (``ServingCore.alloc_slot_state``), so its prefill and tick have exactly the parent's inputs (one a
    leaf of params, pools, tables, key, state, knobs and nothing else: the
    unused slot index is pruned) and give back nothing more. (Lowered from
    the parent commit and from this one, the GLM, OLMoE and Llama engines'
    prefill and ``run`` programs and OLMoE's training step are the same
    text, byte for byte: CHANGES.md, PR 31.)"""
    model = build().eval()
    eng = _engine(model)
    eng.submit(_ids(5) % 200, max_new_tokens=2)
    eng.run()
    assert jax.tree.leaves(eng.slot_state) == []
    assert eng.stats()["slot_state_bytes"] == 0
    assert "moe_skipped" not in eng.stats()
    args = eng._decode_args(False)
    assert jax.tree.leaves(args[6]) == []
    (run,) = eng._decode_fns.values()
    assert len(jax.make_jaxpr(run)(*args).jaxpr.invars) == len(
        jax.tree.leaves(args[:6]))
    (prefill,) = eng._prefill_cache.values()
    pre = (eng._params, jnp.zeros((1, 16), jnp.int32), eng.pools,
           jnp.asarray(eng.tables[:1]), jnp.int32(4))
    (main,) = [line for line in prefill.lower(
        *pre, eng.slot_state, np.int32(0)).as_text().splitlines() if "@main(" in line]
    assert main.count("%arg") == len(jax.tree.leaves(pre))
    out = jax.eval_shape(run, *args)
    assert len(out) == 5 and jax.tree.leaves(out[4]) == []


def test_a_model_with_no_new_field_has_the_parameters_it_had():
    """GLM's and OLMoE's kinds of model pass none of this PR's fields:
    one-matrix router, an ``lm_head`` of its own, plain residual sums."""
    for cfg in (MoEConfig.tiny(capacity_factor=None),
                MoEConfig.tiny(capacity_factor=None, attention="mla",
                               q_lora_rank=8, kv_lora_rank=16,
                               qk_nope_head_dim=8, qk_rope_head_dim=8,
                               v_head_dim=8, scoring_func="sigmoid",
                               router_bias=True)):
        model = MoEForCausalLM(cfg)
        names = [n for n, _ in model.named_parameters()]
        assert "lm_head" in names and cfg.head_dim == 32
        assert not [n for n in names if "merge" in n or "router_" in n
                    or "conv" in n]
        assert jax.tree.leaves(model.alloc_slot_state(4)) == []
        assert "layers.1.moe.gate_weight" in names
        assert model.tick_counters == ("moe_assignments", "moe_peak_load")
