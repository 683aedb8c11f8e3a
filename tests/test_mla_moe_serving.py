"""A latent-attention (MLA), sigmoid-routed model through the program's
normal paths, held to the benchmark family's plain reference
(``benchmarks/families/mla_moe.py`` -> ``refs/mla_moe.py``) at a tiny size
on the CPU, in float32, on seeded random weights.

Tolerances, and why: program and reference compute the same float32
mathematics in different orders (fused projections, the absorbed form of
attention, a sorted or batched expert product against a scan over experts),
so hidden states of size ~1 and logits of size ~0.7 agree to a few units of
float32 rounding accumulated over three layers: 2e-5 absolute. A wrong
rotary convention, a bias leaking into the weights, a missing scale or an
unnormalised latent moves them by 1e-2 or more.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as pt  # noqa: E402
from benchmarks import families, program, weights  # noqa: E402
from paddle_tpu.inference import ContinuousBatchingEngine  # noqa: E402
from paddle_tpu.inference.generation import GenerationConfig  # noqa: E402
from paddle_tpu.models.moe_lm import (LatentAttention, MoEConfig,  # noqa: E402
                                      MoEForCausalLM)
from paddle_tpu.parallel.moe import MoELayer  # noqa: E402

TOL = 2e-5
SEED = 7
ENGINE = dict(max_batch=4, max_len=96, page_size=16, num_pages=20)


def tiny_config():
    """GLM-4.7-Flash's published file with every size shrunk: the program
    is built from it exactly as ``benchmarks/program.build_engine`` builds
    the cell's (same ``config_fields``, same ``param_names``)."""
    import json
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "glm-4.7-flash.serve-1chip.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
               num_attention_heads=4, num_key_value_heads=4,
               n_routed_experts=8, num_experts_per_tok=2, num_hidden_layers=3,
               vocab_size=256, q_lora_rank=24, kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               max_position_embeddings=512, dtype="float32", engine=ENGINE)
    return cfg


@pytest.fixture(scope="module")
def glm():
    """(config, model in eval mode with seeded weights, reference logits fn)."""
    cfg = tiny_config()
    model, names = program.build_model(cfg)
    program.install(model, names, weights.make_all(SEED, cfg))
    model.eval()
    family = families.of(cfg)
    get = lambda ns: weights.make_some(SEED, cfg, ns)

    def reference(ids):
        """Reference logits [s, V] of one row of token ids."""
        ids = np.asarray(ids, np.int32)[None]
        s = ids.shape[1]
        with jax.default_matmul_precision("highest"):
            return np.asarray(family.logits_at(
                cfg, get, [(jnp.asarray(ids), np.zeros(s, int),
                            np.arange(s))])[0])
    return cfg, model, reference


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.int32)


# -- the model against the reference -----------------------------------------

@pytest.mark.parametrize("mode", ["eval", "train"])
def test_forward_logits_match_the_reference(glm, mode):
    """Expanded attention through ``flash_attention``'s dispatcher and the
    routed block on its training (sorted, grouped) or inference (batched)
    path."""
    cfg, model, reference = glm
    ids = _ids(40)
    getattr(model, mode)()
    try:
        got = np.asarray(model(jnp.asarray(ids[None])))[0]
    finally:
        model.eval()
    assert np.abs(got - reference(ids)).max() < TOL


def test_prefill_then_decode_through_the_latent_cache_matches_the_reference(glm):
    """Prefill 21 tokens into the page pool, decode 12 more through it (the
    absorbed form over cached rows): every step's logits are the
    reference's full forward pass at that position."""
    cfg, model, reference = glm
    ids, p, page = _ids(33, 1), 21, 16
    pools, tables = model.alloc_paged_caches(1, 48, page)
    padded = np.zeros((1, 32), np.int32)
    padded[0, :p] = ids[:p]
    state = model.alloc_slot_state(1)
    assert jax.tree.leaves(state) == []      # the latent rows are all of it
    hidden, pools, state = model.prefill_paged(
        jnp.asarray(padded), pools, tables, state, 0, jnp.int32(p - 1))
    want = reference(ids)
    assert np.abs(np.asarray(model.logits(hidden[0, p - 1])) - want[p - 1]
                  ).max() < TOL
    for pos in range(p, len(ids)):
        hidden, pools, state, counts = model.decode_step_paged(
            jnp.asarray(ids[pos:pos + 1]), jnp.asarray([pos], jnp.int32),
            pools, tables, state)
        assert counts.shape == (len(model.tick_counters),)
        got = np.asarray(model.logits(hidden[0, 0]))
        assert np.abs(got - want[pos]).max() < TOL, pos


def test_absorbed_attention_equals_expanded_attention_for_one_layer():
    """The two forms are the same mathematics: a layer's expanded forward
    over 27 positions against prefill of 11 then 16 absorbed decode steps
    over the cache, for a batch of two rows."""
    cfg = MoEConfig(hidden_size=64, num_attention_heads=4, attention="mla",
                    q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
                    qk_rope_head_dim=8, v_head_dim=16, rope_theta=1e6,
                    rms_norm_eps=1e-5, dtype="float32")
    attn = LatentAttention(cfg)
    x = jax.random.normal(jax.random.key(3), (2, 27, 64), jnp.float32)
    want = np.asarray(attn(x))
    (pool,), tables = attn.alloc_pool(2 * 2, 16), jnp.arange(4).reshape(2, 2)
    got, kv, _ = attn.prefill_paged(x[:, :11], None, None, (pool,), tables,
                                    (), None, None)
    assert np.abs(np.asarray(got) - want[:, :11]).max() < TOL
    for pos in range(11, 27):
        out, kv, _ = attn.decode_paged(
            x[:, pos:pos + 1], None, None, jnp.full((2,), pos, jnp.int32),
            kv, tables, ())
        assert np.abs(np.asarray(out)[:, 0] - want[:, pos]).max() < TOL, pos


# -- the router ---------------------------------------------------------------

def _set(layer, name, value):
    dict(layer.named_parameters())[name].value = jnp.asarray(value, jnp.float32)


def _router_layer(**kw):
    layer = MoELayer(16, 8, 6, top_k=2, capacity_factor=None, dtype="float32",
                     **kw)
    _set(layer, "gate_weight", jax.random.normal(jax.random.key(1), (16, 6)))
    return layer


def test_selection_bias_changes_the_choice_and_not_the_weights():
    layer = _router_layer(scoring="sigmoid", select_bias=True,
                          norm_topk_prob=False)
    logits = jnp.asarray([[2.0, 1.0, 0.5, 0.0, -1.0, -2.0]])
    _, gates, ids = layer._choose(logits)
    assert sorted(np.asarray(ids)[0]) == [0, 1]
    _set(layer, "gate_bias", [0.0, 0.0, 0.0, 0.0, 0.0, 5.0])
    scores, gates, ids = layer._choose(logits)
    assert sorted(np.asarray(ids)[0]) == [0, 5]       # the bias chose 5
    sig = 1.0 / (1.0 + np.exp(-np.asarray(logits)[0]))
    want = {0: sig[0], 5: sig[5]}                     # weights: no bias in them
    for g, i in zip(np.asarray(gates)[0], np.asarray(ids)[0]):
        assert abs(g - want[int(i)]) < 1e-6


@pytest.mark.parametrize("norm,scale", [(True, 1.8), (False, 1.8), (True, 1.0)])
def test_router_weights_against_a_hand_written_top_k(norm, scale):
    """Sigmoid scores, the top-2 of scores + bias, renormalised or not,
    times the routing scale: the layer's output is the weighted sum of the
    chosen experts' SwiGLUs, worked out here expert by expert in numpy."""
    layer = _router_layer(scoring="sigmoid", select_bias=True,
                          norm_topk_prob=norm, routed_scaling_factor=scale)
    _set(layer, "gate_bias", [0.3, -0.2, 0.0, 0.1, -0.4, 0.2])
    layer.eval()
    x = np.asarray(jax.random.normal(jax.random.key(2), (1, 5, 16)))
    got, load = layer.forward_inference(jnp.asarray(x))
    wg, b = np.asarray(layer.gate_weight), np.asarray(layer.gate_bias)
    w_gu = np.asarray(layer.experts.w_gate_up)
    w_dn = np.asarray(layer.experts.w_down)
    want, counts = np.zeros((5, 16)), np.zeros(6, int)
    for t in range(5):
        s = 1.0 / (1.0 + np.exp(-(x[0, t] @ wg)))
        chosen = np.argsort(-(s + b))[:2]
        w = s[chosen] / (s[chosen].sum() if norm else 1.0) * scale
        for e, we in zip(chosen, w):
            g, u = np.split(x[0, t] @ w_gu[e], 2)
            want[t] += we * ((g / (1.0 + np.exp(-g)) * u) @ w_dn[e])
            counts[e] += 1
    assert np.abs(np.asarray(got)[0] - want).max() < 1e-5
    assert list(np.asarray(load)) == list(counts)


def test_the_batched_and_the_sorted_inference_paths_agree(monkeypatch):
    """A decode tick's few rows run every expert as one batched matmul, a
    prefill's many are sorted to their experts: the same numbers."""
    layer = _router_layer(scoring="sigmoid", select_bias=True,
                          norm_topk_prob=True, routed_scaling_factor=1.8)
    x = jax.random.normal(jax.random.key(4), (2, 9, 16))
    dense, load_a = layer.forward_inference(x)
    monkeypatch.setattr(MoELayer, "DENSE_ROWS", 0)
    sorted_, load_b = layer.forward_inference(x)
    assert np.abs(np.asarray(dense) - np.asarray(sorted_)).max() < 1e-5
    assert list(np.asarray(load_a)) == list(np.asarray(load_b))
    assert int(np.asarray(load_a).sum()) == 2 * 9 * 2


def test_a_prompts_sorted_rows_equal_every_expert_written_out():
    """GLM-like: 16 SwiGLU experts, all held, top-4 of sigmoid scores + a
    selection bias, renormalised, x 1.8; 300 rows, more than ``DENSE_ROWS``,
    so the rows are sorted, run the loop over an expert's rows (a step of
    192 where the busiest expert has more: it takes two) and come back by
    gathers. Against every expert over every row, written out."""
    pt.seed(3)
    layer = MoELayer(16, 24, 16, top_k=4, capacity_factor=None,
                     dtype="float32", scoring="sigmoid", select_bias=True,
                     norm_topk_prob=True, routed_scaling_factor=1.8).eval()
    _set(layer, "gate_weight", jax.random.normal(jax.random.key(1), (16, 16)))
    _set(layer, "gate_bias", 0.8 * jax.random.normal(jax.random.key(2), (16,)))
    _set(layer, "experts.w_gate_up", 6.0 * layer.experts.w_gate_up)
    _set(layer, "experts.w_down", 6.0 * layer.experts.w_down)
    x = jax.random.normal(jax.random.key(4), (1, 300, 16))
    assert layer.inference_path(300) == ("loop", 192)
    got, load = layer.forward_inference(x)
    assert "while" in str(jax.make_jaxpr(layer.forward_inference)(x))
    assert int(load.sum()) == 1200 and int(load.max()) > 192

    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(x[0] @ layer.gate_weight)
        _, ids = jax.lax.top_k(scores + layer.gate_bias, 4)
        w = jnp.take_along_axis(scores, ids, -1)
        w = 1.8 * w / w.sum(-1, keepdims=True)
        weight = jnp.zeros((300, 16)).at[jnp.arange(300)[:, None], ids].add(w)
        g, u = jnp.split(jnp.einsum("td,edf->etf", x[0],
                                    layer.experts.w_gate_up), 2, -1)
        every = jnp.einsum("etf,efd->etd", jax.nn.silu(g) * u,
                           layer.experts.w_down)
        want = jnp.einsum("te,etd->td", weight, every)
    assert np.abs(np.asarray(want)).max() > 0.05
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < 1e-5


@pytest.mark.parametrize("router", [
    dict(),
    dict(scoring="sigmoid", select_bias=True, norm_topk_prob=True,
         routed_scaling_factor=1.8)], ids=["gshard", "sigmoid_bias_scale"])
def test_the_training_and_the_sorted_inference_paths_agree(router):
    """More rows than ``DENSE_ROWS``: a layer in train mode
    (``_forward_dropless``: ``grouped_matmul``, XLA's ``ragged_dot``) and
    the same layer in eval mode (``forward_inference``: the loop over an
    expert's rows) multiply the same sorted rows by the same weights, so
    they give the same output."""
    layer = _router_layer(**router)
    x = jax.random.normal(jax.random.key(6), (2, 160, 16))
    assert x.shape[0] * x.shape[1] > MoELayer.DENSE_ROWS
    layer.train()
    trained, aux = layer(x)
    layer.eval()
    served, load = layer.forward_inference(x)
    assert np.abs(np.asarray(trained) - np.asarray(served)).max() < 1e-5
    assert float(aux) > 0 and int(np.asarray(load).sum()) == 2 * 160 * 2


def test_a_layer_with_no_new_argument_runs_the_parents_program():
    """OLMoE's layer (dropless, no router argument): what the parent's
    ``_forward_dropless`` computed, written out here as it stood (softmax,
    top-k, renormalised since k > 1, no bias, no scale; the weight applied
    AFTER the rows are back). Since PR 43 the layer applies it to the sorted
    rows before the down product, in float32: the same mathematics summed in
    another order, so equal to float32's rounding and no longer bit for
    bit; the auxiliary loss is the same program."""
    from paddle_tpu.nn import functional as F
    from paddle_tpu.parallel.moe import _aux_loss, grouped_matmul
    layer = MoELayer(16, 8, 6, top_k=2, capacity_factor=None, dtype="float32")
    assert layer._gshard_router and layer.gate_bias is None
    assert [n for n, _ in layer.named_parameters()] == [
        "gate_weight", "experts.w_gate_up", "experts.w_down"]
    x = jax.random.normal(jax.random.key(5), (2, 7, 16))
    got, aux = layer(x)

    flat = x.reshape(14, 16)
    logits = jnp.matmul(flat.astype(jnp.float32), layer.gate_weight)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, 2)
    flat_e = ids.T.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sizes = jnp.bincount(flat_e[order], length=6).astype(jnp.int32)
    gu = grouped_matmul(flat[order % 14], layer.experts.w_gate_up, sizes)
    g, u = jnp.split(gu, 2, axis=-1)
    ys = grouped_matmul(F.silu(g) * u, layer.experts.w_down, sizes)
    y_cm = jnp.zeros_like(ys).at[order].set(ys).reshape(2, 14, 16)
    g_km = gates.T
    g_km = g_km / jnp.maximum(jnp.sum(g_km, 0, keepdims=True), 1e-9)
    want = np.asarray(jnp.sum(g_km[..., None].astype(ys.dtype) * y_cm, axis=0))
    assert np.abs(want).max() > 0
    np.testing.assert_allclose(np.asarray(got).reshape(14, 16), want,
                               rtol=1e-5, atol=1e-6 * np.abs(want).max())
    assert float(aux) == float(_aux_loss(probs, 6))


def test_router_variants_are_refused_off_the_dropless_path():
    with pytest.raises(ValueError, match="capacity_factor=None"):
        MoELayer(16, 8, 6, top_k=2, scoring="sigmoid")
    with pytest.raises(ValueError, match="scoring"):
        MoELayer(16, 8, 6, top_k=2, capacity_factor=None, scoring="tanh")


# -- the kernel ---------------------------------------------------------------

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6), (jnp.bfloat16, 2e-2)])
def test_latent_decode_kernel_matches_its_xla_twin(dtype, tol):
    """Interpret mode against the XLA composition: an empty row (only the
    new token), rows ending inside, at the end of and just past a page,
    a row filling its table, pages scattered over the pool. bf16: the
    kernel rounds the softmax weights to the pages' dtype for the MXU
    (2^-8 relative on sums of ~1), the twin does too but sums in another
    order."""
    from paddle_tpu.ops.pallas.latent_attention import (
        latent_decode_attention, latent_decode_xla)
    B, H, W, R, page, per_row, P = 6, 5, 160, 128, 16, 4, 40
    key = jax.random.key(0)
    q = jax.random.normal(key, (B, H, W), jnp.float32).astype(dtype)
    pages = jax.random.normal(jax.random.fold_in(key, 1), (1, P, page, W),
                              jnp.float32).astype(dtype)
    tables = jnp.asarray(np.random.default_rng(0).permutation(
        np.arange(1, P))[:B * per_row].reshape(B, per_row), jnp.int32)
    lens = jnp.asarray([0, 7, 15, 16, 33, 63], jnp.int32)
    got = latent_decode_attention(q, pages, tables, lens, R, 0.2,
                                  interpret=True)
    want = latent_decode_xla(q, pages, tables, lens, R, 0.2)
    assert got.shape == (B, H, R) and got.dtype == dtype
    assert np.abs(np.asarray(got, np.float32)
                  - np.asarray(want, np.float32)).max() < tol


# -- the engine ---------------------------------------------------------------

def _engine(model, **kw):
    return ContinuousBatchingEngine(
        model, generation_config=GenerationConfig(do_sample=False),
        **dict(ENGINE, **kw))


@pytest.fixture(scope="module")
def served(glm):
    """Six requests through a four-slot engine: (engine, prompts, outputs)."""
    cfg, model, _ = glm
    eng = _engine(model)
    prompts = [_ids(n, 10 + n) for n in (5, 17, 33, 40, 9, 20)]
    rids = [eng.submit(p, max_new_tokens=12) for p in prompts]
    out = eng.run()
    return eng, prompts, [out[r] for r in rids]


def test_engine_serves_the_references_tokens_with_slots_reused(glm, served):
    """Greedy tokens are the reference's argmax at every step (six requests
    over four slots: two slots are used twice), and the token each step
    chose has the reference's best logit to within TOL."""
    _, _, reference = glm
    eng, prompts, outs = served
    for p, t in zip(prompts, outs):
        assert len(t) == 12
        ref = reference(np.concatenate([p, t[:-1]]))[len(p) - 1:]
        gap = ref.max(-1) - ref[np.arange(12), t]
        assert gap.max() < TOL
    stats = eng.stats()
    assert stats["active"] == 0 and stats["free_pages"] == ENGINE["num_pages"]


def test_engine_counts_the_ticks_routed_rows_on_the_device(served):
    """``moe_assignments``: rows x top-k over the routed layers of every
    decode tick (all ``max_batch`` rows are routed, live or not);
    ``moe_peak_load``: the busiest expert's rows, summed likewise, so
    between an even split and everything on one expert."""
    eng, _, _ = served
    stats = eng.stats()
    ticks = stats["attn_paged_ticks"] + stats["attn_dense_ticks"]
    per_tick = ENGINE["max_batch"] * 2 * 2          # rows x top-2 x 2 layers
    assert stats["moe_assignments"] == ticks * per_tick
    assert (stats["moe_assignments"] / 8 <= stats["moe_peak_load"]
            <= stats["moe_assignments"] / 2)


def test_the_pool_holds_one_latent_row_a_token_a_layer(glm, served):
    """One array a layer, [1, pages, page, row]: a token's row is its
    kv_lora_rank + qk_rope_head_dim numbers ``[c_kv | k_r]`` and zeros up to
    whole lane tiles of 128 (stated padding: 32 + 8 -> 128 here; 512 + 64 =
    576 -> 640 at GLM-4.7-Flash's sizes, where the chip would pad a bf16
    row to 640 anyway). ``kv_bytes_per_token`` reads the ALLOCATION: 128 x
    4 bytes x 3 layers here, 640 x 2 x 7 = 8,960 there, of which the family
    counts 576 x 2 x 7 = 8,064 as required."""
    cfg, model, _ = glm
    eng, _, _ = served
    assert eng.attention_kind == "mla" and not eng.kv_quant
    for entry in eng.pools:
        (pool,) = entry
        assert pool.shape == (1, ENGINE["num_pages"] + 1, 16, 128)
        assert not np.asarray(pool[..., 40:]).any()      # the padding is 0
        assert np.asarray(pool[..., :40]).any()
    assert eng.stats()["kv_bytes_per_token"] == 3 * 128 * 4
    assert families.of(cfg).kv_bytes_per_token(cfg, itemsize=4) == 3 * 40 * 4
    # at the published sizes, abstractly: one [1, pages, 128, 640] bf16 array
    from paddle_tpu.models.moe_lm import LatentAttention, MoEConfig
    attn = jax.eval_shape(lambda: LatentAttention(MoEConfig(
        hidden_size=2048, num_attention_heads=20, attention="mla",
        q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
        qk_rope_head_dim=64, v_head_dim=256, dtype="bfloat16")
    ).alloc_pool(1537, 128))
    assert [(a.shape, a.dtype) for a in attn] == [
        ((1, 1537, 128, 640), jnp.bfloat16)]
    assert 7 * 640 * 2 == 8960 and 7 * 576 * 2 == 8064


def test_a_latent_page_is_copied_like_any_other(glm):
    cfg, model, _ = glm
    eng = _engine(model)
    eng.submit(_ids(20, 3), max_new_tokens=2)
    eng.run()
    src = int(np.argmax(np.abs(np.asarray(eng.pools[0][0][0])).sum((1, 2))))
    dst = eng._free[0]
    assert src != dst and src != 0
    eng._cow_page(src, dst)
    for (pool,) in eng.pools:
        assert np.array_equal(np.asarray(pool[0, src]), np.asarray(pool[0, dst]))
        assert np.abs(np.asarray(pool[0, dst])).sum() > 0


@pytest.mark.parametrize("knob,needs", [
    ({"chunked_prefill": True}, "chunked_prefill=True needs .*prefill_chunk_paged"),
    ({"prefix_cache": True}, "prefix_cache=True needs .*prefill_chunk_paged"),
    ({"spec_k": 2}, "spec_k=2 needs .*decode_verify_paged"),
])
def test_engine_refuses_by_name_what_the_model_cannot_run(glm, knob, needs):
    with pytest.raises(ValueError, match=needs):
        _engine(glm[1], **knob)


@pytest.mark.parametrize("call", ["serialize_pages", "adopt_pages"])
def test_handoff_refuses_a_latent_pool_by_name(served, call):
    eng = served[0]
    arg = _ids(32) if call == "serialize_pages" else {"fmt": "pt-kv-pages-v2"}
    with pytest.raises(ValueError, match="handoff .*'mla' pages"):
        getattr(eng, call)(arg)


def test_a_gqa_routed_model_is_served_by_the_same_loop():
    """OLMoE's kind (LlamaAttention over per-head K and V pages, softmax
    router) through the same paged trio: greedy tokens are the argmax of
    the model's own full forward pass."""
    model = MoEForCausalLM(MoEConfig.tiny(capacity_factor=None, dtype="float32"))
    model.eval()
    eng = _engine(model)
    assert eng.attention_kind == "gqa" and len(eng.pools[0]) == 2
    prompt = _ids(19, 8) % 512
    rid = eng.submit(prompt, max_new_tokens=6)
    toks = eng.run()[rid]
    seq = list(prompt)
    for t in toks:
        logits = np.asarray(model(jnp.asarray([seq], jnp.int32)))[0, -1]
        assert logits.max() - logits[t] < 1e-4
        seq.append(int(t))
    assert eng.stats()["moe_assignments"] > 0
