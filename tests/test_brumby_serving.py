"""A decoder of power-retention layers (``brumby``'s layout, as
Brumby-14B-Base publishes it: no attention layer, so NO paged layer) through
the program's normal paths, held to the benchmark family's plain reference
(``benchmarks/families/brumby.py`` -> ``refs/brumby.py``: the QUADRATIC form,
every position against every earlier one, no state, no ``phi``) at a tiny
size on the CPU, in float32, on seeded random weights
(``benchmarks/weights.py``).

The seeded gate forgets half of the state a token, so what a prompt's chunks
and a tick carry hardly shows. Every test here therefore runs a SLOW variant
too, gates near 1 (log g ~ -1/400): column 0 of the table is set to 20, which
no layer's output outweighs, so every normalised input has ~8 in its first
coordinate at every depth, and the gate's projection reads it with 0.75 a
head: a logit of ~6. Program and reference both get the patched leaves.

Tolerances, and why: program and reference compute the same float32
function as two algebraic forms (a recurrence over 136 products of pairs a
head against 8 x 9 x 16 x 16 numbers of state, chunks of 16, against one
masked weighting of all earlier positions). A reading is a ratio whose
denominator is the sum of a position's weights; logits of size ~1 agree to
3e-5 where that sum is not small and to 5e-4 at worst on fast gates, where
the last two or three squared scores ARE the sum and may all be small. A
served token lies under the reference's best by no more.
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
from benchmarks.refs import brumby as ref  # noqa: E402
from paddle_tpu.inference import ContinuousBatchingEngine  # noqa: E402
from paddle_tpu.inference.generation import GenerationConfig  # noqa: E402
from paddle_tpu.models.hybrid_lm import (HybridConfig,  # noqa: E402
                                         HybridForCausalLM,
                                         PowerRetentionMixer)

TOL = {"fast": 5e-4, "slow": 3e-5}
SEED = 13
ENGINE = dict(max_batch=2, max_len=160, page_size=16)
CONFIG = os.path.join(ROOT, "benchmarks", "configs",
                      "brumby-14b-base.serve-1chip.json")


def tiny_config(**kw):
    """The Brumby configuration's file with every size shrunk: three layers
    (six blocks ``p-p-p-``), 10 query heads on 2 KV heads (five a group, as
    published), head size 16 (9 tiles of 16 lanes for 136 pairs), chunks of
    16 so that a prompt of 70 crosses four edges; built exactly as
    ``benchmarks/program.build_engine`` builds the cell's."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, num_hidden_layers=3, num_attention_heads=10,
               num_key_value_heads=2, head_dim=16, intermediate_size=96,
               vocab_size=256, dtype="float32", engine=ENGINE)
    cfg.update(kw)
    cfg["hybrid_pattern"] = ref.pattern(cfg)
    cfg["program"]["config_fields"]["chunk_size"] = 16
    return cfg


def _leaves(cfg, gates):
    """name -> leaf (float32), the slow variant's two patches applied."""
    def patch(name, leaf):
        if gates == "slow" and name == "embed":
            return leaf.at[:, 0].set(20.0)
        if gates == "slow" and name.endswith(".gate"):
            return leaf.at[0, :].set(0.75)
        return leaf
    return lambda names: {n: patch(n, v) for n, v in weights.make_some(
        SEED, cfg, names).items()}


def _build(cfg, get):
    model, names = program.build_model(cfg)
    program.install(model, names, get(list(families.of(cfg).leaf_shapes(cfg))))
    return model.eval()


def _reference(cfg, get):
    def logits(ids):
        """Reference logits [s, V] of one row of token ids."""
        ids = np.asarray(ids, np.int32)[None]
        s = ids.shape[1]
        with jax.default_matmul_precision("highest"):
            return np.asarray(families.of(cfg).logits_at(
                cfg, get, [(jnp.asarray(ids), np.zeros(s, int),
                            np.arange(s))])[0])
    return logits


@pytest.fixture(scope="module", params=["fast", "slow"])
def brumby(request):
    """(gates, config, model in eval mode, reference logits)."""
    cfg = tiny_config()
    get = _leaves(cfg, request.param)
    return request.param, cfg, _build(cfg, get), _reference(cfg, get)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.int32)


def _engine(model, **kw):
    return ContinuousBatchingEngine(
        model, generation_config=GenerationConfig(do_sample=False),
        **dict(ENGINE, **kw))


def _gaps(reference, prompts, outs):
    gaps = []
    for p, t in zip(prompts, outs):
        r = reference(np.concatenate([p, t[:-1]]))[len(p) - 1:]
        gaps.append(r.max(-1) - r[np.arange(len(t)), t])
    return np.concatenate(gaps)


# -- (a) the mixer --------------------------------------------------------------

def test_the_slow_variants_gates_are_near_one(brumby):
    """What the variant is for: the logarithm of every gate of every layer
    over a prompt, as the program computes it."""
    gates, _, model, _ = brumby
    x = jnp.take(model.embed_tokens, jnp.asarray(_ids(70, 3))[None], axis=0)
    for layer in model.layers:
        if layer.kind == "p":
            log_g = layer.mixer._qkvg(layer.norm(x), jnp.arange(70))[3]
            if gates == "slow":
                assert -0.01 < float(log_g.min()) and float(log_g.max()) < 0
            else:
                assert float(log_g.mean()) < -0.5
        x = layer(x)


def test_a_mixer_is_the_references_retention(brumby):
    """One mixer's whole-sequence form against ``refs/brumby.py::retention``
    on the same leaves: the per-head norm BEFORE the rotary embedding, the
    rotation by position, the gate's float32 projection, five query heads a
    KV head."""
    gates, cfg, model, _ = brumby
    mixer = model.layers[2].mixer
    assert isinstance(mixer, PowerRetentionMixer)
    w = {"qkv": mixer.qkv_proj, "gate": mixer.g_proj, "q_norm": mixer.q_norm,
         "k_norm": mixer.k_norm, "o": mixer.o_proj}
    u = jnp.asarray(np.random.default_rng(5).normal(size=(2, 50, 64)),
                    jnp.float32)
    if gates == "slow":
        u = u.at[:, :, 0].set(8.0)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.retention(cfg, w, u))
    assert np.abs(want).max() > 0.1
    assert np.abs(np.asarray(mixer(u)) - want).max() < 2e-5


@pytest.mark.parametrize("mode", ["eval", "train"])
def test_forward_logits_match_the_reference(brumby, mode):
    gates, _, model, reference = brumby
    ids = _ids(70, 1)
    getattr(model, mode)()
    got = np.asarray(model(jnp.asarray(ids)[None]))[0]
    model.eval()
    want = reference(ids)
    assert np.abs(want).max() > 0.3
    assert np.abs(got - want).max() < 3e-5


# -- (b) prefill, then ticks, through the slot state ---------------------------

def test_prefill_then_ticks_through_the_slot_state_equal_the_full_forward(
        brumby):
    """A prompt of 37 tokens (three chunks of 16, the last ragged) padded to
    TWO buckets leaves the same state and the same next-token logits; then
    20 decode ticks through the slot state alone (no pool, no table that
    means anything) read the reference's logits at every position, each
    row at its OWN position: the other slot's garbage moves nothing."""
    gates, _, model, reference = brumby
    ids = _ids(57, 2)
    want = reference(ids)
    pools, tables = model.alloc_paged_caches(2, 64, 16)
    assert pools == []
    seen = []
    for bucket in (48, 64):
        padded = jnp.zeros((1, bucket), jnp.int32).at[0, :37].set(ids[:37])
        h, filled, state = model.prefill_paged(
            padded, pools, tables[1:2], model.alloc_slot_state(2), 1,
            jnp.int32(36))
        logits = np.asarray(model.logits(h[0, 36]))
        assert np.abs(logits - want[36]).max() < TOL[gates]
        seen.append((logits, state))
    assert filled == []
    assert np.abs(seen[0][0] - seen[1][0]).max() < 1e-6
    for a, b in zip(jax.tree.leaves(seen[0][1]), jax.tree.leaves(seen[1][1])):
        assert np.abs(np.asarray(a) - np.asarray(b)).max() < 1e-5
        assert not np.asarray(a)[0].any()          # slot 0 was not written
    pos = jnp.asarray([5, 37], jnp.int32)
    for t in range(37, 57):
        h, filled, state, counts = model.decode_step_paged(
            jnp.asarray([7, ids[t]], jnp.int32), pos, filled, tables, state)
        assert counts is None                # it declares no counter
        assert np.abs(np.asarray(model.logits(h[1, 0]))
                      - want[t]).max() < TOL[gates]
        pos = pos + 1


# -- (c) through the engine: a core with no pool -------------------------------

@pytest.fixture(scope="module")
def served(brumby):
    """Five requests through a two-slot engine (every slot is used again;
    every prompt is shorter than its bucket; the longest crosses five
    chunks)."""
    _, _, model, _ = brumby
    eng = _engine(model)
    prompts = [_ids(n, 10 + n) for n in (5, 17, 70, 40, 9)]
    rids = [eng.submit(p, max_new_tokens=12) for p in prompts]
    out = eng.run()
    return eng, prompts, [out[r] for r in rids]


def test_engine_serves_the_references_tokens_with_slots_reused(brumby,
                                                               served):
    """Five requests on two slots: a reused slot's state is overwritten by
    its next prefill (nothing clears it in between), or the third, fourth
    and fifth requests would read their predecessors' context."""
    gates, _, _, reference = brumby
    eng, prompts, outs = served
    assert all(len(t) == 12 for t in outs)
    assert _gaps(reference, prompts, outs).max() < TOL[gates]
    state = jax.tree.leaves(eng.slot_state)
    assert len(state) == 6 and all(np.asarray(a).any() for a in state)


def test_an_engine_without_a_pool_admits_by_slot_and_says_so(brumby, served):
    """No page is counted, claimed or freed: ``kv_bytes_per_token`` 0,
    ``paged_layers`` 0 (which tells "no pages" from "pages unused"), no
    device array of pages, tables that stay at 0; a prompt of any length
    under ``max_len`` is admitted as soon as a slot is free; ``build_log``
    says ``pages: none`` beside the state's path."""
    eng = served[0]
    stats = eng.stats()
    assert eng.pools == [] and not eng.tables.any()
    assert stats["kv_bytes_per_token"] == 0 and stats["paged_layers"] == 0
    assert stats["free_pages"] == 0 and stats["active"] == 0
    assert stats["preemptions"] == 0
    per_layer = 2 * (9 * 16 * 16 + 16 * 16) * 4    # 9 tiles; z in 16 rows
    assert stats["slot_state_bytes"] == 2 * 3 * per_layer
    rows = [r for r in eng.build_log if r["name"] in ("prefill_paged", "run")]
    assert {r["name"] for r in rows} == {"prefill_paged", "run"}
    assert all(r["state_path"] == "xla" and r["pages"] == "none"
               for r in rows)
    assert all("pages" not in r for r in eng.build_log if r not in rows)
    # three requests wait for two slots: admitted by a free slot alone
    model = brumby[2]
    eng = _engine(model)
    for n in (150, 100, 120):
        eng.submit(_ids(n, n), max_new_tokens=4)
    eng.step()
    assert (eng.stats()["active"], eng.stats()["queued"]) == (2, 1)
    assert len(eng.run()) == 3
    # a paged model still says how many of its layers keep pages
    paged = _engine(HybridForCausalLM(HybridConfig.tiny(pattern="p-*-")),
                    num_pages=8)
    assert paged.stats()["paged_layers"] == 1
    assert paged.stats()["free_pages"] == 8
    assert paged.stats()["kv_bytes_per_token"] > 0


@pytest.mark.parametrize("mode", [dict(chunked_prefill=True),
                                  dict(prefix_cache=True), dict(spec_k=2)])
def test_what_needs_a_snapshot_of_the_state_is_refused_by_name(brumby, mode):
    model = brumby[2]
    with pytest.raises(ValueError, match=next(iter(mode))):
        _engine(model, **mode)
    with pytest.raises(ValueError, match="per-slot state"):
        _engine(model).serialize_pages(0)


def test_the_build_log_says_which_form_the_recurrence_took(monkeypatch):
    """On a TPU the cell's shapes take both kernels (128-lane tiles, five a
    group, chunks of 128); a head size of 16 or chunks of 16 take the twins;
    a model without a stateful layer says nothing."""
    from paddle_tpu.ops import registry
    with open(CONFIG) as f:
        cfg = json.load(f)
    model, _ = program.build_model(cfg)
    assert model.state_path(None, 32) == model.state_path(4096, 1) == "xla"
    monkeypatch.setattr(registry, "backend_kind", lambda: "tpu")
    assert model.state_path(None, 32) == "kernel"
    assert all(model.state_path(b, 1) == "kernel"
               for b in range(512, 4097, 256))
    tiny, _ = program.build_model(tiny_config())
    assert tiny.state_path(None, 2) == tiny.state_path(64, 1) == "xla"
    plain = HybridForCausalLM(HybridConfig.tiny(pattern="*-"))
    assert plain.state_path(None, 2) is None


def test_the_gauges_at_the_published_sizes():
    """What the engine would keep for one 5-layer stage of Brumby-14B-Base
    at 32 slots, from shapes alone (nothing is allocated): no pool, 10
    state leaves, 5,499,781,120 B of them as laid out (65 tiles and 72
    rows of normaliser a head; the mathematics' 8,256 rows are
    5,452,922,880 B), 3,207,594,240 parameters held."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    model, _ = program.build_model(cfg)
    assert model.cfg.kinds == ref.pattern(cfg) == "p-p-p-p-p-"
    assert model.cfg.rope_theta == 1e6 and model.cfg.chunk_size == 128
    pools, _ = jax.eval_shape(lambda: model.alloc_paged_caches(1, 256, 128))
    assert pools == []
    state = jax.eval_shape(lambda: model.alloc_slot_state(32))
    assert len(state) == 5
    assert [a.shape for a in state[0]] == [(32, 8, 65, 128, 128),
                                           (32, 8, 72, 128)]
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(state))
    assert held == 32 * 5 * 8 * (65 * 128 * 128 + 72 * 128) * 4 == 5_499_781_120
    assert 32 * 5 * families.of(cfg).slot_state_bytes(cfg) == 5_452_922_880
    assert sum(int(np.prod(p.value.shape))
               for _, p in model.named_parameters()) == (
        5 * 330_352_896 + 2 * 777_912_320 + 5120) == 3_207_594_240


def test_a_pattern_may_mix_retention_with_the_other_kinds():
    """``p`` beside a paged attention layer, a Mamba layer and experts: the
    state leaves follow the pattern's order, the pool serves ``*`` alone,
    and prefill-then-decode equals the whole-sequence forward."""
    import paddle_tpu as pt
    pt.seed(3)
    model = HybridForCausalLM(HybridConfig.tiny(pattern="p-*Mp", head_dim=16,
                                                num_attention_heads=10,
                                                num_key_value_heads=2)).eval()
    ids = _ids(30, 4)
    want = np.asarray(model(jnp.asarray(ids)[None]))[0]
    eng = ContinuousBatchingEngine(
        model, generation_config=GenerationConfig(do_sample=False),
        max_batch=2, max_len=64, page_size=16)
    assert eng.stats()["paged_layers"] == 1 and len(eng.slot_state) == 3
    rid = eng.submit(ids[:20], max_new_tokens=8)
    toks = eng.run()[rid]
    full = np.asarray(model(jnp.asarray(np.concatenate([ids[:20], toks]))[None]))[0]
    assert (full[19:27].argmax(-1) == toks).all()
    assert np.isfinite(want).all()
