"""Token-level speculative decoding (ISSUE 6): draft → verify → commit
inside the continuous-batching engine.

The engine (``spec_k=k``) drafts k tokens per tick from the slot's own
history (n-gram prompt-lookup, ``DraftProvider``), verifies all k in one
(k+1)-wide forward against the paged KV cache, and commits the agreeing
prefix — accept/reject folds into the same ``decode_stop_update`` carry
that already self-masks retired slots, so the depth-2 in-flight window
survives and nothing ever rolls back. These tests pin the safety story:

* spec-on ≡ spec-off token-for-token (greedy AND sampled — acceptance
  reuses the per-(seed, rid, token_index) keys, so the committed stream
  IS the non-speculative stream);
* ``spec_k=0`` is characterization-identical to the current engine;
* eos / budget landing inside an accepted run truncates on device, with
  a speculative next block already in flight;
* multi-token drains divide the ITL interval per token (k=1 pinned);
* acceptance counters/gauges move through the metrics registry.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.inference import (ContinuousBatchingEngine, DraftProvider,
                                  GenerationConfig, NgramDraftProvider)
from paddle_tpu.inference.generation import generate_scan
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.serving_core import ServingCore

PAGE = 8


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    return LlamaForCausalLM(LlamaConfig.tiny())


def _ref_greedy(model, prompt, new_tokens):
    gc = GenerationConfig(max_new_tokens=new_tokens, do_sample=False)
    out = generate_scan(model, jnp.asarray(prompt)[None, :], gc)
    return np.asarray(out)[0, len(prompt):]


def _mk_prompt(rs, n, vocab):
    return rs.randint(0, vocab, (n,)).astype(np.int32)


def _rep_prompt(rs, n, vocab, period=3):
    """Repetitive prompt: the n-gram drafter's best case (and the greedy
    continuation of a tiny model on it tends to loop too)."""
    base = rs.randint(0, vocab, (period,)).astype(np.int32)
    return np.tile(base, -(-n // period))[:n]


def _mixed_run(model, spec_k, depth=2, *, num_pages=None, max_batch=2,
               new_tokens=8, seed=31):
    """4 mixed greedy/sampled, repetitive/random requests through
    ``max_batch`` slots."""
    rs = np.random.RandomState(seed)
    vocab = model.cfg.vocab_size
    prompts = [_rep_prompt(rs, 10, vocab), _mk_prompt(rs, 9, vocab),
               _rep_prompt(rs, 7, vocab), _mk_prompt(rs, 5, vocab)]
    eng = ContinuousBatchingEngine(
        model, max_batch=max_batch, page_size=PAGE, max_len=64,
        num_pages=num_pages,
        generation_config=GenerationConfig(max_new_tokens=new_tokens,
                                           do_sample=False),
        async_depth=depth, spec_k=spec_k)
    sgc = GenerationConfig(max_new_tokens=new_tokens, do_sample=True,
                           temperature=0.9, top_k=20)
    rids = [eng.submit(p, generation_config=sgc if i % 2 else None)
            for i, p in enumerate(prompts)]
    out = eng.run()
    return {i: out[r].tolist() for i, r in enumerate(rids)}, eng, prompts


# --- parity: spec-on ≡ spec-off, greedy and sampled -------------------------

def test_spec_greedy_matches_generate_scan_and_drafts_accepted(model):
    """Repetitive prompt: the speculative engine must be token-identical
    to generate_scan AND actually accept drafts (the speedup exists)."""
    rs = np.random.RandomState(0)
    prompt = _rep_prompt(rs, 12, model.cfg.vocab_size, period=4)
    ref = _ref_greedy(model, prompt, 12)
    eng = ContinuousBatchingEngine(
        model, max_batch=2, page_size=PAGE, max_len=64,
        generation_config=GenerationConfig(max_new_tokens=12,
                                           do_sample=False),
        spec_k=3)
    rid = eng.submit(prompt)
    out = eng.run()
    np.testing.assert_array_equal(out[rid], ref)
    st = eng.spec_stats()
    assert st["spec_tokens_proposed"] > 0
    assert st["spec_tokens_accepted"] > 0          # drafts really accept
    assert st["spec_mean_accepted_len"] > 1.0


def test_spec_on_off_identical_mixed_batch(model):
    """spec_k in {2, 3} × depth in {1, 2}: every stream (greedy AND
    sampled) token-identical to the non-speculative engine — acceptance
    reuses the per-(seed, rid, token_index) keys, so speculation can
    change WHEN tokens commit but never WHICH."""
    ref, _, prompts = _mixed_run(model, spec_k=0, depth=1)
    for spec_k in (2, 3):
        for depth in (1, 2):
            got, eng, _ = _mixed_run(model, spec_k=spec_k, depth=depth)
            assert got == ref, (spec_k, depth)
    for i in (0, 2):                               # the greedy rows
        np.testing.assert_array_equal(np.asarray(ref[i]),
                                      _ref_greedy(model, prompts[i], 8))


def test_spec_k0_characterization(model):
    """spec_k=0 must be EXACTLY today's engine: same outputs, same
    preemption count on a tight pool, and none of the speculative
    machinery allocated."""
    base, beng, _ = _mixed_run(model, spec_k=0, depth=2, num_pages=6,
                               max_batch=3, new_tokens=PAGE + 3)
    eng = ContinuousBatchingEngine(model, max_batch=3, page_size=PAGE,
                                   max_len=64)
    assert eng.spec_k == 0 and eng._hist is None and eng._draft is None
    assert eng.spec_stats() == {}
    got, geng, _ = _mixed_run(model, spec_k=0, depth=2, num_pages=6,
                              max_batch=3, new_tokens=PAGE + 3)
    assert got == base
    assert geng.preemptions == beng.preemptions
    assert "spec_tokens_proposed" not in geng.stats()


def test_spec_with_preemption_replay(model):
    """Tight pool forces recompute-preemption mid-speculation: the
    replayed request re-uploads its history and every stream stays
    exact; the allocator ends balanced."""
    ref, _, _ = _mixed_run(model, spec_k=0, depth=1, max_batch=3,
                           new_tokens=PAGE + 3)
    got, eng, _ = _mixed_run(model, spec_k=3, depth=2, num_pages=6,
                             max_batch=3, new_tokens=PAGE + 3)
    assert got == ref
    assert eng.preemptions >= 1
    assert eng.stats()["free_pages"] == 6
    assert eng.stats()["inflight"] == 0


# --- eos / budget inside an accepted run ------------------------------------

def test_eos_inside_accepted_prefix_with_block_in_flight(model):
    """eos lands INSIDE an accepted speculative run while the next block
    is already dispatched: tokens past the stop are dropped on device,
    every page returns to the pool (KV unreachable), and the slot is
    immediately reusable for an exact fresh request."""
    rs = np.random.RandomState(3)
    prompt = _rep_prompt(rs, 12, model.cfg.vocab_size, period=4)
    ref = _ref_greedy(model, prompt, 10)
    eos = int(ref[4])                   # stops mid accepted run (k=3)
    eng = ContinuousBatchingEngine(
        model, max_batch=1, page_size=PAGE, max_len=64,
        generation_config=GenerationConfig(max_new_tokens=10,
                                           do_sample=False,
                                           eos_token_id=eos),
        async_depth=2, spec_k=3)
    rid = eng.submit(prompt)
    free0 = eng.stats()["free_pages"]
    emitted = []
    eng._admit()
    assert eng._dispatch_block(emitted)            # verify block 1
    assert eng._dispatch_block(emitted)            # block 2, SPECULATIVE
    assert eng.stats()["inflight"] == 2
    out = eng.run()
    stop = int(np.where(ref == eos)[0][0])
    np.testing.assert_array_equal(out[rid], ref[:stop + 1])
    assert eng.stats()["free_pages"] == free0 == eng._total_pages
    assert not eng.tables.any()
    p2 = _mk_prompt(rs, 6, model.cfg.vocab_size)
    rid2 = eng.submit(p2)
    out2 = eng.run()
    np.testing.assert_array_equal(out2[rid2], _ref_greedy(model, p2, 10))


def test_budget_exhaustion_inside_accepted_prefix(model):
    """max_new_tokens NOT a multiple of the spec stride: the budget runs
    out mid-accepted-run and the device must truncate — no over-budget
    tokens, exact prefix of the reference, pool balanced, with the
    depth-2 window keeping a speculative block in flight throughout."""
    rs = np.random.RandomState(7)
    prompt = _rep_prompt(rs, 10, model.cfg.vocab_size)
    ref = _ref_greedy(model, prompt, 11)
    for new in (1, 2, 5, 7, 11):
        eng = ContinuousBatchingEngine(
            model, max_batch=1, page_size=PAGE, max_len=64,
            generation_config=GenerationConfig(max_new_tokens=new,
                                               do_sample=False),
            async_depth=2, spec_k=3)
        rid = eng.submit(prompt)
        out = eng.run()
        assert len(out[rid]) == new                # never over budget
        np.testing.assert_array_equal(out[rid], ref[:new])
        assert eng.stats()["free_pages"] == eng._total_pages


def test_projection_saturation_does_not_orphan_commits(model):
    """Regression (review find): the max-stride projection saturates a
    slot's budget while the device — committing fewer than the stride —
    is still decoding its row. The slot must STAY a participant (it is
    excluded only when the MINIMUM possible commits exhaust the budget),
    or blocks dispatched for its peers would carry device commits the
    drain never reads. Heterogeneous budgets make the window
    deterministic: r0's projection saturates after two dispatches while
    r1 keeps the pipeline full."""
    rs = np.random.RandomState(17)
    vocab = model.cfg.vocab_size
    p0, p1 = _mk_prompt(rs, 6, vocab), _mk_prompt(rs, 7, vocab)
    eng = ContinuousBatchingEngine(
        model, max_batch=2, page_size=PAGE, max_len=64,
        generation_config=GenerationConfig(max_new_tokens=4,
                                           do_sample=False),
        async_depth=2, spec_k=2)
    r0 = eng.submit(p0, max_new_tokens=4)
    r1 = eng.submit(p1, max_new_tokens=12)
    emitted = []
    eng._admit()
    slot0 = eng._requests[r0].slot
    # stack dispatches without draining: r0's projection saturates (3+1)
    # while its device row has committed at most 2 tokens
    assert eng._dispatch_block(emitted)
    assert eng._dispatch_block(emitted)
    assert int(eng._proj_gen[slot0]) >= 4      # projection saturated...
    assert eng._dispatch_block(emitted)        # ...but block 3 must
    parts3 = {s for s, _ in eng._inflight[-1].participants}
    assert slot0 in parts3                     # still carry r0
    out = eng.run()
    np.testing.assert_array_equal(out[r0], _ref_greedy(model, p0, 4))
    np.testing.assert_array_equal(out[r1], _ref_greedy(model, p1, 12))
    assert eng.stats()["free_pages"] == eng._total_pages


# --- determinism per seed ---------------------------------------------------

def test_spec_sampled_determinism_per_seed_across_depths(model):
    """Sampled streams with speculation ON are a pure function of
    (seed, rid, token index): depth 1 ≡ depth 2 ≡ depth 3, and repeat
    runs reproduce — the ISSUE 6 determinism contract."""
    runs = [_mixed_run(model, spec_k=3, depth=d)[0] for d in (1, 2, 3, 2)]
    assert runs[0] == runs[1] == runs[2] == runs[3]


# --- draft provider ---------------------------------------------------------

def test_ngram_provider_proposes_continuation():
    """Direct contract check: the trailing n-gram's PRIOR occurrence's
    continuation is proposed; rows with no match fall back to repeating
    the last token."""
    prov = NgramDraftProvider(max_ngram=3, min_ngram=1)
    hist = jnp.asarray([[5, 6, 7, 9, 5, 6, 0, 0],     # ...5 6 → 7 9 5
                        [1, 2, 3, 4, 9, 9, 9, 0]])    # no repeat → 9 9 9
    out = np.asarray(prov.propose(hist, jnp.asarray([6, 7]), 3))
    np.testing.assert_array_equal(out[0], [7, 9, 5])
    np.testing.assert_array_equal(out[1], [9, 9, 9])


def test_custom_draft_provider_wrong_drafts_are_safe(model):
    """A provider proposing garbage must cost only speed, never
    correctness: outputs stay identical to the non-speculative engine
    with (near-)zero acceptance."""
    class Adversarial(DraftProvider):
        def propose(self, history, hist_len, k):
            B = history.shape[0]
            # constant wrong-ish tokens (vocab-1), never the greedy pick
            return jnp.full((B, k), history.shape[1] % 7 + 1, jnp.int32)

    rs = np.random.RandomState(11)
    prompt = _rep_prompt(rs, 9, model.cfg.vocab_size)
    ref = _ref_greedy(model, prompt, 10)
    eng = ContinuousBatchingEngine(
        model, max_batch=1, page_size=PAGE, max_len=64,
        generation_config=GenerationConfig(max_new_tokens=10,
                                           do_sample=False),
        spec_k=3, draft_provider=Adversarial())
    rid = eng.submit(prompt)
    out = eng.run()
    np.testing.assert_array_equal(out[rid], ref)


def test_spec_rejects_model_without_verify(model):
    class NoVerify(ServingCore):
        pass

    class M:
        model = NoVerify()
    with pytest.raises(ValueError, match="decode_verify_paged"):
        ContinuousBatchingEngine(M(), max_batch=1, page_size=PAGE,
                                 max_len=32, spec_k=2)


# --- ITL stamping for multi-token drains (satellite) ------------------------

def test_itl_k1_path_pinned_one_gap_per_tick(model):
    """decode_block=1, spec off: the per-tick ITL stamping is unchanged —
    a request emitting n tokens one per tick records exactly n-1 gaps."""
    rs = np.random.RandomState(5)
    prompt = _mk_prompt(rs, 5, model.cfg.vocab_size)
    eng = ContinuousBatchingEngine(
        model, max_batch=1, page_size=PAGE, max_len=64,
        generation_config=GenerationConfig(max_new_tokens=6,
                                           do_sample=False),
        decode_block=1)
    eng.submit(prompt)
    eng.run()
    assert len(eng._itl_gaps) == 5


def test_itl_divided_across_multi_token_drains(model):
    """decode_block=4: a drain delivering 4 tokens contributes 4 equal
    per-token gaps (old behavior: ONE outsized per-tick gap), so ITL
    percentiles describe tokens, not ticks."""
    rs = np.random.RandomState(5)
    prompt = _mk_prompt(rs, 5, model.cfg.vocab_size)
    eng = ContinuousBatchingEngine(
        model, max_batch=1, page_size=PAGE, max_len=64,
        generation_config=GenerationConfig(max_new_tokens=8,
                                           do_sample=False),
        decode_block=4)
    eng.submit(prompt)
    eng.run()
    # two 4-token drains: the second contributes 4 equal gaps
    gaps = list(eng._itl_gaps)
    assert len(gaps) == 4
    assert max(gaps) - min(gaps) < 1e-12           # equal shares


# --- observability ----------------------------------------------------------

def test_spec_metrics_published_through_registry(model):
    from paddle_tpu.observability.metrics import REGISTRY
    rs = np.random.RandomState(2)
    prompt = _rep_prompt(rs, 12, model.cfg.vocab_size, period=4)
    eng = ContinuousBatchingEngine(
        model, max_batch=1, page_size=PAGE, max_len=64,
        generation_config=GenerationConfig(max_new_tokens=10,
                                           do_sample=False),
        spec_k=3)
    eng.submit(prompt)
    was = REGISTRY.enabled
    REGISTRY.enable()
    try:
        eng.run()
        snap = {e["name"]: e for e in REGISTRY.collect()}
    finally:
        REGISTRY.enabled = was
    assert snap["pt_spec_tokens_proposed_total"]["value"] > 0
    assert snap["pt_spec_tokens_accepted_total"]["value"] > 0
    assert snap["pt_spec_accept_rate"]["value"] > 0
    assert snap["pt_spec_mean_accepted_len"]["value"] > 1.0
