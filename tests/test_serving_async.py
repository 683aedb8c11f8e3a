"""Async continuous-batching engine (ISSUE 3): pipelined dispatch with
on-device stop detection.

The decode scan carries per-slot eos ids + remaining budgets and returns
done flags, so the host dispatches block N+1 without block N's tokens
(bounded in-flight window, ``async_depth``). These tests pin the safety
story: depth>1 is token-identical to the synchronous depth-1 schedule for
mixed greedy/sampled batches, an eos landing mid-block while a
speculative next block is in flight drops every token past the stop and
leaves its KV unreachable, and page exhaustion with a dispatch
outstanding drains the pipeline before anyone is evicted.
"""

import glob
import json
from collections import deque

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import profiler
from paddle_tpu.inference import ContinuousBatchingEngine, GenerationConfig
from paddle_tpu.inference.generation import generate_scan
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

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


def _mixed_run(model, depth, *, decode_block=1, num_pages=None,
               max_batch=2, new_tokens=6):
    """4 mixed greedy/sampled requests through ``max_batch`` slots."""
    rs = np.random.RandomState(31)
    vocab = model.cfg.vocab_size
    prompts = [_mk_prompt(rs, n, vocab) for n in (5, 9, 4, 7)]
    eng = ContinuousBatchingEngine(
        model, max_batch=max_batch, page_size=PAGE, max_len=64,
        num_pages=num_pages,
        generation_config=GenerationConfig(max_new_tokens=new_tokens,
                                           do_sample=False),
        decode_block=decode_block, async_depth=depth)
    sgc = GenerationConfig(max_new_tokens=new_tokens, do_sample=True,
                           temperature=0.9, top_k=20)
    rids = [eng.submit(p, generation_config=sgc if i % 2 else None)
            for i, p in enumerate(prompts)]
    out = eng.run()
    return {i: out[r].tolist() for i, r in enumerate(rids)}, eng, prompts


# --- depth parity (satellite: CI assertion async == sync) ------------------

def test_depth2_token_identical_to_depth1_mixed_batch(model):
    """The pipelined engine must be bit-identical to its synchronous
    (depth-1) schedule for greedy AND sampled rows: sampling keys fold
    from (seed, request id, token index), never from the dispatch
    schedule. Greedy rows additionally match generate_scan."""
    ref, _, prompts = _mixed_run(model, depth=1)
    got, eng, _ = _mixed_run(model, depth=2)
    assert got == ref
    assert eng.async_depth == 2
    for i in (0, 2):       # the greedy rows
        np.testing.assert_array_equal(np.asarray(ref[i]),
                                      _ref_greedy(model, prompts[i], 6))


def test_queue_is_a_deque(model):
    eng = ContinuousBatchingEngine(model, max_batch=1, page_size=PAGE,
                                   max_len=32)
    assert isinstance(eng._queue, deque)


@pytest.mark.slow
def test_depth_parity_matrix(model):
    """Depth 1/2/3 × decode_block 1/4 × (roomy | preemption-tight pool):
    token-identical outputs everywhere; the tight pool must actually
    preempt at every depth."""
    for decode_block in (1, 4):
        for num_pages in (None, 6):
            runs = [_mixed_run(model, depth, decode_block=decode_block,
                               num_pages=num_pages, max_batch=3,
                               new_tokens=PAGE + 3)
                    for depth in (1, 2, 3)]
            base = runs[0][0]
            for got, eng, _ in runs[1:]:
                assert got == base, (decode_block, num_pages,
                                     eng.async_depth)
            if num_pages == 6:
                assert all(eng.preemptions >= 1 for _, eng, _ in runs)
            assert all(eng.stats()["free_pages"] ==
                       (eng._total_pages if num_pages is None else 6)
                       for _, eng, _ in runs)


@pytest.mark.slow
def test_depth1_characterization_vs_presync_engine(model):
    """Pinned against the pre-async engine (validated by running the git
    predecessor on this exact scenario): depth-1 must keep its outputs
    AND its preemption count — the async refactor may not change the
    synchronous schedule's eviction behavior."""
    rs = np.random.RandomState(9)
    vocab = model.cfg.vocab_size
    prompts = [_mk_prompt(rs, 8, vocab) for _ in range(3)]
    eng = ContinuousBatchingEngine(
        model, max_batch=3, page_size=PAGE, max_len=32, num_pages=7,
        generation_config=GenerationConfig(max_new_tokens=12,
                                           do_sample=False),
        decode_block=4, async_depth=1)
    rids = [eng.submit(p) for p in prompts]
    out = eng.run()
    assert eng.preemptions == 1          # the pre-async engine's count
    assert eng.stats()["free_pages"] == 7
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(out[rid],
                                      _ref_greedy(model, p, 12))


# --- eos mid-block with a speculative block in flight ----------------------

def test_eos_mid_block_with_speculative_block_in_flight(model):
    """eos lands mid-block-1 while speculative block 2 is already
    dispatched: every token past the stop is dropped, the slot's pages
    all return to the pool (KV unreachable), and the slot is immediately
    reusable for an exact fresh request."""
    rs = np.random.RandomState(40)
    prompt = _mk_prompt(rs, 5, model.cfg.vocab_size)
    ref = _ref_greedy(model, prompt, 8)
    eos = int(ref[2])                    # stop mid first 4-token block
    eng = ContinuousBatchingEngine(
        model, max_batch=1, page_size=PAGE, max_len=64,
        generation_config=GenerationConfig(max_new_tokens=8,
                                           do_sample=False,
                                           eos_token_id=eos),
        decode_block=4, async_depth=2)
    rid = eng.submit(prompt)
    free0 = eng.stats()["free_pages"]
    emitted = []
    eng._admit()
    assert eng._dispatch_block(emitted)          # block 1: tokens 0..3
    assert eng._dispatch_block(emitted)          # block 2, SPECULATIVE
    assert eng.stats()["inflight"] == 2          # issued before block 1
    out = eng.run()                              # drained anything
    np.testing.assert_array_equal(out[rid], ref[:3])
    # tokens past the stop (rest of block 1 + all of block 2) dropped;
    # the slot's table row is zeroed and every page is back in the pool,
    # so the kept AND speculative KV are both unreachable
    assert eng.stats()["free_pages"] == free0 == eng._total_pages
    assert not eng.tables.any()
    # slot reusable: a fresh request through the same slot stays exact
    p2 = _mk_prompt(rs, 6, model.cfg.vocab_size)
    rid2 = eng.submit(p2)
    out2 = eng.run()
    np.testing.assert_array_equal(out2[rid2], _ref_greedy(model, p2, 8))


# --- page exhaustion with a dispatch outstanding ---------------------------

def test_page_exhaustion_with_dispatch_outstanding(model):
    """The pool runs dry while speculative blocks are in flight: the
    engine must drain the window FIRST (pool_dry_drains), then fall back
    to recompute-preemption, and every request — including the evicted
    replay — must stay exact with the allocator balanced."""
    rs = np.random.RandomState(41)
    vocab = model.cfg.vocab_size
    p1, p2 = _mk_prompt(rs, 6, vocab), _mk_prompt(rs, 6, vocab)
    # each sequence spans 3 pages by completion (6 + 12 tokens); pool of
    # 5 cannot hold both, so the 6th claim lands on a dry pool
    eng = ContinuousBatchingEngine(
        model, max_batch=2, page_size=PAGE, max_len=32, num_pages=5,
        generation_config=GenerationConfig(max_new_tokens=12,
                                           do_sample=False),
        decode_block=2, async_depth=2)
    r1, r2 = eng.submit(p1), eng.submit(p2)
    emitted = []
    eng._admit()
    # stack dispatches without reconciling: the dry pool is guaranteed
    # to be hit with the window non-empty
    for _ in range(30):
        if not eng._dispatch_block(emitted):
            break
    out = eng.run()                      # finish + replay the evicted one
    assert eng.pool_dry_drains >= 1
    assert eng.preemptions >= 1
    np.testing.assert_array_equal(out[r1], _ref_greedy(model, p1, 12))
    np.testing.assert_array_equal(out[r2], _ref_greedy(model, p2, 12))
    assert eng.stats()["free_pages"] == 5
    assert eng.stats()["inflight"] == 0


# --- profiler: tick-level spans in the chrome trace ------------------------

def test_serving_spans_exported_to_chrome_trace(model, tmp_path):
    rs = np.random.RandomState(42)
    prompt = _mk_prompt(rs, 5, model.cfg.vocab_size)
    eng = ContinuousBatchingEngine(
        model, max_batch=1, page_size=PAGE, max_len=32,
        generation_config=GenerationConfig(max_new_tokens=4,
                                           do_sample=False),
        async_depth=2)
    with profiler.serving_trace(str(tmp_path)):
        eng.submit(prompt)
        eng.run()
    traces = glob.glob(str(tmp_path / "*.json"))
    assert traces
    with open(traces[0]) as f:
        events = {e["name"] for e in json.load(f)["traceEvents"]}
    missing = set(profiler.SERVING_EVENTS) - events
    assert not missing, f"spans absent from chrome trace: {missing}"


def test_cancel_with_a_block_in_flight_keeps_the_other_streams_emissions(
        model):
    """``cancel`` drains the blocks in flight outside ``step()``: the
    tokens the OTHER slots commit in that drain are emitted by the next
    ``step()``, so a consumer that streams by emission (the fabric's front
    door) sees every token of a stream once and none of the cancelled one."""
    rs = np.random.RandomState(5)
    vocab = model.cfg.vocab_size
    eng = ContinuousBatchingEngine(
        model, max_batch=2, page_size=PAGE, max_len=64,
        generation_config=GenerationConfig(max_new_tokens=12,
                                           do_sample=False),
        async_depth=2)
    # a block stays in flight after every step, however fast the device
    eng._block_ready = lambda blk: False
    gone = eng.submit(_mk_prompt(rs, 6, vocab))
    kept = eng.submit(_mk_prompt(rs, 7, vocab))
    streamed = {gone: [], kept: []}

    def step():
        for rid, tok in eng.step():
            streamed[rid].append(int(tok))

    while len(streamed[kept]) < 3:
        step()
    assert eng._inflight            # a dispatched block not yet drained
    before = len(streamed[gone])
    assert eng.cancel(gone)
    assert eng._held_emitted and all(r == kept for r, _ in eng._held_emitted)
    while eng.has_work():
        step()
    assert len(streamed[gone]) == before
    np.testing.assert_array_equal(streamed[kept],
                                  eng.take_finished()[kept])
    assert len(streamed[kept]) == 12
    # a stream cancelled next has no token held from the first cancel's drain
    a, b = (eng.submit(_mk_prompt(rs, n, vocab)) for n in (5, 6))
    streamed.update({a: [], b: []})
    while len(streamed[b]) < 2:
        step()
    assert eng._inflight and eng.cancel(a) and eng._held_emitted
    assert eng.cancel(b) and not eng._held_emitted and eng.step() == []
