"""Context-aware dense/paged attention dispatch in the serving engine
(VERDICT r05 weak #5): each dispatched decode block picks its attention
path from the batch's max projected context length vs the measured
crossover (TuneDB-backed default in ops/pallas/autotune.py). These tests
pin the no-regression story: contexts under an explicit crossover route
DENSE and outputs are bit-identical to the forced-paged schedule
(exactness must not depend on the path choice), the crossover knob
actually flips the choice, and the default gives an engine one decode
executable."""

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.inference import ContinuousBatchingEngine, GenerationConfig
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

PAGE = 8


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    return LlamaForCausalLM(LlamaConfig.tiny())


def _run(model, crossover, new_tokens=6):
    rs = np.random.RandomState(7)
    vocab = model.cfg.vocab_size
    prompts = [rs.randint(0, vocab, (n,)).astype(np.int32)
               for n in (5, 9, 4)]
    eng = ContinuousBatchingEngine(
        model, max_batch=2, page_size=PAGE, max_len=32,
        generation_config=GenerationConfig(max_new_tokens=new_tokens,
                                           do_sample=False),
        decode_block=2, attn_crossover=crossover)
    rids = [eng.submit(p) for p in prompts]
    out = eng.run()
    return {r: out[r].tolist() for r in rids}, eng


def test_short_context_routes_dense_no_regression(model):
    """Contexts far below the crossover must pick the dense path on every
    tick — and produce exactly the tokens the forced-paged engine does
    (the short-context no-regression contract)."""
    out_auto, eng_auto = _run(model, crossover=10 ** 6)   # always dense
    out_paged, eng_paged = _run(model, crossover=0)       # always paged
    assert eng_auto.attn_path_ticks["paged"] == 0
    assert eng_auto.attn_path_ticks["dense"] > 0
    assert eng_paged.attn_path_ticks["dense"] == 0
    assert eng_paged.attn_path_ticks["paged"] > 0
    assert out_auto == out_paged


def test_default_crossover_from_tunedb_default(model):
    """With no explicit knob the engine consults the autotune default. On
    v5e the Pallas kernel was ahead at every context measured (256-8192),
    so the default is 0, below any context a tick can have: every tick is
    paged (off the TPU that path still computes with the XLA fallback)."""
    from paddle_tpu.ops.pallas.autotune import paged_decode_crossover
    assert paged_decode_crossover() == 0
    rs = np.random.RandomState(3)
    eng = ContinuousBatchingEngine(
        model, max_batch=2, page_size=PAGE, max_len=32,
        generation_config=GenerationConfig(max_new_tokens=4,
                                           do_sample=False),
        decode_block=2)
    assert eng.attn_crossover == 0
    eng.submit(rs.randint(0, model.cfg.vocab_size, (6,)).astype(np.int32))
    eng.run()
    assert eng.attn_path_ticks["dense"] == 0
    assert eng.attn_path_ticks["paged"] > 0


def test_one_decode_executable_from_short_to_near_max_len(model):
    """The benchmark's engine (max_len = 16 pages, every other knob at its
    default) must resolve exactly ONE decode executable whatever the
    contexts of a tick: a second one would be met first inside a measured
    window (a quiet tick with one short prompt, or a batch near max_len)
    and compile there. Contexts here run from 3 tokens alone in the batch
    to two rows within a token of max_len."""
    max_len = 16 * PAGE
    rs = np.random.RandomState(11)
    eng = ContinuousBatchingEngine(
        model, max_batch=4, page_size=PAGE, max_len=max_len,
        generation_config=GenerationConfig(max_new_tokens=3,
                                           do_sample=False))

    def serve(*lengths):
        for n in lengths:
            eng.submit(rs.randint(0, model.cfg.vocab_size, (n,))
                       .astype(np.int32))
        eng.run()

    serve(3)                                   # one short prompt, alone
    serve(max_len - 4, max_len - 3, 5, 40)     # near max_len beside short
    serve(max_len // 2)
    assert len(eng._decode_fns) == 1, list(eng._decode_fns)
    ticks = eng.attn_path_ticks
    assert sorted(ticks.values())[0] == 0 and sum(ticks.values()) > 6, ticks
    assert eng.stats()["attn_dense_ticks"] == ticks["dense"]
    assert eng.stats()["attn_paged_ticks"] == ticks["paged"]


def test_crossover_flips_mid_request(model):
    """A request whose context GROWS past the crossover flips from dense
    to paged between blocks — both path executables coexist and the output
    stays exact (parity with the always-paged engine)."""
    out_flip, eng_flip = _run(model, crossover=12, new_tokens=8)
    out_paged, _ = _run(model, crossover=0, new_tokens=8)
    assert eng_flip.attn_path_ticks["dense"] > 0
    assert eng_flip.attn_path_ticks["paged"] > 0
    assert out_flip == out_paged
