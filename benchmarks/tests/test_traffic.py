import collections
import json

import numpy as np
import pytest

from benchmarks import traffic

MIXES = ["batch-decode", "short-answers", "chat-steady", "chat-bursty",
         "long-prompts", "sessions", "test-closed", "test-open"]


def _key(s):
    return [(r.idx, r.prompt.tobytes(), r.out_len, r.due_s, r.client)
            for r in s.requests]


@pytest.mark.parametrize("name", MIXES)
def test_generator_is_a_pure_function_of_file_and_seed(name):
    mix = traffic.load(name)
    a = traffic.serving_schedule(mix, 2**31 + 12345, 20, 1000, 8192)
    b = traffic.serving_schedule(json.loads(json.dumps(mix)), 2**31 + 12345,
                                 20, 1000, 8192)
    assert _key(a) == _key(b)
    c = traffic.serving_schedule(mix, 2**31 + 12346, 20, 1000, 8192)
    assert _key(a) != _key(c)


@pytest.mark.parametrize("name", MIXES)
def test_two_seeds_same_multiset_other_order(name):
    mix = traffic.load(name)
    a = traffic.serving_schedule(mix, 1, 30, 1000, 8192)
    b = traffic.serving_schedule(mix, 2, 30, 1000, 8192)
    # closed loop: the first outputs are staggered, so leave out their blocks
    block = mix.get("stratify_block") or len(a.requests)
    skip = -(-a.clients // block) * block
    for field, start in ((lambda r: len(r.prompt), 0), (lambda r: r.out_len, skip)):
        va = [field(r) for r in a.requests[start:]]
        vb = [field(r) for r in b.requests[start:]]
        assert collections.Counter(va) == collections.Counter(vb)
    assert [len(r.prompt) for r in a.requests] != [len(r.prompt)
                                                   for r in b.requests]
    if a.loop == "open":
        ga = np.diff([r.due_s for r in a.requests])
        gb = np.diff([r.due_s for r in b.requests])
        assert len(ga) == len(gb)
        # the same span of time, so the same offered rate, for every seed
        assert a.requests[-1].due_s == pytest.approx(b.requests[-1].due_s,
                                                     rel=0.02)


def test_stratified_takes_midpoints_of_equal_slices():
    vals = traffic.stratified({"dist": "uniform", "lo": 0, "hi": 100}, 4,
                              traffic.rng_for(0, "t"), integer=False)
    assert sorted(vals) == pytest.approx([12.5, 37.5, 62.5, 87.5])
    lg = traffic.stratified({"dist": "loguniform", "lo": 32, "hi": 256}, 1000,
                            traffic.rng_for(0, "t"))
    # mean of a log-uniform on [32, 256]: (256 - 32) / ln 8 = 107.7
    assert np.mean(lg) == pytest.approx(107.7, rel=0.01)


@pytest.mark.parametrize("shape", [0.25, 1.0, 4.0])
def test_gamma_gaps_have_their_mean_and_burstiness(shape):
    g = traffic.stratified({"dist": "gamma", "shape": shape, "mean": 0.25},
                           2000, traffic.rng_for(0, "g"), integer=False)
    assert g.mean() == pytest.approx(0.25, rel=0.01)
    assert g.std() / g.mean() == pytest.approx(shape ** -0.5, rel=0.05)


def test_sessions_share_prefixes_and_replay_repeats_prompts():
    s = traffic.serving_schedule(traffic.load("sessions"), 3, 30, 1000, 8192)
    fams = traffic.load("sessions")["sessions"]["families"]
    a, b = s.requests[0], s.requests[fams]        # same family
    k = int(min(len(a.prompt), len(b.prompt)) * 0.5)
    assert (a.prompt[:k] == b.prompt[:k]).all()
    r = traffic.serving_schedule(traffic.load("long-prompts"), 3, 30, 1000, 8192)
    counts = collections.Counter(q.prompt.tobytes() for q in r.requests)
    assert max(counts.values()) == traffic.load("long-prompts")["replay"]["times"]


def test_closed_loop_first_outputs_are_staggered():
    s = traffic.serving_schedule(traffic.load("batch-decode"), 5, 45, 1000, 2048)
    first = [r.out_len for r in s.requests[:s.clients]]
    later = [r.out_len for r in s.requests[s.clients:2 * s.clients]]
    assert np.mean(first) < 0.7 * np.mean(later)
    assert [r.client for r in s.requests[:s.clients]] == list(range(s.clients))


def test_traffic_that_does_not_fit_the_engine_is_refused():
    with pytest.raises(ValueError):
        traffic.serving_schedule(traffic.load("long-prompts"), 1, 10, 1000, 2048)


@pytest.mark.parametrize("name", ["pretrain-4k", "pretrain-packed-8k", "test-train"])
def test_training_rows_depend_on_seed_and_row_only(name):
    mix = traffic.load(name)
    a = traffic.training_rows(mix, 9, 0, 4, 500)
    b = traffic.training_rows(mix, 9, 2, 2, 500)
    assert (a["input_ids"][2:] == b["input_ids"]).all()
    assert (a["input_ids"][:, 1:] == a["labels"][:, :-1]).all() or "segment_ids" in a
    assert len({r.tobytes() for r in a["input_ids"]}) == 4      # rows all differ
    many = traffic.training_rows(dict(mix, seq_len=8), 9, 0, 64, 500)["input_ids"]
    assert len({r.tobytes() for r in many}) == 64       # row 12 is not row 21
    assert a["input_ids"].shape == (4, mix["seq_len"])
    if "segment_ids" in a:
        seg, pos = a["segment_ids"], a["position_ids"]
        assert (pos[seg != np.roll(seg, 1, axis=1)][1:] == 0).all()
        assert (a["labels"] == -100).sum() > 0
